"""Hugging Face Llama/Mistral/Gemma checkpoint importer.

Maps a `transformers` Llama, Mistral, or Gemma state dict (identical
key layout; Mistral adds sliding-window attention -> sliding_window;
Gemma adds GeGLU, norm weights stored as w-1, and sqrt(d) embedding
scaling -> act/norm_offset/embed_scale) onto this repo's param tree so
real released weights run through the TPU-native stack (training,
decode, serving) — and, just as importantly, gives the Llama
implementation a gold-standard external parity check: logits must match
HF's reference implementation (tests/test_import_hf.py pins it).

Conventions line up by construction:
  * our `_mm` computes x @ W with W [in, out]; torch Linear stores
    [out, in] -> every projection transposes on import;
  * our `_rope` is the half-split rotate_half formulation — the same
    one HF Llama uses — so Q/K rows need NO permutation;
  * our MLP is down(silu(gate(x)) * up(x)) with w1=gate, w3=up, w2=down.

Import is torch -> numpy -> jax host-side; nothing here touches the
device until the caller places the tree.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

from kubedl_tpu.models.llama import LlamaConfig, RopeScaling


def config_from_hf(hf_config, **overrides) -> LlamaConfig:
    """LlamaConfig from a transformers LlamaConfig."""
    import jax.numpy as jnp

    model_type = getattr(hf_config, "model_type", "llama")
    passes = int(getattr(hf_config, "total_ut_steps", 1) or 1)
    if passes > 1:
        raise ValueError(
            f"model_type {model_type!r} runs its stack total_ut_steps = "
            f"{passes} times with four norms a layer and an exit gate: no "
            "checkpoint mapping is written for it (LlamaConfig.ouro_2_6b "
            "trains it from seeded weights)")
    kinds = tuple(getattr(hf_config, "layer_types", None) or ())
    if "mamba" in kinds:
        raise ValueError(
            f"model_type {model_type!r} holds {kinds.count('mamba')} "
            "state-space (mamba) layers: no checkpoint mapping is written "
            "for their leaves (LlamaConfig.granite_4_0_h_micro trains the "
            "architecture from seeded weights)")
    if getattr(hf_config, "kv_lora_rank", None) or int(
            getattr(hf_config, "hc_mult", 1) or 1) > 1:
        raise ValueError(
            f"model_type {model_type!r} holds latent-attention (MLA) layers "
            f"(kv_lora_rank {getattr(hf_config, 'kv_lora_rank', None)}) or "
            f"several-stream layers (hyper-connections, hc_mult "
            f"{getattr(hf_config, 'hc_mult', 1)}): no checkpoint mapping is "
            "written for their leaves, nor for the rotary columns' "
            "interleaved layout (LlamaConfig.xing4_0_29b_a4b trains the "
            "architecture from seeded weights)")
    if model_type == "afmoe":
        raise ValueError(
            f"model_type {model_type!r} holds gated attention layers (the "
            "core's output times sigmoid(h wg) before o_proj) with RoPE in "
            "its sliding_attention layers and none in its full_attention "
            "ones, and routed experts beside a shared one: no checkpoint "
            "mapping is written for the gate's, the experts' or the four "
            "norms' leaves (LlamaConfig.trinity_large_preview trains the "
            "architecture from seeded weights)")
    if model_type not in ("llama", "mistral", "gemma", "gemma2", "qwen2"):
        raise ValueError(
            f"unsupported model_type {model_type!r} "
            f"(llama, mistral, gemma, gemma2, qwen2)")
    kw = dict(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=getattr(hf_config, "num_key_value_heads", None)
        or hf_config.num_attention_heads,
        d_ff=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        rms_eps=float(getattr(hf_config, "rms_norm_eps", 1e-5)),
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
        # HF uses sliding_window in {None, 0} to mean "disabled"
        sliding_window=(getattr(hf_config, "sliding_window", None) or None),
        dtype=jnp.bfloat16,
    )
    if model_type in ("gemma", "gemma2"):
        kw.update(
            act="gelu_tanh",
            norm_offset=1.0,  # HF stores RMSNorm weights as w - 1
            embed_scale=float(hf_config.hidden_size) ** 0.5,
        )
    if model_type == "gemma2":
        # Gemma-2: sandwich norms, attn/final logit softcapping, scores
        # scaled by query_pre_attn_scalar**-0.5, head_dim decoupled from
        # d_model/n_heads, and alternating local/global attention
        kw.update(
            post_block_norms=True,
            attn_logit_softcap=float(
                getattr(hf_config, "attn_logit_softcapping", 0.0) or 0.0),
            final_logit_softcap=float(
                getattr(hf_config, "final_logit_softcapping", 0.0) or 0.0),
            query_pre_attn_scalar=float(hf_config.query_pre_attn_scalar),
            head_dim_override=int(hf_config.head_dim),
        )
        kw["sliding_window"] = None
        w = getattr(hf_config, "sliding_window", None) or None
        if w is not None:
            layer_types = getattr(hf_config, "layer_types", None)
            if layer_types is not None:
                wins = tuple(int(w) if lt == "sliding_attention" else None
                             for lt in layer_types)
            else:
                # older transformers: sliding on even layers
                wins = tuple(int(w) if i % 2 == 0 else None
                             for i in range(hf_config.num_hidden_layers))
            if any(x is not None for x in wins):
                kw["layer_windows"] = wins
    if model_type == "qwen2":
        # Qwen2/2.5: biased q/k/v projections (o_proj and MLP bias-free);
        # the config always CARRIES a sliding_window value but the model
        # only applies it when use_sliding_window is set — and then only
        # to layers at or above max_window_layers, which maps onto
        # layer_windows (full attention below, windowed above)
        kw["attn_qkv_bias"] = True
        kw["sliding_window"] = None
        if getattr(hf_config, "use_sliding_window", False):
            # sliding_window None/0 both mean disabled in HF; and when
            # max_window_layers covers every layer no layer is actually
            # windowed — collapse both to plain full attention rather
            # than shipping an all-None layer_windows tuple that would
            # spuriously trip uniform-window-only paths (pipelined fwd)
            w = getattr(hf_config, "sliding_window", None) or None
            cut = int(getattr(hf_config, "max_window_layers",
                              hf_config.num_hidden_layers))
            if w is not None and cut < hf_config.num_hidden_layers:
                kw["layer_windows"] = tuple(
                    None if i < cut else int(w)
                    for i in range(hf_config.num_hidden_layers))

    # rope scaling: llama3 (Llama 3.1+) and linear interpolation map to
    # the native RopeScaling; others (dynamic/NTK, yarn) are refused —
    # importing them would produce degraded logits with exit 0
    scaling = getattr(hf_config, "rope_scaling", None)
    if scaling:
        rope_type = scaling.get("rope_type") or scaling.get("type")
        if rope_type in (None, "default"):
            pass
        elif rope_type == "llama3":
            # all four parameters are required: defaulting a missing
            # original_max_position_embeddings would rescale at the
            # wrong wavelength boundaries — degraded logits, exit 0
            missing = [k for k in ("factor", "low_freq_factor",
                                   "high_freq_factor",
                                   "original_max_position_embeddings")
                       if k not in scaling]
            if missing:
                raise ValueError(
                    f"rope_scaling llama3 is missing {missing} — refusing "
                    f"to guess frequency boundaries")
            kw["rope_scaling"] = RopeScaling(
                kind="llama3",
                factor=float(scaling["factor"]),
                low_freq_factor=float(scaling["low_freq_factor"]),
                high_freq_factor=float(scaling["high_freq_factor"]),
                original_max_position_embeddings=int(
                    scaling["original_max_position_embeddings"]),
            )
        elif rope_type == "linear":
            kw["rope_scaling"] = RopeScaling(
                kind="linear", factor=float(scaling["factor"]))
        else:
            raise ValueError(
                f"rope_scaling {scaling!r} not supported (default, llama3, "
                f"linear; dynamic/yarn aren't implemented)")
    kw.update(overrides)
    if getattr(hf_config, "attention_bias", False) or getattr(hf_config, "mlp_bias", False):
        raise ValueError("attention/mlp bias tensors not supported "
                         "(this stack's projections are bias-free)")
    cfg = LlamaConfig(**kw)
    cfg.require_plain_attention("the HF importer")
    expect_hd = hf_config.hidden_size // hf_config.num_attention_heads
    got_hd = getattr(hf_config, "head_dim", None) or expect_hd
    if cfg.head_dim != got_hd:
        raise ValueError(
            f"head_dim mismatch: ours {cfg.head_dim}, HF {got_hd} — "
            f"non-standard head_dim checkpoints aren't supported")
    return cfg


def params_from_state_dict(
    state_dict: Dict[str, Any], config: LlamaConfig
) -> Dict:
    """Our param tree from an HF Llama state dict (torch tensors or arrays)."""
    import jax.numpy as jnp

    config.require_plain_attention("the HF importer (params_from_state_dict)")

    def arr(key: str, transpose: bool = False):
        t = state_dict[key]
        if hasattr(t, "detach"):  # torch tensor
            t = t.detach().to("cpu").float().numpy()
        a = np.asarray(t, np.float32)
        if transpose:
            a = a.T
        return a

    def cast(a):
        return jnp.asarray(a).astype(config.dtype)

    layers = []
    for i in range(config.n_layers):
        p = f"model.layers.{i}"
        layer = {
            "attn_norm": jnp.asarray(arr(f"{p}.input_layernorm.weight"),
                                     jnp.float32),
            "wq": cast(arr(f"{p}.self_attn.q_proj.weight", transpose=True)),
            "wk": cast(arr(f"{p}.self_attn.k_proj.weight", transpose=True)),
            "wv": cast(arr(f"{p}.self_attn.v_proj.weight", transpose=True)),
            "wo": cast(arr(f"{p}.self_attn.o_proj.weight", transpose=True)),
            # Gemma-2 reuses this HF name for its attention OUTPUT norm;
            # its pre-MLP norm loads below from pre_feedforward_layernorm
            "mlp_norm": (None if config.post_block_norms else jnp.asarray(
                arr(f"{p}.post_attention_layernorm.weight"), jnp.float32)),
            "w1": cast(arr(f"{p}.mlp.gate_proj.weight", transpose=True)),
            "w3": cast(arr(f"{p}.mlp.up_proj.weight", transpose=True)),
            "w2": cast(arr(f"{p}.mlp.down_proj.weight", transpose=True)),
        }
        if config.attn_qkv_bias:  # Qwen2 family
            layer["bq"] = jnp.asarray(
                arr(f"{p}.self_attn.q_proj.bias"), jnp.float32)
            layer["bk"] = jnp.asarray(
                arr(f"{p}.self_attn.k_proj.bias"), jnp.float32)
            layer["bv"] = jnp.asarray(
                arr(f"{p}.self_attn.v_proj.bias"), jnp.float32)
        if config.post_block_norms:  # Gemma-2 sandwich norms: HF's
            # "post_attention_layernorm" is the attention OUTPUT norm
            # here (not the pre-MLP norm, which is
            # "pre_feedforward_layernorm")
            layer["mlp_norm"] = jnp.asarray(
                arr(f"{p}.pre_feedforward_layernorm.weight"), jnp.float32)
            layer["post_attn_norm"] = jnp.asarray(
                arr(f"{p}.post_attention_layernorm.weight"), jnp.float32)
            layer["post_mlp_norm"] = jnp.asarray(
                arr(f"{p}.post_feedforward_layernorm.weight"), jnp.float32)
        layers.append(layer)
    params = {
        "embed": cast(arr("model.embed_tokens.weight")),
        "layers": layers,
        "final_norm": jnp.asarray(arr("model.norm.weight"), jnp.float32),
    }
    if not config.tie_embeddings:
        key = "lm_head.weight"
        if key in state_dict:
            params["lm_head"] = cast(arr(key, transpose=True))
        else:  # checkpoint ties but config didn't say so
            params["lm_head"] = cast(arr("model.embed_tokens.weight",
                                         transpose=True))
    return params


def load_hf(
    name_or_path: str,
    config_overrides: Optional[Dict] = None,
) -> Tuple[Dict, LlamaConfig]:
    """(params, config) from a HF model name or local checkpoint dir."""
    import transformers

    hf_config = transformers.AutoConfig.from_pretrained(name_or_path)
    config = config_from_hf(hf_config, **(config_overrides or {}))
    # dtype='auto' + low_cpu_mem_usage: load at checkpoint dtype without
    # a second fp32 copy — a 7B import otherwise peaks ~3x the bf16 tree
    # and OOM-kills serve pods that fit the model fine. (The kwarg was
    # renamed from torch_dtype; support both transformers generations.)
    try:
        model = transformers.AutoModelForCausalLM.from_pretrained(
            name_or_path, dtype="auto", low_cpu_mem_usage=True)
    except TypeError:
        model = transformers.AutoModelForCausalLM.from_pretrained(
            name_or_path, torch_dtype="auto", low_cpu_mem_usage=True)
    try:
        params = params_from_state_dict(model.state_dict(), config)
    finally:
        del model
    return params, config
