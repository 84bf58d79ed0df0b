"""Llama-family decoder — the flagship JAXJob workload (BASELINE.json
config 4: "Llama-7B SPMD pretrain on v5p-32").

Pure-functional JAX: params are a pytree of arrays, the forward is a plain
jittable function, and every tensor carries a logical sharding spec
(parallel/mesh.ShardingRules) so one model definition runs 1-chip or
dp/fsdp/tp/cp-sharded unchanged — XLA inserts the collectives.

TPU-first choices:
  * bf16 params/activations, f32 RMSNorm epsilon path and logits
    (MXU-friendly, HBM-light);
  * attention via the Pallas flash kernel (ops/flash_attention.py) on a
    single context shard, or ring attention (ops/ring_attention.py) when the
    mesh's "context" axis > 1;
  * per-layer jax.checkpoint (remat) to trade FLOPs for HBM on long
    sequences;
  * weights laid out so tensor-parallel matmuls contract over the sharded
    dim exactly once (wo/w2 row-sharded -> one psum per block).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from kubedl_tpu import ops
from kubedl_tpu.models import PRESETS, hyper, moe, ssm
from kubedl_tpu.models.hyper import (hc_branch, hc_init, hc_merge, hc_param_specs,
                                     hc_streams, hc_sum)
from kubedl_tpu.models.mla import mla_init, mla_param_specs, mla_qkv
from kubedl_tpu.models.moe import moe_init, moe_layer, moe_param_specs
from kubedl_tpu.models.quant import matmul as _mm
from kubedl_tpu.models.short_conv import (short_conv, short_conv_init,
                                          short_conv_param_specs)
from kubedl_tpu.models.ssm import ssm_init, ssm_mixer, ssm_param_specs
from kubedl_tpu.ops.flash_attention import (FLASH_LSE, FLASH_OUT,
                                            flash_attention)
from kubedl_tpu.ops.ring_attention import ring_attention
from kubedl_tpu.parallel import pipeline
from kubedl_tpu.parallel.mesh import ShardingRules


@dataclass(frozen=True)
class RopeScaling:
    """RoPE frequency rescaling for long-context checkpoints
    (Llama 3.1's "llama3" scheme, plain "linear" position
    interpolation, or "yarn") — see _rope_freqs for the math. Frozen so
    LlamaConfig stays hashable."""

    kind: str  # "llama3" | "linear" | "yarn"
    factor: float
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position_embeddings: int = 8192
    # YaRN's keys: the rotations within the original context under which
    # a frequency is kept (beta_fast) and over which it is interpolated
    # (beta_slow), and the two magnitudes whose ratio scales cos and sin
    # and whose second scales the scores (yarn_mscale)
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    d_ff: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    # None = plain RoPE; RopeScaling for Llama-3.1-style long-context
    # frequency rescaling (applied identically in training, prefill,
    # and cached decode — all paths share _rope)
    rope_scaling: Optional["RopeScaling"] = None
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # None = full recompute; "dots" saves matmul outputs and recomputes
    # only elementwise ops (jax dots_with_no_batch_dims_saveable) — most
    # of remat's HBM win at a fraction of its ~15-35% step-time cost.
    # Both keep the flash kernel's out and lse (_remat_policy).
    remat_policy: Optional[str] = None
    use_flash: bool = True
    # context-parallel attention strategy when the mesh's "context" axis
    # is >1: "ring" rotates K/V with ppermute (any P, score memory t/P);
    # "ulysses" all-to-alls into head shards and runs plain full-sequence
    # attention per rank (cheaper comms at small P, capped at the head
    # count) — see ops/ulysses.py for the trade-off.
    context_parallel: str = "ring"
    # family knobs (Gemma: gelu_tanh FFN, norm weight stored as w-1,
    # embeddings scaled by sqrt(d_model))
    act: str = "silu"  # "silu" | "gelu_tanh"
    norm_offset: float = 0.0  # rms_norm multiplies by (weight + offset)
    embed_scale: float = 1.0
    # Gemma-2 family knobs:
    # head_dim decoupled from d_model/n_heads (None = derived)
    head_dim_override: Optional[int] = None
    # sandwich norms: extra RMSNorm on the attention and FFN OUTPUTS
    # before their residual adds (post_attn_norm / post_mlp_norm params)
    post_block_norms: bool = False
    # logit softcapping: x -> cap * tanh(x / cap); 0 = off. The Pallas
    # flash kernel applies the attention cap natively (forward and VJP);
    # context parallelism still refuses it (uncapped online softmax in
    # the ring/all-to-all paths).
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    # attention scores scale by query_pre_attn_scalar**-0.5 instead of
    # head_dim**-0.5 (None = standard); applied by pre-scaling q so the
    # attention kernels keep their 1/sqrt(head_dim) convention
    query_pre_attn_scalar: Optional[float] = None
    # Mistral-style sliding-window attention: query i attends keys in
    # (i - sliding_window, i]. None = full causal. Applies to prefill,
    # decode, and training; not combined with context parallelism.
    sliding_window: Optional[int] = None
    # Per-layer windows (Qwen2 use_sliding_window: full attention below
    # max_window_layers; Gemma-2-style alternating patterns): a tuple of
    # n_layers entries, each None (full causal) or a window size.
    # Overrides sliding_window per layer; see window_for(). Unsupported
    # with ring caches and the pipelined forward (their per-layer
    # buffers/scan assume one uniform window).
    layer_windows: Optional[tuple] = None
    # Qwen2-family checkpoints carry biases on the q/k/v projections
    # (o_proj and the MLP stay bias-free)
    attn_qkv_bias: bool = False
    tie_embeddings: bool = False
    # >1: compute the training loss over this many vocab chunks instead of
    # materializing [b, t, vocab] f32 logits (a 1 GB HBM round-trip at
    # b8/s1024/V32k) — each chunk's lm_head matmul fuses with its logsumexp
    # reduction and is recomputed in backward (see _next_token_ce_chunked).
    # A memory knob, not a speed knob (measured ~5-9% slower on v5e).
    # Ignored (with a one-time warning) on tensor-parallel meshes, where
    # the head's vocab dim is sharded and the full-logits path applies.
    ce_chunks: int = 0
    # MoE (expert parallelism over the "expert" mesh axis): n_experts=0 means
    # dense FFN; >0 replaces every FFN with a top-k-routed expert layer
    n_experts: int = 0
    expert_top_k: int = 2
    expert_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01
    # None = auto (gmm off-mesh, capacity path on a mesh); True forces the
    # dropless gmm route, False forces capacity/scatter (models/moe.py)
    moe_dropless: Optional[bool] = None
    # None = auto (True): fused SwiGLU grouped-matmul epilogue
    # (ops/gmm.py gmm_swiglu); False keeps the three-launch reference
    # path (parity tests / kernel triage)
    moe_fused: Optional[bool] = None
    # expert-parallel dispatch pipelining: split the all-to-all quota
    # into this many chunks so ICI transfer overlaps the local grouped
    # matmuls (models/moe.py _dropless_shard_fn); 1 = no chunking
    moe_a2a_chunks: int = 1
    # What an MoE model's layers are, beyond "every FFN routed":
    # the first n_dense_layers keep a dense FFN of width d_ff, the
    # others route to experts of width d_ff_expert (None = d_ff)
    n_dense_layers: int = 0
    d_ff_expert: Optional[int] = None
    # the router's score: "softmax" over all outputs with the GShard
    # auxiliary loss, or "sigmoid": each output's own sigmoid, a
    # router_bias leaf that only the top-k selection sees, weights
    # normalised over the k chosen, no auxiliary loss (models/moe.py
    # _sigmoid_gating)
    moe_router: str = "softmax"
    # the chip's share of an expert-parallel deployment: the router keeps
    # all n_experts outputs, the layer holds experts first_expert ..
    # first_expert + n_experts_held - 1 and computes their part of the
    # result (None = all held; models/moe.py moe_layer)
    n_experts_held: Optional[int] = None
    first_expert: int = 0
    # Token mixer per layer: None = attention in every layer, else a tuple
    # of n_layers entries, "attention", "conv" (a gated short
    # convolution over conv_kernel tokens, models/short_conv.py) or "ssm"
    # (a Mamba-2 state-space mixer, models/ssm.py)
    layer_types: Optional[tuple] = None
    conv_kernel: int = 3
    # An "ssm" layer's sizes: ssm_heads heads of ssm_head_dim, each with
    # a state of ssm_head_dim x ssm_state; B and C shared by all heads
    # (one group); a depthwise convolution of ssm_conv_kernel taps with
    # bias before the scan, which runs in chunks of ssm_chunk tokens
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 256
    # False = no position embedding: q and k go to the scores as projected
    use_rope: bool = True
    # Granite's scalars: each branch's output times residual_multiplier
    # before its residual add, the logits over logits_scaling. 1 = absent
    # (no multiply is emitted)
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # RMSNorm over each head's entries of q and of k before RoPE
    # (q_norm / k_norm leaves of size head_dim)
    qk_norm: bool = False
    # A looped stack (Ouro's `total_ut_steps`): the n_layers layers are
    # applied this many times over the same weights, the final norm after
    # every pass (its output feeds the next pass, the head and an exit
    # gate, a Linear(d_model, 1) with bias: the exit_gate leaves). 1 = a
    # plain decoder, no gate. Training only (_looped_loss); the paths
    # that carry state from token to token refuse it.
    total_ut_steps: int = 1
    # weight of the exit distribution's entropy in the looped objective
    # (the paper's stage-I beta, a uniform prior over exit steps)
    exit_entropy_beta: float = 0.05
    # Latent attention (models/mla.py): kv_lora_rank set makes every
    # attention layer an MLA layer, its keys qk_nope_head_dim +
    # qk_rope_head_dim wide (the rope part shared by all heads), its
    # values v_head_dim; q through a rank of q_lora_rank
    kv_lora_rank: Optional[int] = None
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # Hyper-connections (models/hyper.py): hc_mult residual streams mixed
    # around every sublayer by mappings whose residual part goes through
    # hc_sinkhorn_iters Sinkhorn-Knopp iterations. 1 = one residual x
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp_min: float = -30.0
    hc_res_clamp_max: float = 30.0
    # shared experts of width d_ff_expert beside the routed ones, run as
    # one dense SwiGLU of n_shared_experts times that width
    n_shared_experts: int = 0
    # the sigmoid router's weights: times routed_scaling_factor, the k
    # chosen scores normalised over their sum + moe_norm_eps (None = the
    # LFM2 family's 1e-6, models/moe.py SIGMOID_NORM_EPS)
    routed_scaling_factor: float = 1.0
    moe_norm_eps: Optional[float] = None
    # Multi-token prediction (DeepSeek-V3's): this many extra modules,
    # each a projection of [embedding of the next token; the stack's
    # state], one more layer and the shared head, predicting one token
    # further; their losses enter at mtp_loss_weight (over the modules'
    # mean). Training only; 0 or 1 modules.
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3
    # Gated attention (Trinity's): the core's output times sigmoid(h W_g),
    # one gate a head and channel from the normed input h that feeds q, k
    # and v, before the output projection (the wg leaf)
    attn_gate: bool = False
    # RoPE per layer: None = use_rope in every layer, else a tuple of
    # n_layers booleans (see rope_for()). Like layer_windows, a static
    # choice a layer: the stack closes over it
    layer_rope: Optional[tuple] = None

    def __post_init__(self):
        if self.sliding_window is not None and self.sliding_window < 1:
            # a window of 0 masks EVERY key: softmax over all -inf rows
            # returns uniform garbage with exit 0 — refuse loudly
            raise ValueError(
                f"sliding_window must be >= 1 or None, got {self.sliding_window}")
        if self.layer_windows is not None:
            if len(self.layer_windows) != self.n_layers:
                raise ValueError(
                    f"layer_windows has {len(self.layer_windows)} entries "
                    f"for {self.n_layers} layers")
            for i, w in enumerate(self.layer_windows):
                if w is not None and w < 1:
                    raise ValueError(
                        f"layer_windows[{i}] must be >= 1 or None, got {w}")
        if self.layer_rope is not None:
            if len(self.layer_rope) != self.n_layers:
                raise ValueError(
                    f"layer_rope has {len(self.layer_rope)} entries "
                    f"for {self.n_layers} layers")
            for i, r in enumerate(self.layer_rope):
                if not isinstance(r, bool):
                    raise ValueError(f"layer_rope[{i}] must be a bool, got {r!r}")
        if self.latent and (self.attn_gate or self.layer_rope is not None):
            raise ValueError(
                "a latent-attention (MLA) layer has no output gate and ropes "
                "its decoupled key always: attn_gate and layer_rope are not "
                "combined with kv_lora_rank")
        if self.layer_types is not None:
            if len(self.layer_types) != self.n_layers:
                raise ValueError(
                    f"layer_types has {len(self.layer_types)} entries "
                    f"for {self.n_layers} layers")
            bad = set(self.layer_types) - {"attention", "conv", "ssm"}
            if bad:
                raise ValueError(
                    f"layer_types holds {sorted(bad)} (attention, conv, ssm)")
            if "ssm" in self.layer_types and self.ssm_heads < 1:
                raise ValueError(
                    "layer_types holds ssm layers and ssm_heads is "
                    f"{self.ssm_heads}")
        if self.moe_router not in ("softmax", "sigmoid"):
            raise ValueError(
                f"unknown moe_router {self.moe_router!r} (softmax, sigmoid)")
        if self.total_ut_steps < 1:
            raise ValueError(
                f"total_ut_steps must be >= 1, got {self.total_ut_steps}")
        if self.kv_lora_rank is not None and not self.q_lora_rank:
            raise ValueError(
                f"kv_lora_rank {self.kv_lora_rank} makes the attention layers "
                f"latent ones, whose q goes through a rank too: q_lora_rank "
                f"is {self.q_lora_rank}")
        if self.hc_mult < 1:
            raise ValueError(f"hc_mult must be >= 1, got {self.hc_mult}")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError(
                "num_nextn_predict_layers must be 0 or 1, got "
                f"{self.num_nextn_predict_layers}")
        if self.looped and (self.hc_mult > 1 or self.num_nextn_predict_layers):
            raise ValueError(
                "a looped stack (total_ut_steps > 1) has one residual and "
                "its own loss: hc_mult > 1 and num_nextn_predict_layers are "
                "not combined with it")

    def mixer_for(self, i: int) -> str:
        """Layer i's token mixer: "attention", "conv" or "ssm"."""
        return "attention" if self.layer_types is None else self.layer_types[i]

    def routed(self, i: int) -> bool:
        """Whether layer i's FFN is a routed expert layer."""
        return self.n_experts > 0 and i >= self.n_dense_layers

    @property
    def looped(self) -> bool:
        """Whether the stack is applied more than once (total_ut_steps)."""
        return self.total_ut_steps > 1

    @property
    def latent(self) -> bool:
        """Whether the attention layers are latent-attention (MLA) layers."""
        return self.kv_lora_rank is not None

    @property
    def softmax_scale(self) -> Optional[float]:
        """The scores' scale where it is not the kernels' own
        head_dim^-0.5 of q's width: a latent layer's (nope + rope)^-0.5
        times YaRN's squared magnitude. None = the kernels' own."""
        if not self.latent:
            return None
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        sc = self.rope_scaling
        if sc is not None and sc.kind == "yarn" and sc.mscale_all_dim:
            scale *= yarn_mscale(sc.factor, sc.mscale_all_dim) ** 2
        return scale

    def require_kv_heads(self, what: str) -> None:
        """Refusal of the paths whose state is keys and values a head."""
        if self.latent:
            raise NotImplementedError(
                f"{what} holds keys and values a head and has no latent "
                f"cache: a latent-attention (MLA) layer would carry its "
                f"kv_lora_rank + qk_rope_head_dim = "
                f"{self.kv_lora_rank + self.qk_rope_head_dim} compressed "
                f"numbers a token, with prefill and decode paths of their "
                f"own; it trains (llama.loss_and_stats) and is not served")

    def require_one_stream(self, what: str) -> None:
        """Refusal of the paths that carry one residual a token."""
        if self.hc_mult > 1:
            raise NotImplementedError(
                f"{what} carries one residual a token: a several-stream "
                f"layer (hyper-connections, hc_mult = {self.hc_mult}) mixes "
                f"{self.hc_mult} streams around every sublayer; it trains "
                f"(llama.loss_and_stats) and is not served")

    def require_single_pass(self, what: str) -> None:
        """Refusal of the paths that visit each layer once a token."""
        if self.looped:
            raise NotImplementedError(
                f"{what} visits each layer once a token: a stack run "
                f"total_ut_steps = {self.total_ut_steps} times over "
                f"{self.n_layers} layers needs {self.total_ut_steps} x "
                f"{self.n_layers} key/value entries a token (or stages "
                f"visited {self.total_ut_steps} times) and an exit by the "
                f"gate; it trains (llama.loss_and_stats) and is not served")

    def require_plain_attention(self, what: str) -> None:
        """Refusal of the paths whose attention layer is wq, wk, wv and wo
        alone, with RoPE in every layer or in none (use_rope)."""
        if self.attn_gate:
            raise NotImplementedError(
                f"{what} reads an attention layer's wq, wk, wv and wo alone: "
                f"a gated attention layer (attn_gate) multiplies the core's "
                f"output by sigmoid(h wg), one gate a head and channel, "
                f"before wo; it trains (llama.loss_and_stats) and is not "
                f"served")
        if self.layer_rope is not None:
            raise NotImplementedError(
                f"{what} applies RoPE in every layer or in none (use_rope): "
                f"RoPE chosen per layer (layer_rope: "
                f"{self.layer_rope.count(False)} of {self.n_layers} layers "
                f"without a position embedding) needs the choice a layer; "
                f"it trains (llama.loss_and_stats) and is not served")

    def require_kv_state_only(self, what: str) -> None:
        """Refusal of the paths that carry state from token to token and
        know keys and values alone, one entry a layer."""
        self.require_plain_attention(what)
        self.require_single_pass(what)
        if self.layer_types is not None and "conv" in self.layer_types:
            raise NotImplementedError(
                f"{what} has no state for a short-convolution layer: the "
                f"last conv_kernel - 1 = {self.conv_kernel - 1} gated "
                f"inputs a layer would carry from step to step have no "
                f"cache beside the keys and values (layer_types holds "
                f"{self.layer_types.count('conv')} conv layers)")
        self.require_no_ssm(what)
        self.require_kv_heads(what)
        self.require_one_stream(what)

    def require_no_ssm(self, what: str) -> None:
        """Refusal of the paths that have no state for a state-space
        layer."""
        if self.layer_types is not None and "ssm" in self.layer_types:
            raise NotImplementedError(
                f"{what} has no state for a state-space (ssm) layer: the "
                f"{self.ssm_heads} x {self.ssm_head_dim} x {self.ssm_state} "
                f"recurrent state and the last ssm_conv_kernel - 1 = "
                f"{self.ssm_conv_kernel - 1} convolution inputs a layer "
                f"would carry from step to step have no cache beside the "
                f"keys and values (layer_types holds "
                f"{self.layer_types.count('ssm')} ssm layers); it trains "
                f"(llama.loss_and_stats) and is not served")

    def require_whole_sequences(self, what: str) -> None:
        """Refusal of a sequence split over devices by the layers that
        carry something along it."""
        carried = [k for k in ("conv", "ssm")
                   if self.layer_types is not None and k in self.layer_types]
        if self.latent:
            raise NotImplementedError(
                f"{what} splits the sequence over devices: the ring and "
                f"all-to-all attention paths give q, k and v one head size, "
                f"and a latent-attention (MLA) layer's keys are "
                f"{self.qk_nope_head_dim + self.qk_rope_head_dim} wide and "
                f"its values {self.v_head_dim}")
        if carried:
            raise NotImplementedError(
                f"{what} splits the sequence over devices: a "
                f"{' or '.join(carried)} layer's taps and state reach across "
                f"a shard's edge and nothing hands them from shard to shard "
                f"(layer_types holds {len(self.layer_types)} layers, "
                f"{sum(self.layer_types.count(k) for k in carried)} of them "
                f"{' / '.join(carried)})")

    def window_for(self, i: int) -> Optional[int]:
        """Layer i's attention window: layer_windows wins, else the
        global sliding_window, else None (full causal)."""
        if self.layer_windows is not None:
            return self.layer_windows[i]
        return self.sliding_window

    def rope_for(self, i: int) -> bool:
        """Whether layer i ropes its q and k: layer_rope wins, else
        use_rope."""
        if self.layer_rope is not None:
            return self.layer_rope[i]
        return self.use_rope

    @property
    def has_windows(self) -> bool:
        return self.sliding_window is not None or (
            self.layer_windows is not None
            and any(w is not None for w in self.layer_windows))

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.d_model // self.n_heads

    @property
    def q_prescale(self) -> float:
        """Multiplier applied to q after RoPE so the kernels' built-in
        1/sqrt(head_dim) nets out to 1/sqrt(query_pre_attn_scalar)."""
        if self.query_pre_attn_scalar is None:
            return 1.0
        return (self.head_dim / self.query_pre_attn_scalar) ** 0.5

    @staticmethod
    def llama_7b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """Test/dry-run size."""
        defaults = dict(
            vocab_size=256, d_model=128, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=256, max_seq_len=256,
        )
        defaults.update(kw)
        return LlamaConfig(**defaults)

    @staticmethod
    def config_for(name: str) -> "LlamaConfig":
        """Named configs shared by the trainer/generate CLIs (PRESETS)."""
        if name not in PRESETS:
            raise ValueError(
                f"unknown model {name!r} (choose from {sorted(PRESETS)})"
            )
        return getattr(LlamaConfig, name.replace("-", "_").replace(".", "_"))()

    @staticmethod
    def lfm2_8b_a1b(**kw) -> "LlamaConfig":
        """LFM2-8B-A1B at its published sizes (LiquidAI/LFM2-8B-A1B
        config.json): 24 layers of hidden 2,048, 18 gated short
        convolutions and 6 GQA layers with q/k head norms, two leading
        dense FFNs of 7,168, then 4 of 32 experts of 1,792 by a sigmoid
        router with a selection bias. 8.34B parameters, 1.5B active."""
        kinds = ["conv", "conv", "attention"] + ["conv", "conv", "conv",
                                                "attention"] * 4
        kinds += ["conv", "conv", "attention", "conv", "conv"]
        defaults = dict(
            vocab_size=65536, d_model=2048, n_layers=24, n_heads=32,
            n_kv_heads=8, d_ff=7168, max_seq_len=128000,
            rope_theta=1000000.0, rms_eps=1e-5, tie_embeddings=True,
            layer_types=tuple(kinds), conv_kernel=3, qk_norm=True,
            n_experts=32, expert_top_k=4, n_dense_layers=2,
            d_ff_expert=1792, moe_router="sigmoid",
        )
        defaults.update(kw)
        return LlamaConfig(**defaults)

    @staticmethod
    def ouro_2_6b(**kw) -> "LlamaConfig":
        """Ouro-2.6B at its published sizes (ByteDance/Ouro-2.6B
        config.json; arXiv:2510.25741): 48 layers of hidden 2,048 with four
        norms each, 16 heads of 128 with as many key/value heads, SwiGLU
        of 5,632, an untied head over 49,152, the whole stack run 4 times
        over the same weights with a head and an exit gate after every
        pass. 2.67B parameters."""
        defaults = dict(
            vocab_size=49152, d_model=2048, n_layers=48, n_heads=16,
            n_kv_heads=16, d_ff=5632, max_seq_len=65536,
            rope_theta=1000000.0, rms_eps=1e-6, post_block_norms=True,
            total_ut_steps=4,
        )
        defaults.update(kw)
        return LlamaConfig(**defaults)

    @staticmethod
    def granite_4_0_h_micro(**kw) -> "LlamaConfig":
        """Granite-4.0-H-Micro at its published sizes
        (ibm-granite/granite-4.0-h-micro config.json): 40 layers of hidden
        2,048 in four periods of five state-space layers, one attention
        layer, four state-space layers; Mamba-2 mixers of 64 heads of 64
        with a state of 128, 4 taps, chunks of 256; GQA of 32 query and 8
        key/value heads of 64 with no position embedding and scores
        scaled by 1/64; a SwiGLU of 8,192 in every layer; embeddings
        times 12, each branch times 0.22, logits over 8; tied head over
        100,352. 3.19B parameters."""
        period = ("ssm",) * 5 + ("attention",) + ("ssm",) * 4
        defaults = dict(
            vocab_size=100352, d_model=2048, n_layers=40, n_heads=32,
            n_kv_heads=8, d_ff=8192, max_seq_len=131072, rms_eps=1e-5,
            tie_embeddings=True, layer_types=period * 4, ssm_heads=64,
            ssm_head_dim=64, ssm_state=128, ssm_conv_kernel=4, ssm_chunk=256,
            use_rope=False, query_pre_attn_scalar=4096.0, embed_scale=12.0,
            residual_multiplier=0.22, logits_scaling=8.0,
        )
        defaults.update(kw)
        return LlamaConfig(**defaults)

    @staticmethod
    def xing4_0_29b_a4b(**kw) -> "LlamaConfig":
        """Xing4.0-29B-A4B at its published sizes (XingChen-AGI/
        Xing4.0-29B-A4B config.json): 40 layers of hidden 3,584 on four
        residual streams mixed by manifold-constrained hyper-connections
        (20 Sinkhorn iterations); latent attention of 32 heads, q rank
        768, kv rank 512, keys of 128 + 64 (YaRN, factor 64 over 4,096)
        and values of 128; two leading dense FFNs of 9,216, then 4 of 64
        experts of 1,024 by a sigmoid router with a selection bias,
        weights times 2, beside one shared expert; one multi-token
        prediction module; an untied head over 131,072. 30.3B parameters
        (29.5B without the module), 3.9B active."""
        defaults = dict(
            vocab_size=131072, d_model=3584, n_layers=40, n_heads=32,
            n_kv_heads=32, d_ff=9216, max_seq_len=262144, rope_theta=10000.0,
            rms_eps=1e-6, rope_scaling=RopeScaling(
                kind="yarn", factor=64.0, original_max_position_embeddings=4096,
                beta_fast=32.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0),
            kv_lora_rank=512, q_lora_rank=768, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, hc_mult=4,
            hc_sinkhorn_iters=20, hc_eps=1e-6, hc_res_clamp_min=-30.0,
            hc_res_clamp_max=30.0, n_experts=64, expert_top_k=4,
            n_dense_layers=2, d_ff_expert=1024, moe_router="sigmoid",
            n_shared_experts=1, routed_scaling_factor=2.0, moe_norm_eps=1e-20,
            num_nextn_predict_layers=1, mtp_loss_weight=0.3,
        )
        defaults.update(kw)
        return LlamaConfig(**defaults)

    @staticmethod
    def trinity_large_preview(**kw) -> "LlamaConfig":
        """Trinity-Large-Preview at its published sizes (arcee-ai/
        Trinity-Large-Preview config.json, model_type afmoe): 60 layers of
        hidden 3,072 with four norms each; gated GQA of 48 query and 8
        key/value heads of 128 with q/k head norms, windowed at 4,096 with
        RoPE in three layers of four and full with no position embedding
        in every fourth; six leading dense SwiGLUs of 12,288, then 4 of 256
        experts of 3,072 by a sigmoid router with a selection bias, weights
        times 2.448, beside one shared expert; embeddings times
        sqrt(3,072); an untied head over 200,192. 398.6B parameters,
        about 13.4B active."""
        n = kw.get("n_layers", 60)
        windows = tuple(None if i % 4 == 3 else 4096 for i in range(n))
        defaults = dict(
            vocab_size=200192, d_model=3072, n_layers=n, n_heads=48,
            n_kv_heads=8, head_dim_override=128, d_ff=12288,
            max_seq_len=262144, rope_theta=10000.0, rms_eps=1e-5,
            layer_windows=windows,
            layer_rope=tuple(w is not None for w in windows),
            attn_gate=True, qk_norm=True, post_block_norms=True,
            embed_scale=3072 ** 0.5, n_experts=256, expert_top_k=4,
            n_dense_layers=6, d_ff_expert=3072, moe_router="sigmoid",
            n_shared_experts=1, routed_scaling_factor=2.448,
            moe_norm_eps=1e-20,
        )
        defaults.update(kw)
        return LlamaConfig(**defaults)

    @staticmethod
    def bench_150m(**kw) -> "LlamaConfig":
        """~170M params — the single-chip quick-proof bench size."""
        defaults = dict(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=8,
            n_kv_heads=8, d_ff=2816, max_seq_len=1024,
        )
        defaults.update(kw)
        return LlamaConfig(**defaults)

    @staticmethod
    def bench_1b(**kw) -> "LlamaConfig":
        """~1.1B params — fits one v5e chip (16 GB HBM) in bf16 + optimizer."""
        defaults = dict(
            vocab_size=32000, d_model=2048, n_layers=16, n_heads=16,
            n_kv_heads=16, d_ff=5632, max_seq_len=2048,
        )
        defaults.update(kw)
        return LlamaConfig(**defaults)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def param_specs(config: LlamaConfig, rules: Optional[ShardingRules] = None) -> Dict:
    """PartitionSpec pytree matching init() — the sharding contract."""
    r = rules or ShardingRules()

    def layer_specs(i: int) -> Dict:
        # i = n_layers is the multi-token prediction module's block
        kind = config.mixer_for(i) if i < config.n_layers else "attention"
        if kind == "conv":
            layer = {"conv_norm": r.spec("embed"), **short_conv_param_specs(r)}
        elif kind == "ssm":
            layer = {"ssm_norm": r.spec("embed"), **ssm_param_specs(r)}
        elif config.latent:
            layer = {"attn_norm": r.spec("embed"),
                     **mla_param_specs(r)}
        else:
            layer = {
                "attn_norm": r.spec("embed"),
                "wq": r.spec("embed", "heads"),
                "wk": r.spec("embed", "heads"),
                "wv": r.spec("embed", "heads"),
                "wo": r.spec("heads", "embed"),
            }
            if config.attn_qkv_bias:
                # biases follow their projection's OUTPUT axis sharding
                layer.update({"bq": r.spec("heads"), "bk": r.spec("heads"),
                              "bv": r.spec("heads")})
            if config.qk_norm:
                layer.update({"q_norm": r.spec(None), "k_norm": r.spec(None)})
            if config.attn_gate:
                layer["wg"] = r.spec("embed", "heads")
        layer["mlp_norm"] = r.spec("embed")
        if config.post_block_norms:
            layer.update({"post_attn_norm": r.spec("embed"),
                          "post_mlp_norm": r.spec("embed")})
        if config.routed(i):
            layer["moe"] = moe_param_specs(
                r, router_bias=config.moe_router == "sigmoid",
                shared=config.n_shared_experts > 0)
        else:
            layer.update({
                "w1": r.spec("embed", "mlp"),
                "w3": r.spec("embed", "mlp"),
                "w2": r.spec("mlp", "embed"),
            })
        if config.hc_mult > 1:
            layer.update({"hc_mixer": hc_param_specs(r),
                          "hc_mlp": hc_param_specs(r)})
        return layer

    specs = {
        "embed": r.spec("vocab", "embed"),
        "layers": [layer_specs(i) for i in range(config.n_layers)],
        "final_norm": r.spec("embed"),
    }
    if not config.tie_embeddings:
        specs["lm_head"] = r.spec("embed", "vocab")
    if config.looped:
        specs["exit_gate"] = {"w": r.spec("embed", None), "b": r.spec(None)}
    if config.num_nextn_predict_layers:
        specs["mtp"] = {
            "embed_norm": r.spec("embed"), "hidden_norm": r.spec("embed"),
            "w_eh": r.spec(None, "embed"),
            "block": layer_specs(config.n_layers),
            "final_norm": r.spec("embed"),
        }
    return specs


def init(config: LlamaConfig, key: jax.Array) -> Dict:
    """Initialize the param pytree (truncated-normal fan-in scaling)."""
    d, dff, hd = config.d_model, config.d_ff, config.head_dim
    nq, nkv = config.n_heads, config.n_kv_heads
    dt = config.dtype

    def dense(key, shape, fan_in):
        return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in))).astype(dt)

    # keys[-1] was never drawn from: the gate takes it, and a model with
    # no gate keeps the weights it had
    keys = jax.random.split(key, config.n_layers + 3)
    norm_init = jnp.full((d,), 1.0 - config.norm_offset, jnp.float32)

    def make_layer(i: int, key) -> Dict:
        # i = n_layers is the multi-token prediction module's block
        ks = jax.random.split(key, 7)
        kind = config.mixer_for(i) if i < config.n_layers else "attention"
        if kind == "conv":
            layer = {"conv_norm": norm_init, **short_conv_init(
                ks[0], d, config.conv_kernel, dtype=dt)}
        elif kind == "ssm":
            layer = {"ssm_norm": norm_init, **ssm_init(
                ks[0], d, config.ssm_heads, config.ssm_head_dim,
                config.ssm_state, config.ssm_conv_kernel, dtype=dt)}
        elif config.latent:
            layer = {"attn_norm": norm_init, **mla_init(
                ks[0], d, nq, config.q_lora_rank, config.kv_lora_rank,
                config.qk_nope_head_dim, config.qk_rope_head_dim,
                config.v_head_dim, dtype=dt)}
        else:
            layer = {
                "attn_norm": norm_init,
                "wq": dense(ks[0], (d, nq * hd), d),
                "wk": dense(ks[1], (d, nkv * hd), d),
                "wv": dense(ks[2], (d, nkv * hd), d),
                "wo": dense(ks[3], (nq * hd, d), nq * hd),
            }
            if config.attn_qkv_bias:
                layer["bq"] = jnp.zeros((nq * hd,), jnp.float32)
                layer["bk"] = jnp.zeros((nkv * hd,), jnp.float32)
                layer["bv"] = jnp.zeros((nkv * hd,), jnp.float32)
            if config.qk_norm:
                head_norm = jnp.full((hd,), 1.0 - config.norm_offset,
                                     jnp.float32)
                layer["q_norm"] = head_norm
                layer["k_norm"] = head_norm
            if config.attn_gate:
                # a key of its own: a model with no gate keeps its weights
                layer["wg"] = dense(jax.random.fold_in(key, 8), (d, nq * hd), d)
        layer["mlp_norm"] = norm_init
        if config.post_block_norms:
            layer["post_attn_norm"] = norm_init
            layer["post_mlp_norm"] = norm_init
        if config.routed(i):
            dff_e = config.d_ff_expert or dff
            layer["moe"] = moe_init(
                ks[4], d, dff_e, config.n_experts,
                dtype=dt, n_held=config.n_experts_held,
                router_bias=config.moe_router == "sigmoid",
                d_ff_shared=config.n_shared_experts * dff_e)
        else:
            layer.update({
                "w1": dense(ks[4], (d, dff), d),
                "w3": dense(ks[5], (d, dff), d),
                "w2": dense(ks[6], (dff, d), dff),
            })
        if config.hc_mult > 1:
            # keys of their own: a model of one stream keeps its weights
            hk = jax.random.split(jax.random.fold_in(key, 7), 2)
            layer["hc_mixer"] = hc_init(hk[0], d, config.hc_mult)
            layer["hc_mlp"] = hc_init(hk[1], d, config.hc_mult)
        return layer

    layers = [make_layer(i, keys[i]) for i in range(config.n_layers)]
    params = {
        "embed": dense(keys[-3], (config.vocab_size, d), d),
        "layers": layers,
        "final_norm": jnp.full((d,), 1.0 - config.norm_offset, jnp.float32),
    }
    if not config.tie_embeddings:
        params["lm_head"] = dense(keys[-2], (d, config.vocab_size), d)
    if config.looped:
        params["exit_gate"] = {"w": dense(keys[-1], (d, 1), d),
                               "b": jnp.zeros((1,), jnp.float32)}
    if config.num_nextn_predict_layers:
        mk = jax.random.split(jax.random.fold_in(key, config.n_layers), 2)
        params["mtp"] = {
            "embed_norm": norm_init, "hidden_norm": norm_init,
            "w_eh": dense(mk[0], (2 * d, d), 2 * d),
            "block": make_layer(config.n_layers, mk[1]),
            "final_norm": norm_init,
        }
    return params


def param_count(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def _remat_policy(name: Optional[str]):
    """What a rematerialised layer keeps besides its input. Both policies
    keep the flash kernel's output and log-sum-exp, the residuals its
    backward needs, so the backward does not run the forward kernel a
    second time: one [tokens, d_model] activation and one f32 per head
    and token a layer. A layer that holds no flash kernel holds neither
    name and keeps nothing more."""
    policies = jax.checkpoint_policies
    flash = policies.save_only_these_names(FLASH_OUT, FLASH_LSE)
    if name is None:
        return flash  # everything else: full recompute
    if name == "dots":
        return policies.save_from_both_policies(
            policies.dots_with_no_batch_dims_saveable, flash)
    raise ValueError(f"unknown remat_policy {name!r} (None | 'dots')")


def rms_norm(x, weight, eps, offset: float = 0.0):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    w = weight + offset if offset else weight
    return (xf * jax.lax.rsqrt(var + eps) * w).astype(x.dtype)


def softcap(x, cap: float):
    """Gemma-2 logit softcapping: cap * tanh(x / cap) — a smooth clamp
    keeping scores/logits in (-cap, cap)."""
    return jnp.tanh(x / cap) * cap


def _act(x, kind: str):
    if kind == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    if kind != "silu":
        raise ValueError(f"unknown activation {kind!r} (silu, gelu_tanh)")
    return jax.nn.silu(x)


def _rope_freqs(half: int, theta: float, scaling) -> np.ndarray:
    """Inverse rotary frequencies, optionally rescaled (trace-time numpy).

    scaling kinds (ref transformers modeling_rope_utils, re-derived):
      * "linear"  — every frequency divided by `factor` (position
        interpolation).
      * "llama3"  — Llama 3.1's frequency-dependent stretch: long
        wavelengths (past original_max/low_freq_factor) divide by
        `factor`, short wavelengths (under original_max/
        high_freq_factor) stay, and the band between interpolates
        smoothly — long-context positions compress without wrecking
        the short-range frequencies that encode local order.
    """
    freqs = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    if scaling is None:
        return freqs
    if scaling.kind == "linear":
        return (freqs / scaling.factor).astype(np.float32)
    if scaling.kind == "yarn":
        return _yarn_freqs(freqs, theta, scaling)
    if scaling.kind != "llama3":
        raise ValueError(f"unknown rope scaling kind {scaling.kind!r} "
                         "(linear, llama3, yarn)")
    orig = float(scaling.original_max_position_embeddings)
    low_wl = orig / scaling.low_freq_factor
    high_wl = orig / scaling.high_freq_factor
    wavelen = 2.0 * np.pi / freqs
    smooth = (orig / wavelen - scaling.low_freq_factor) / (
        scaling.high_freq_factor - scaling.low_freq_factor)
    scaled = np.where(
        wavelen > low_wl, freqs / scaling.factor,
        np.where(wavelen < high_wl, freqs,
                 (1.0 - smooth) * freqs / scaling.factor + smooth * freqs))
    return scaled.astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude for a context stretched `factor` times:
    0.1 mscale ln(factor) + 1 (1 where nothing is stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * float(np.log(factor)) + 1.0


def _yarn_freqs(freqs: np.ndarray, theta: float, scaling) -> np.ndarray:
    """YaRN (arXiv:2309.00071), per frequency a blend of the frequency
    itself and the same over `factor`: a dimension that turns more than
    beta_fast times within the original context keeps its frequency, one
    that turns fewer than beta_slow times is interpolated, and between
    the two correction dimensions the blend is a linear ramp."""
    half = freqs.shape[0]
    dim, orig = 2 * half, float(scaling.original_max_position_embeddings)

    def correction_dim(rotations: float) -> float:
        return dim * np.log(orig / (rotations * 2.0 * np.pi)) / (2.0 * np.log(theta))

    low = max(int(np.floor(correction_dim(scaling.beta_fast))), 0)
    high = min(int(np.ceil(correction_dim(scaling.beta_slow))), dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float32) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (freqs / scaling.factor * ramp + freqs * (1.0 - ramp)).astype(np.float32)


def _rope(x, positions, theta, scaling=None):
    """Rotary embeddings over [b, h, t, d_head]."""
    d = x.shape[-1]
    half = d // 2
    freqs = _rope_freqs(half, theta, scaling)
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
    cos = jnp.cos(angles)[:, None, :, :]  # [b, 1, t, half]
    sin = jnp.sin(angles)[:, None, :, :]
    if scaling is not None and scaling.kind == "yarn":
        # cos and sin times mscale / mscale_all_dim (1 for equal keys)
        mag = (yarn_mscale(scaling.factor, scaling.mscale)
               / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if mag != 1.0:
            cos, sin = cos * mag, sin * mag
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


def _proj(h, layer, name, lora=None, adapter_ids=None):
    """Projection through layer['w<name>'], plus the optional QKV bias
    (Qwen2-family checkpoints: attn_qkv_bias). Biases are stored f32
    and added in the activation dtype.

    Multi-adapter serving (models/serving.py register_adapter): `lora`
    is this layer's stacked adapters {name: {"a": [N, in, r],
    "b": [N, r, out]}} with row 0 all-zero (the base model) and the
    alpha/r scale folded into b; `adapter_ids` [b] selects each row's
    adapter. The rank-r delta is two small einsums on top of the main
    matmul — per-request adapters without per-request weight copies."""
    wkey = "w" + name
    out = _mm(h, layer[wkey])
    bias = layer.get("b" + name)
    if bias is not None:
        out = out + bias.astype(out.dtype)
    if lora is not None and wkey in lora:
        a = jnp.take(lora[wkey]["a"], adapter_ids, axis=0).astype(h.dtype)
        bm = jnp.take(lora[wkey]["b"], adapter_ids, axis=0).astype(h.dtype)
        delta = jnp.einsum("btd,bdr->btr", h, a,
                           preferred_element_type=jnp.float32)
        out = out + jnp.einsum("btr,bro->bto", delta.astype(h.dtype), bm,
                               preferred_element_type=jnp.float32
                               ).astype(out.dtype)
    return out


def _branch_input(x, hc: Optional[Dict], config: LlamaConfig, mesh=None,
                  rules=None):
    """(what a sublayer reads of the residual, what its output goes back
    onto): x itself and None, or, where the layer carries the sublayer's
    hyper-connection leaves `hc`, the mix of the streams x [b, t, n*d]
    by the mappings made from them, and the streams with the mappings
    (models/hyper.py hc_branch)."""
    if hc is None:
        return x, None
    return hc_branch(x, hc, config.hc_mult, config.hc_sinkhorn_iters,
                     config.hc_eps, (config.hc_res_clamp_min,
                                     config.hc_res_clamp_max), mesh, rules)


def _add_branch(x, out, config: LlamaConfig, onto=None):
    """x + residual_multiplier * out: a branch's output onto the residual
    stream it read, the multiply in float32 and absent at 1. `onto`
    (`_branch_input`'s) is the residual of several streams that x was
    mixed from: each stream becomes a mix of all of them plus its own
    share of the output."""
    if config.residual_multiplier != 1.0:
        out = (out.astype(jnp.float32)
               * config.residual_multiplier).astype(x.dtype)
    if onto is not None:
        return hc_merge(onto, out)
    return x + out


def _hc_counters(onto) -> Dict:
    return {} if onto is None else onto.mapping["stats"]


# attention's own declaration: the gate's two means are over the gated
# layers, whose count the step then leaves out
_ATTN_COMBINE = {"attn_gate_mean": "attn_gated_layers",
                 "attn_gate_spread": "attn_gated_layers",
                 "attn_gated_layers": "drop"}

# How a step's counters combine, as the module that makes them declares it
# (its COMBINE; a counter it leaves out is a sum). Over sublayers, layers
# and passes: a sum, or the largest ("max"). Once the step's are all in
# (`_finish_counters`, in this order): a counter declared with a count's
# name is divided by that count (a mean), one declared "drop" is left out,
# and one declared with a function is finished by it, fn(counters, config).
_COMBINE = {**hyper.COMBINE, **ssm.COMBINE, **_ATTN_COMBINE, **moe.COMBINE}


def _add_counters(into: Dict, new: Dict) -> Dict:
    """`into` with a sublayer's or a layer's counters added (`_COMBINE`)."""
    for k, v in new.items():
        if k not in into:
            into[k] = v
        elif _COMBINE.get(k) == "max":
            into[k] = jnp.maximum(into[k], v)
        else:
            into[k] = into[k] + v
    return into


def _finish_counters(stats: Dict, config: "LlamaConfig") -> Dict:
    """The step's counters from their sums (`_COMBINE`)."""
    stats = dict(stats)
    for k, rule in _COMBINE.items():
        if k not in stats or rule == "max":
            continue
        if rule == "drop":
            del stats[k]
        elif callable(rule):
            rule(stats, config)
        else:
            stats[k] = stats[k] / stats[rule]
    return stats


# The named scopes below (embed, attn > attn_core, mlp, head_loss) reach
# each instruction's op_name in a profile, the same names whatever
# implements the work, so a share read by scope compares a flash step with
# a plain-XLA one. They are metadata: no sharding, remat boundary or
# instruction name moves with them (PERF.md section 3).


@jax.named_scope("attn_core")
def _attention_core(q, k, v, config: LlamaConfig, mesh, rules, context_size,
                    window):
    """softmax(q k^T) v over [b, heads, t, head_dim] by whichever of ring,
    ulysses, flash or plain XLA the config and the mesh select. v's head
    size may be unlike q's and k's (a latent layer's), and the scale the
    config's own (`softmax_scale`) where the kernels' head_dim^-0.5 is
    not it."""
    softcap = config.attn_logit_softcap or None
    scale = {} if config.softmax_scale is None else {
        "sm_scale": config.softmax_scale}
    if context_size > 1:
        if config.has_windows:
            raise NotImplementedError(
                "sliding_window + context parallelism is not implemented "
                "(a windowed ring would skip most hops; use full attention "
                "on the context mesh or a single-shard windowed model)")
        if config.attn_logit_softcap:
            raise NotImplementedError(
                "attn_logit_softcap + context parallelism is not "
                "implemented (the ring/all-to-all paths run uncapped "
                "online softmax)")
        if config.context_parallel == "ulysses":
            from kubedl_tpu.ops.ulysses import ulysses_attention

            return ulysses_attention(
                q, k, v, mesh=mesh, causal=True, use_flash=config.use_flash)
        return ring_attention(q, k, v, mesh=mesh, causal=True)
    if config.use_flash:
        flash = functools.partial(
            flash_attention, causal=True, window=window, softcap=softcap,
            **scale)
        # each device on its own batch and head shard (attention mixes neither)
        spec = (rules or ShardingRules()).spec("batch", "heads", None, None)
        return ops.on_rows(flash, mesh, (spec, spec, spec), spec)(q, k, v)
    from kubedl_tpu.ops.flash_attention import attention_reference

    return attention_reference(q, k, v, causal=True, window=window,
                               softcap=softcap, **scale)


def _latent_attention(h, layer, config: LlamaConfig, positions, mesh, rules,
                      context_size, window):
    """A latent-attention layer's output from its normed input h
    [b, t, d] (models/mla.py): the low-rank projections, the core over
    keys wider than the values, the output projection."""
    b, t, _ = h.shape
    norm = lambda x, w: rms_norm(x, w, config.rms_eps, config.norm_offset)
    rope = lambda x: _rope(x, positions, config.rope_theta, config.rope_scaling)
    q, k, v = mla_qkv(h, layer, config.n_heads, config.qk_nope_head_dim,
                      config.qk_rope_head_dim, config.v_head_dim, norm, rope)
    attn = _attention_core(q, k, v, config, mesh, rules, context_size, window)
    attn = attn.transpose(0, 2, 1, 3).reshape(
        b, t, config.n_heads * config.v_head_dim)
    return _mm(attn, layer["wo"]).astype(h.dtype)


def _attention_counters(config: LlamaConfig, window, rope: bool) -> Dict:
    """An attention layer's attn_windowed_layers and attn_nope_layers,
    where the model reports them: one with the gate or with RoPE chosen
    per layer."""
    if not (config.attn_gate or config.layer_rope is not None):
        return {}
    return {"attn_windowed_layers": jnp.asarray(window is not None, jnp.float32),
            "attn_nope_layers": jnp.asarray(not rope, jnp.float32)}


@jax.named_scope("attn")
def _attention_block(x, layer, config: LlamaConfig, positions, mesh, rules,
                     context_size, window=None, onto=None, rope=None):
    """(the residual after the layer's attention over x [b, t, d], the
    layer's attn_* counters); `onto` as `_add_branch` takes it, `rope`
    whether q and k are roped (None = use_rope)."""
    b, t, d = x.shape
    hd, nq, nkv = config.head_dim, config.n_heads, config.n_kv_heads
    rope = config.use_rope if rope is None else rope
    h = rms_norm(x, layer["attn_norm"], config.rms_eps, config.norm_offset)
    if "wkv_a" in layer:
        out = _latent_attention(h, layer, config, positions, mesh, rules,
                                context_size, window)
        return _add_branch(x, out, config, onto), {}
    q = _proj(h, layer, "q").reshape(b, t, nq, hd).transpose(0, 2, 1, 3)
    k = _proj(h, layer, "k").reshape(b, t, nkv, hd).transpose(0, 2, 1, 3)
    v = _proj(h, layer, "v").reshape(b, t, nkv, hd).transpose(0, 2, 1, 3)
    if "q_norm" in layer:
        q = rms_norm(q, layer["q_norm"], config.rms_eps, config.norm_offset)
        k = rms_norm(k, layer["k_norm"], config.rms_eps, config.norm_offset)
    if rope:
        q = _rope(q, positions, config.rope_theta, config.rope_scaling)
        k = _rope(k, positions, config.rope_theta, config.rope_scaling)
    if config.q_prescale != 1.0:
        q = q * jnp.asarray(config.q_prescale, q.dtype)
    if nq != nkv:
        rep = nq // nkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    attn = _attention_core(q, k, v, config, mesh, rules, context_size, window)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, t, nq * hd)
    stats = _attention_counters(config, window, rope)
    if "wg" in layer:
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid(jnp.matmul(
                h, layer["wg"], preferred_element_type=jnp.float32))
            attn = attn * gate.astype(attn.dtype)
            stats["attn_gate_mean"] = jnp.mean(gate)
            stats["attn_gate_spread"] = jnp.mean(jnp.square(gate - 0.5))
            stats["attn_gated_layers"] = jnp.ones((), jnp.float32)
    out = _mm(attn, layer["wo"]).astype(x.dtype)
    if "post_attn_norm" in layer:
        out = rms_norm(out, layer["post_attn_norm"], config.rms_eps,
                       config.norm_offset)
    return _add_branch(x, out, config, onto), stats


@jax.named_scope("short_conv")
def _short_conv_block(x, layer, config: LlamaConfig, mesh, rules, onto=None):
    """A convolution layer's mixer with its norm and residual, as
    _attention_block is an attention layer's, and the layer's counters
    (models/short_conv.py). The mesh is for the gates' and taps' kernels,
    which ride a shard_map over `batch`."""
    h = rms_norm(x, layer["conv_norm"], config.rms_eps, config.norm_offset)
    out, stats = short_conv(h, layer, mesh, rules)
    return _add_branch(x, out.astype(x.dtype), config, onto), stats


@jax.named_scope("ssm")
def _ssm_block(x, layer, config: LlamaConfig, mesh, rules, onto=None):
    """A state-space layer's mixer with its norm and residual, and the
    layer's counters (models/ssm.py ssm_mixer). The mesh is for the
    scan's and the convolution's kernels, which ride a shard_map over
    `batch` as flash does."""
    h = rms_norm(x, layer["ssm_norm"], config.rms_eps, config.norm_offset)
    out, stats = ssm_mixer(h, layer, config.ssm_heads, config.ssm_head_dim,
                           config.ssm_state, config.ssm_chunk, config.rms_eps,
                           mesh, rules)
    return _add_branch(x, out.astype(x.dtype), config, onto), stats


def _mixer_block(x, layer, config: LlamaConfig, positions, mesh, rules,
                 context_size, window=None, rope=None):
    """The layer's token mixer, by what the layer holds, and its counters
    ({} but for a state-space or convolution layer, for a gated
    attention layer or one of a model that chooses RoPE by layer, and for
    a layer of several streams, whose mixer reads a mix of them:
    `_branch_input`)."""
    u, onto = _branch_input(x, layer.get("hc_mixer"), config, mesh, rules)
    if "ssm_in" in layer:
        y, stats = _ssm_block(u, layer, config, mesh, rules, onto)
        return y, {**stats, **_hc_counters(onto)}
    if "conv_in" in layer:
        y, stats = _short_conv_block(u, layer, config, mesh, rules, onto)
        return y, {**stats, **_hc_counters(onto)}
    y, stats = _attention_block(u, layer, config, positions, mesh, rules,
                                context_size, window=window, onto=onto,
                                rope=rope)
    return y, {**stats, **_hc_counters(onto)}


@jax.named_scope("mlp")
def _mlp_block(x, layer, config: LlamaConfig, mesh=None, rules=None,
               lora=None, adapter_ids=None):
    """Dense or MoE FFN; returns (out, aux_loss, counters): the
    counters are an expert layer's (moe.py _dispatch_stats), {} for a
    dense one. lora/adapter_ids: per-row serving adapters on w1/w3/w2
    (see _proj); MoE layers carry no dense projections for adapters to
    target."""
    x, onto = _branch_input(x, layer.get("hc_mlp"), config, mesh, rules)
    h = rms_norm(x, layer["mlp_norm"], config.rms_eps, config.norm_offset)
    stats = {}
    if "moe" in layer:
        y, aux, stats = moe_layer(
            h, layer["moe"], top_k=config.expert_top_k,
            capacity_factor=config.expert_capacity_factor, mesh=mesh, rules=rules,
            dropless=config.moe_dropless, fused=config.moe_fused,
            a2a_chunks=config.moe_a2a_chunks,
            first_expert=config.first_expert,
            routed_scale=config.routed_scaling_factor,
            norm_eps=config.moe_norm_eps,
        )
        y = y.astype(x.dtype)
    else:
        gate = _act(_proj(h, layer, "1", lora, adapter_ids)
                    .astype(jnp.float32), config.act).astype(h.dtype)
        up = _proj(h, layer, "3", lora, adapter_ids)
        y = _proj(gate * up, layer, "2", lora, adapter_ids).astype(x.dtype)
        aux = jnp.zeros((), jnp.float32)
    if "post_mlp_norm" in layer:
        y = rms_norm(y, layer["post_mlp_norm"], config.rms_eps,
                     config.norm_offset)
    return _add_branch(x, y, config, onto), aux, {**stats, **_hc_counters(onto)}


def _constrainer(mesh, rules):
    def constrain(x, *dims):
        if mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, rules.sharding(mesh, *dims))
    return constrain


def _layer_maker(config: LlamaConfig, positions, mesh, rules, context_size):
    """(window, rope) -> the function that applies one layer,
    `layer_fn((x, aux), layer) -> ((x, aux), counters)`, rematerialised as
    the config says. x is [b, t, d], or [b, t, n*d] where the layers mix
    several streams. The stack and the multi-token prediction module's
    block run their layers through it."""
    constrain = _constrainer(mesh, rules)

    def make_layer_fn(window, rope):
        # window and rope are trace-time static (they select the mask
        # program and whether RoPE is emitted), so they ride a closure,
        # not a traced argument
        def layer_fn(carry, layer):
            x, aux = carry
            wide = (None,) * (x.ndim - 2)  # streams and embed, unsharded
            x, mixed = _mixer_block(x, layer, config, positions, mesh, rules,
                                    context_size, window=window, rope=rope)
            x = constrain(x, "batch", "seq", *wide)
            x, a, counters = _mlp_block(x, layer, config, mesh, rules)
            return (constrain(x, "batch", "seq", *wide), aux + a), _add_counters(
                dict(mixed), counters)

        if config.remat:
            return jax.checkpoint(
                layer_fn, policy=_remat_policy(config.remat_policy))
        return layer_fn

    return make_layer_fn


def _context_size(config: LlamaConfig, mesh) -> int:
    """The mesh's `context` axis, refused by the layers that cannot have
    their sequence split."""
    context_size = 1 if mesh is None else mesh.shape.get("context", 1)
    if context_size > 1:
        config.require_whole_sequences(f"a mesh with context: {context_size}")
    return context_size


def _positions(b: int, t: int):
    return jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))


def _backbone(
    params: Dict,
    tokens: jax.Array,  # [batch, seq] int32
    config: LlamaConfig,
    mesh: Optional[Mesh],
    rules: ShardingRules,
) -> Tuple[jax.Array, jax.Array, Dict]:
    """(what the head reads, summed MoE aux loss, the layers' counters
    summed over layers: {} for a dense model). What the head reads is
    the pre-final-norm activations [batch, seq, d] of a stack run once
    (of a stack of several residual streams, their sum), and of a looped
    one (total_ut_steps > 1) every pass's state after the final norm,
    [passes, batch, seq, d]: the norm sits inside the loop there, its
    output feeds the next pass."""
    context_size = _context_size(config, mesh)
    constrain = _constrainer(mesh, rules)

    b, t = tokens.shape
    positions = _positions(b, t)
    # FSDP-gather the table's embed dim before the lookup: a gather whose
    # output inherits a feature-dim sharding forces SPMD into an involuntary
    # full rematerialization when the result is then batch-sharded; with the
    # embed dim unsharded the output reshards by a cheap dynamic-slice.
    with jax.named_scope("embed"):
        tbl = constrain(params["embed"], "vocab", None)
        x = tbl[tokens].astype(config.dtype)
        if config.embed_scale != 1.0:
            x = x * jnp.asarray(config.embed_scale, config.dtype)
        x = constrain(x, "batch", "seq", None)
        if config.hc_mult > 1:
            # every stream starts as the embedding
            x = constrain(hc_streams(x, config.hc_mult), "batch", "seq", None)

    make_layer_fn = _layer_maker(config, positions, mesh, rules, context_size)

    def stack(x):
        """Every layer once over x."""
        aux = jnp.zeros((), jnp.float32)
        stats: Dict = {}
        for i, layer in enumerate(params["layers"]):
            (x, aux), counters = make_layer_fn(
                config.window_for(i), config.rope_for(i))((x, aux), layer)
            _add_counters(stats, counters)
        if config.hc_mult > 1:
            x = hc_sum(x, config.hc_mult)
        return x, aux, stats

    if not config.looped:
        return stack(x)

    # The passes are a loop in the program, the weights closed over: the
    # step holds n_layers layer bodies whatever total_ut_steps is, the
    # loop's own backward sums the passes' gradients of each weight, and
    # what a pass saves for it (each layer's input, the flash kernel's out
    # and lse) is stacked by pass.
    def final_norm(u):
        return rms_norm(u, params["final_norm"], config.rms_eps,
                        config.norm_offset)

    if config.remat:  # or the loop keeps each pass's float32 copy of u
        final_norm = jax.checkpoint(final_norm)

    def one_pass(h, _):
        with jax.named_scope("loop_pass"):
            u, aux, stats = stack(h)
            h = final_norm(u)
        return h, (h, aux, stats)

    _, (states, aux, stats) = _scan_passes(
        one_pass, x, None, length=config.total_ut_steps)
    return states, jnp.sum(aux), {
        k: (jnp.max if _COMBINE.get(k) == "max" else jnp.sum)(v, axis=0)
        for k, v in sorted(stats.items())}


# the loop over the passes of a looped stack (hack/probe_loop_shape.py
# measures it against its unrolled form)
_scan_passes = jax.lax.scan


def forward_and_aux(
    params: Dict,
    tokens: jax.Array,  # [batch, seq] int32
    config: LlamaConfig,
    mesh: Optional[Mesh] = None,
    rules: Optional[ShardingRules] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(logits [batch, seq, vocab] f32, summed MoE aux loss — 0 when dense)."""
    rules = rules or ShardingRules()
    x, aux, _ = _backbone(params, tokens, config, mesh, rules)
    if config.looped:
        # every pass runs, as early_exit_threshold 1 has inference do: the
        # last pass's state, already normed
        with jax.named_scope("head_loss"):
            logits = _head_logits(x[-1], params, config)
    else:
        logits = _lm_head(x, params, config)
    return _constrainer(mesh, rules)(logits, "batch", "seq", "vocab"), aux


def forward(params, tokens, config: LlamaConfig, mesh=None, rules=None) -> jax.Array:
    """Logits [batch, seq, vocab] (f32)."""
    return forward_and_aux(params, tokens, config, mesh=mesh, rules=rules)[0]


def _head_matrix(params, config: LlamaConfig):
    """[d, vocab] LM head (possibly an int8 quantized leaf) — separate
    weights or the tied embedding table."""
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T.astype(config.dtype)
    return head


def _head_logits(x, params, config: LlamaConfig, rounded: bool = True) -> jax.Array:
    """(Tied or separate) LM head over a normed state -> f32 logits:
    the product rounded to the activations' dtype first, as the plain
    decoder's head always was, or (rounded=False) as the matmul
    accumulated it."""
    if rounded:
        logits = _mm(x, _head_matrix(params, config)).astype(jnp.float32)
    else:
        logits = jnp.matmul(x, _head_matrix(params, config),
                            preferred_element_type=jnp.float32)
    if config.logits_scaling != 1.0:
        logits = logits / config.logits_scaling
    if config.final_logit_softcap:
        logits = softcap(logits, config.final_logit_softcap)
    return logits


@jax.named_scope("head_loss")
def _lm_head(x, params, config: LlamaConfig) -> jax.Array:
    """Final norm + (tied or separate) LM head -> f32 logits."""
    x = rms_norm(x, params["final_norm"], config.rms_eps, config.norm_offset)
    return _head_logits(x, params, config)


def _mean_over(nll, mask):
    """Mean of the per-position losses: over all of them, or over those
    `mask` [b, t] counts."""
    if mask is None:
        return jnp.mean(nll)
    return jnp.sum(nll * mask) / jnp.sum(mask)


@jax.named_scope("head_loss")
def _next_token_ce(logits, targets, mask=None):
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll) if mask is None else _mean_over(-ll, mask)


@jax.named_scope("head_loss")
def _next_token_ce_chunked(x, params, config: LlamaConfig, targets,
                           n_chunks: int, final_norm=None, mask=None):
    """CE without materializing [b, t, V] f32 logits.

    lax.scan over vocab chunks: each chunk's lm_head matmul fuses with its
    max/sumexp reduction (only [b, t] statistics leave the chunk), and
    jax.checkpoint recomputes the chunk logits in backward instead of
    saving them. Online-logsumexp merge across chunks is exact.
    `final_norm` is the norm's weight where it is not the model's own (a
    multi-token prediction module's), `mask` as `_mean_over` takes it.
    """
    xn = rms_norm(x, params["final_norm"] if final_norm is None else final_norm,
                  config.rms_eps, config.norm_offset)
    head = _head_matrix(params, config)
    d, V = head.shape
    if V % n_chunks:
        raise ValueError(f"vocab {V} not divisible by ce_chunks {n_chunks}")
    cs = V // n_chunks
    hc = jnp.moveaxis(head.reshape(d, n_chunks, cs), 1, 0)  # [n, d, cs]
    offs = jnp.arange(n_chunks, dtype=targets.dtype) * cs
    # the chunks' cotangents of xn are summed by the loop in the dtype of
    # what it closes over: in float32, or a chunk's part (the softmax's
    # pull, a hundredth of the target row's) falls under half an ulp of a
    # bf16 running sum and is dropped, every token alike
    xn32 = xn.astype(jnp.float32)

    @jax.checkpoint
    def chunk_stats(h_c, off):
        logits = (xn32.astype(xn.dtype) @ h_c).astype(jnp.float32)  # [b, t, cs]
        if config.logits_scaling != 1.0:
            logits = logits / config.logits_scaling
        if config.final_logit_softcap:
            # softcap is elementwise, so capping per chunk == capping the
            # full logits — the chunked loss must match _lm_head's math
            logits = softcap(logits, config.final_logit_softcap)
        m = jnp.max(logits, axis=-1)
        l = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
        in_chunk = (targets >= off) & (targets < off + cs)
        idx = jnp.clip(targets - off, 0, cs - 1)
        tl = jnp.take_along_axis(logits, idx[..., None], axis=-1)[..., 0]
        tl = jnp.where(in_chunk, tl, -jnp.inf)
        return m, l, tl

    def body(carry, inp):
        big_m, big_l, tgt = carry
        m, l, tl = chunk_stats(*inp)
        new_m = jnp.maximum(big_m, m)
        big_l = big_l * jnp.exp(big_m - new_m) + l * jnp.exp(m - new_m)
        # exactly one chunk holds each target, the rest contribute -inf
        return (new_m, big_l, jnp.maximum(tgt, tl)), None

    b, t = targets.shape
    init = (
        jnp.full((b, t), -jnp.inf, jnp.float32),
        jnp.zeros((b, t), jnp.float32),
        jnp.full((b, t), -jnp.inf, jnp.float32),
    )
    (big_m, big_l, tgt), _ = jax.lax.scan(body, init, (hc, offs))
    lse = big_m + jnp.log(big_l)
    return _mean_over(lse - tgt, mask)


# tokens whose float32 logits a looped stack's loss holds at a time: 4,096
# x 49,152 are 0.8 GB, and the loss's backward holds three such arrays
HEAD_TOKENS = 4096


def _looped_loss(states, params, config: LlamaConfig, targets, mesh, rules):
    """A looped stack's objective from every pass's normed state
    [passes, b, t, d], and its counters.

    Pass t's head gives each token's cross entropy CE_t, its gate
    lambda_t = sigmoid(h_t w_g + b_g). A token leaves after pass t with
    probability p_t = lambda_t prod_{j<t}(1 - lambda_j), and after the
    last with what is left, prod_{j<T}(1 - lambda_j) (the last pass's
    gate decides nothing). The loss is the mean over tokens of
    sum_t p_t CE_t - beta H(p): the expected loss over the exit step
    with a uniform prior over it (arXiv:2510.25741, stage I).

    The passes' heads are a loop too, over pieces of HEAD_TOKENS tokens,
    each recomputed in the backward pass, so that one piece's
    [tokens, vocab] float32 logits live at a time and none from forward
    to backward."""
    n, (b, t) = config.total_ut_steps, targets.shape
    constrain = _constrainer(mesh, rules)
    # a call of the head sees HEAD_TOKENS tokens: the sequence in c pieces
    c = max(1, b * t // HEAD_TOKENS)
    c = c if t % c == 0 else 1

    tc, d = t // c, states.shape[-1]
    pieces = jnp.moveaxis(states.reshape(n, b, c, tc, d), 2, 1).reshape(
        n * c, b, tc, d)
    wanted = jnp.tile(jnp.moveaxis(targets.reshape(b, c, tc), 1, 0), (n, 1, 1))

    def whole(a):  # [n * c, b, t / c] -> [n, b, t]
        return jnp.moveaxis(a.reshape(n, c, b, tc), 1, 2).reshape(n, b, t)

    @jax.checkpoint
    def head_and_gate(piece):
        h, want = piece
        with jax.named_scope("head_loss"):
            # float32 out of the matmul: the gate learns from the
            # differences of the passes' losses, which on a token are no
            # larger than a bf16 logit's rounding
            logits = constrain(_head_logits(h, params, config, rounded=False),
                               "batch", "seq", "vocab")
            lse = jax.nn.logsumexp(logits, axis=-1)
            hit = jnp.take_along_axis(logits, want[..., None], axis=-1)[..., 0]
        with jax.named_scope("exit_gate"):
            gate = params["exit_gate"]
            z = jnp.einsum("btd,do->bto", h, gate["w"],
                           preferred_element_type=jnp.float32)[..., 0]
        return lse - hit, z + gate["b"][0]

    ce, z = jax.lax.map(head_and_gate, (pieces, wanted))
    ce, z = whole(ce), whole(z)  # [passes, b, t] each
    with jax.named_scope("exit_gate"):
        log_stay = jax.nn.log_sigmoid(-z)  # log(1 - lambda_t)
        stayed = jnp.cumsum(log_stay, axis=0) - log_stay  # sum over j < t
        log_p = jnp.concatenate(
            [stayed[:-1] + jax.nn.log_sigmoid(z[:-1]), stayed[-1:]], axis=0)
        p = jnp.exp(log_p)
        entropy = -jnp.sum(p * log_p, axis=0)
        loss = jnp.mean(jnp.sum(p * ce, axis=0)
                        - config.exit_entropy_beta * entropy)
        mass, ce_mean = jnp.mean(p, axis=(1, 2)), jnp.mean(ce, axis=(1, 2))
        stats = {"loop_passes": jnp.asarray(n, jnp.float32),
                 "loop_layer_applications": jnp.asarray(
                     n * len(params["layers"]), jnp.float32),
                 "loop_exit_entropy": jnp.mean(entropy)}
        for i in range(n):
            stats[f"loop_exit_mass_{i + 1}"] = mass[i]
            stats[f"loop_ce_{i + 1}"] = ce_mean[i]
    return loss, stats


@jax.named_scope("mtp")
def _mtp_hidden(h, params, tokens, config: LlamaConfig, mesh, rules):
    """The multi-token prediction module's state before its final norm
    [b, t, d], its block's MoE aux loss and counters (DeepSeek-V3,
    arXiv:2412.19437, one module).

    h [b, t, d] is the main stack's state before its final norm, position
    i having read tokens 0..i of the t + 1 fed. The module reads
    z_i = W_eh [norm_e(E[t_{i+1}]); norm_h(h_i)] and runs its one block
    over z (under hyper-connections, on streams that each start as z); E
    is the model's embedding. Position i has then read tokens 0..i+1."""
    mtp = params["mtp"]
    b, t, _ = h.shape
    constrain = _constrainer(mesh, rules)
    norm = lambda x, w: rms_norm(x, w, config.rms_eps, config.norm_offset)
    with jax.named_scope("embed"):
        tbl = constrain(params["embed"], "vocab", None)
        e = tbl[tokens[:, 1:]].astype(config.dtype)
        if config.embed_scale != 1.0:
            e = e * jnp.asarray(config.embed_scale, config.dtype)
        e = constrain(e, "batch", "seq", None)
    z = _mm(jnp.concatenate([norm(e, mtp["embed_norm"]),
                             norm(h, mtp["hidden_norm"])], axis=-1),
            mtp["w_eh"]).astype(config.dtype)
    z = constrain(z, "batch", "seq", None)
    if config.hc_mult > 1:
        z = constrain(hc_streams(z, config.hc_mult), "batch", "seq", None)
    layer_fn = _layer_maker(config, _positions(b, t), mesh, rules,
                            _context_size(config, mesh))(config.sliding_window,
                                                         config.use_rope)
    (z, aux), stats = layer_fn((z, jnp.zeros((), jnp.float32)), mtp["block"])
    if config.hc_mult > 1:
        z = hc_sum(z, config.hc_mult)
    return z, aux, stats


@jax.named_scope("mtp")
def _mtp_loss(h, params, tokens, config: LlamaConfig, mesh, rules,
              chunked: bool):
    """The multi-token prediction module's loss: (cross entropy of the
    second-next token, the block's MoE aux loss, counters). Position i of
    `_mtp_hidden` predicts token i + 2 through the module's own final
    norm and the model's head; position t - 1 has no second-next token
    among the t + 1 fed and is left out of the mean."""
    mtp = params["mtp"]
    b, t = tokens.shape[0], tokens.shape[1] - 1
    constrain = _constrainer(mesh, rules)
    norm = lambda x, w: rms_norm(x, w, config.rms_eps, config.norm_offset)
    z, aux, stats = _mtp_hidden(h, params, tokens, config, mesh, rules)
    # position i's target is token i + 2; the last position has none
    targets = jnp.concatenate(
        [tokens[:, 2:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
    mask = (jnp.arange(t) < t - 1).astype(jnp.float32)[None, :] * jnp.ones(
        (b, 1), jnp.float32)
    if chunked:
        ce = _next_token_ce_chunked(z, params, config, targets,
                                    config.ce_chunks,
                                    final_norm=mtp["final_norm"], mask=mask)
    else:
        with jax.named_scope("head_loss"):
            logits = _head_logits(norm(z, mtp["final_norm"]), params, config)
        ce = _next_token_ce(constrain(logits, "batch", "seq", "vocab"),
                            targets, mask)
    stats = dict(stats)
    stats["mtp_ce"] = ce
    stats["mtp_positions"] = jnp.asarray(b * (t - 1), jnp.float32)
    return ce, aux, stats


def loss_fn(params, tokens, config: LlamaConfig, mesh=None, rules=None):
    """Next-token cross entropy (+ MoE aux); tokens [b, t], loss over [:, 1:].

    With config.ce_chunks > 1 (and no vocab/tensor sharding to respect)
    the loss runs chunked — the full logits tensor never exists.
    """
    return loss_and_stats(params, tokens, config, mesh=mesh, rules=rules)[0]


def loss_and_stats(params, tokens, config: LlamaConfig, mesh=None, rules=None):
    """(loss_fn's loss, the step's counters): what
    make_train_step(has_aux=True) returns as metrics beside the loss, and
    what the trainer's step record carries. {} for a dense model run once;
    otherwise float32 scalars, each named by the module that makes it and
    combined over sublayers, layers and passes as that module declares
    (`_COMBINE`). docs/observability.md lists them."""
    rules = rules or ShardingRules()
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    chunked = config.ce_chunks > 1
    if chunked and mesh is not None and mesh.shape.get("tensor", 1) != 1:
        _warn_ce_chunks_ignored(mesh.shape.get("tensor", 1))
        chunked = False
    x, aux, stats = _backbone(params, inputs, config, mesh, rules)
    if config.looped:
        if chunked:
            raise NotImplementedError(
                "ce_chunks is not wired into a looped stack's loss (each "
                "pass's head is recomputed in the backward pass as it is)")
        ce, loop_stats = _looped_loss(x, params, config, targets, mesh, rules)
        stats = {**stats, **loop_stats}
    elif chunked:
        ce = _next_token_ce_chunked(x, params, config, targets, config.ce_chunks)
    else:
        logits = _constrainer(mesh, rules)(
            _lm_head(x, params, config), "batch", "seq", "vocab")
        ce = _next_token_ce(logits, targets)
    if "mtp" in params:
        ce2, aux2, mtp_stats = _mtp_loss(x, params, tokens, config, mesh,
                                         rules, chunked)
        stats = _add_counters({**stats, "ce": ce}, mtp_stats)
        ce, aux = ce + config.mtp_loss_weight * ce2, aux + aux2
    stats = _finish_counters(stats, config)
    return ce + config.moe_aux_coef * aux, stats


_warned_ce_chunks = False


def _warn_ce_chunks_ignored(tensor_size: int) -> None:
    global _warned_ce_chunks
    if _warned_ce_chunks:
        return
    _warned_ce_chunks = True
    import warnings

    warnings.warn(
        f"ce_chunks ignored: the mesh's tensor axis ({tensor_size}) shards the "
        f"head's vocab dim, so the full-logits loss path applies",
        stacklevel=3,
    )


# ---------------------------------------------------------------------------
# pipeline-parallel path ("stage" mesh axis; SURVEY.md §2.4 PP row)
# ---------------------------------------------------------------------------


def param_specs_pp(config: LlamaConfig, rules: Optional[ShardingRules] = None) -> Dict:
    """Spec pytree matching stack_params(): layer leaves gain a leading
    layer dim sharded over "stage"."""
    r = rules or ShardingRules()
    base = param_specs(config, r)
    layer0 = base["layers"][0]
    base["layers"] = jax.tree_util.tree_map(
        lambda s: P(*(r.rules["layers"] + tuple(s))), layer0,
        is_leaf=lambda x: isinstance(x, P),
    )
    return base


def stack_params(params: Dict) -> Dict:
    """Per-layer list-of-dicts -> stacked leaves [n_layers, ...] for the
    pipelined forward (parallel/pipeline.py layout)."""
    out = dict(params)
    out["layers"] = pipeline.stack_layers(params["layers"])
    return out


def pipeline_layer_fn(config: LlamaConfig, t: int,
                      rules: Optional[ShardingRules] = None):
    """The ONE per-layer body every pipelined path applies — the GPipe
    oracle, the interleaved 1F1B schedule, and the MPMD stage programs
    (train/pipeline_runtime.py) all run this closure, so schedule parity
    can never drift into layer-math drift. `layer_fn(act, layer) ->
    (act, aux_scalar)`; `t` is the (static) sequence length."""
    config.require_plain_attention("the pipelined forward")
    rules = rules or ShardingRules()
    positions1 = jnp.arange(t, dtype=jnp.int32)[None]

    def layer_fn(a, layer):
        pos = jnp.broadcast_to(positions1, (a.shape[0], t))
        a, _ = _attention_block(a, layer, config, pos, None, rules, 1,
                                window=config.sliding_window)
        a, aux, _ = _mlp_block(a, layer, config)
        return a, aux

    return layer_fn


def forward_pipelined_and_aux(
    params: Dict,  # stacked layout (stack_params)
    tokens: jax.Array,
    config: LlamaConfig,
    mesh: Mesh,
    rules: Optional[ShardingRules] = None,
    n_microbatches: int = 4,
    schedule: str = "gpipe",
    interleave: int = 1,
) -> Tuple[jax.Array, jax.Array]:
    """Pipelined forward over the mesh's "stage" axis; returns (logits,
    summed MoE aux loss — 0 when dense). `schedule` picks the loop:
    "gpipe" (parallel/pipeline.py pipeline_apply — the parity oracle) or
    "1f1b" (pipeline_apply_1f1b, interleaved circular schedule with
    `interleave` virtual stages per rank; interleave > 1 requires it).
    Composes with data parallelism AND MoE (experts replicated per
    stage: _mlp_block runs the local dropless gmm route inside the stage
    body, aux accumulated per valid microbatch window);
    tensor/context/expert must be size 1 on a pipelined mesh (those
    shardings need manual collectives inside shard_map)."""
    config.require_plain_attention("the pipelined forward")
    config.require_single_pass("the pipelined forward")
    config.require_no_ssm("the pipelined forward")
    config.require_one_stream("the pipelined forward")
    if config.latent or config.num_nextn_predict_layers:
        raise NotImplementedError(
            "the pipelined forward's stages hand one another one [b, t, d] "
            "activation through grouped-query layers of one head size: a "
            "latent-attention (MLA) layer and a multi-token prediction "
            "module (a second head over the last stage's state and the "
            "first stage's embedding) have no stage program")
    if config.layer_windows is not None:
        # the pipeline scans ONE compiled layer program over stacked
        # params; a per-layer static mask can't vary inside the scan
        raise ValueError("pipelined path requires a uniform window "
                         "(layer_windows unsupported)")
    if config.layer_types is not None or config.n_dense_layers:
        # the same scan: every layer must hold the same leaves
        raise NotImplementedError(
            "the pipelined forward scans one layer program over stacked "
            "layers and has no per-stage layer kinds: layer_types "
            f"{config.layer_types} / n_dense_layers {config.n_dense_layers} "
            "need layers of unlike leaves (a short convolution beside "
            "attention, a dense FFN before routed ones)")
    for ax in ("tensor", "context", "expert"):
        if mesh.shape.get(ax, 1) != 1:
            raise ValueError(f"pipelined mesh must have {ax}=1, got {mesh.shape[ax]}")
    from kubedl_tpu.api.validation import validate_pipeline_shapes

    # the schedule-name/interleave pairing rules live in the SHARED
    # validator (api/validation.py) so submit-time and runtime can't
    # drift; the shape rules re-check inside the schedule builders
    sched_errs = validate_pipeline_shapes(
        mesh.shape["stage"], n_microbatches, interleave,
        schedule=schedule, path="forward_pipelined")
    if sched_errs:
        raise ValueError("; ".join(sched_errs))
    rules = rules or ShardingRules()
    layer_fn = pipeline_layer_fn(config, tokens.shape[1], rules)

    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(config.dtype)
    x = pipeline.microbatch(x, n_microbatches)
    if schedule == "1f1b":
        y, aux = pipeline.pipeline_apply_1f1b(
            params["layers"], x, layer_fn, mesh=mesh,
            interleave=interleave, remat=config.remat,
        )
    else:
        y, aux = pipeline.pipeline_apply(
            params["layers"], x, layer_fn, mesh=mesh, remat=config.remat,
        )
    x = pipeline.unmicrobatch(y)
    return _lm_head(x, params, config), aux


def forward_pipelined(
    params: Dict,
    tokens: jax.Array,
    config: LlamaConfig,
    mesh: Mesh,
    rules: Optional[ShardingRules] = None,
    n_microbatches: int = 4,
    schedule: str = "gpipe",
    interleave: int = 1,
) -> jax.Array:
    return forward_pipelined_and_aux(
        params, tokens, config, mesh, rules=rules,
        n_microbatches=n_microbatches, schedule=schedule,
        interleave=interleave)[0]


def loss_fn_pp(
    params, tokens, config: LlamaConfig, mesh: Mesh, rules=None,
    n_microbatches: int = 4, schedule: str = "gpipe", interleave: int = 1,
):
    logits, aux = forward_pipelined_and_aux(
        params, tokens[:, :-1], config, mesh, rules=rules,
        n_microbatches=n_microbatches, schedule=schedule,
        interleave=interleave,
    )
    return _next_token_ce(logits, tokens[:, 1:]) + config.moe_aux_coef * aux
