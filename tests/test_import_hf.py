"""HF Llama import (models/import_hf.py): logits parity with the
transformers reference implementation — an EXTERNAL correctness pin on
the whole Llama stack (rope convention, GQA, SwiGLU, rms-norm, head)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")

from kubedl_tpu.models import decode, llama
from kubedl_tpu.models.import_hf import config_from_hf, params_from_state_dict


@pytest.fixture(scope="module")
def hf_pair():
    hf_config = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=144,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False, attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(hf_config).eval()
    config = config_from_hf(hf_config, dtype=jnp.float32, use_flash=False)
    params = params_from_state_dict(model.state_dict(), config)
    return model, params, config


def test_config_mapping(hf_pair):
    _, _, config = hf_pair
    assert (config.vocab_size, config.d_model, config.n_layers) == (128, 64, 2)
    assert (config.n_heads, config.n_kv_heads, config.d_ff) == (4, 2, 144)
    assert config.head_dim == 16


def test_logits_match_transformers(hf_pair):
    model, params, config = hf_pair
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, config.vocab_size, size=(2, 12))
    with torch.no_grad():
        ref = model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(llama.forward(params, jnp.asarray(tokens), config))
    np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=2e-3)


def test_greedy_decode_matches_transformers_generate(hf_pair):
    model, params, config = hf_pair
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, config.vocab_size, size=(1, 7))
    with torch.no_grad():
        ref = model.generate(
            torch.tensor(prompt), max_new_tokens=6, do_sample=False,
            pad_token_id=0,
        ).numpy()[0, 7:]
    ours = np.asarray(jax.device_get(decode.generate(
        params, jnp.asarray(prompt), config, max_new_tokens=6, max_len=13)))[0]
    np.testing.assert_array_equal(ours, ref)


def test_tied_embeddings_import():
    hf_config = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=True,
        attn_implementation="eager",
    )
    torch.manual_seed(1)
    model = transformers.LlamaForCausalLM(hf_config).eval()
    config = config_from_hf(hf_config, dtype=jnp.float32, use_flash=False)
    assert config.tie_embeddings
    params = params_from_state_dict(model.state_dict(), config)
    assert "lm_head" not in params
    tokens = np.arange(6)[None, :]
    with torch.no_grad():
        ref = model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(llama.forward(params, jnp.asarray(tokens), config))
    np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=2e-3)


def test_unsupported_configs_rejected():
    base = dict(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=64, attn_implementation="eager",
    )
    # llama3/linear scaling is implemented (see the parity tests below);
    # NTK-style dynamic scaling is not, and must refuse loudly
    scaled = transformers.LlamaConfig(
        **base, rope_scaling={"rope_type": "yarn", "factor": 8.0})
    with pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf(scaled)
    biased = transformers.LlamaConfig(**base, attention_bias=True)
    with pytest.raises(ValueError, match="bias"):
        config_from_hf(biased)


# ---------------------------------------------------------------------------
# Mistral: same weight layout + sliding-window attention
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mistral_pair():
    hf_config = transformers.MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=144,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=10000.0,
        sliding_window=8, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(2)
    model = transformers.MistralForCausalLM(hf_config).eval()
    config = config_from_hf(hf_config, dtype=jnp.float32, use_flash=False)
    params = params_from_state_dict(model.state_dict(), config)
    return model, params, config


def test_mistral_config_maps_sliding_window(mistral_pair):
    _, _, config = mistral_pair
    assert config.sliding_window == 8


def test_mistral_logits_match_transformers(mistral_pair):
    model, params, config = mistral_pair
    rng = np.random.default_rng(5)
    # 24 tokens >> window 8: the window mask matters
    tokens = rng.integers(0, config.vocab_size, size=(2, 24))
    with torch.no_grad():
        ref = model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(llama.forward(params, jnp.asarray(tokens), config))
    np.testing.assert_allclose(ours, ref, atol=3e-4, rtol=3e-3)
    # sanity: the window genuinely changes our logits
    import dataclasses

    full_cfg = dataclasses.replace(config, sliding_window=None)
    full = np.asarray(llama.forward(params, jnp.asarray(tokens), full_cfg))
    assert np.abs(full - ours).max() > 1e-3


def test_mistral_greedy_decode_matches_transformers(mistral_pair):
    model, params, config = mistral_pair
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, config.vocab_size, size=(1, 13))
    with torch.no_grad():
        ref = model.generate(
            torch.tensor(prompt), max_new_tokens=8, do_sample=False,
            pad_token_id=0,
        ).numpy()[0, 13:]
    ours = np.asarray(jax.device_get(decode.generate(
        params, jnp.asarray(prompt), config, max_new_tokens=8, max_len=21)))[0]
    np.testing.assert_array_equal(ours, ref)


# ---------------------------------------------------------------------------
# Gemma: GeGLU + (1+w) RMSNorm + sqrt(d) embedding scale, tied head
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gemma_pair():
    hf_config = transformers.GemmaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=144,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=128, rms_norm_eps=1e-5,
        attn_implementation="eager",
    )
    torch.manual_seed(3)
    model = transformers.GemmaForCausalLM(hf_config).eval()
    config = config_from_hf(hf_config, dtype=jnp.float32, use_flash=False)
    params = params_from_state_dict(model.state_dict(), config)
    return model, params, config


def test_gemma_config_mapping(gemma_pair):
    _, _, config = gemma_pair
    assert config.act == "gelu_tanh"
    assert config.norm_offset == 1.0
    assert config.embed_scale == pytest.approx(8.0)
    assert config.tie_embeddings


@pytest.mark.slow
def test_gemma_logits_match_transformers(gemma_pair):
    model, params, config = gemma_pair
    rng = np.random.default_rng(7)
    tokens = rng.integers(0, config.vocab_size, size=(2, 14))
    with torch.no_grad():
        ref = model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(llama.forward(params, jnp.asarray(tokens), config))
    np.testing.assert_allclose(ours, ref, atol=3e-4, rtol=3e-3)


@pytest.mark.slow
def test_gemma_greedy_decode_matches_transformers(gemma_pair):
    model, params, config = gemma_pair
    rng = np.random.default_rng(8)
    prompt = rng.integers(1, config.vocab_size, size=(1, 9))
    with torch.no_grad():
        ref = model.generate(
            torch.tensor(prompt), max_new_tokens=6, do_sample=False,
            pad_token_id=0,
        ).numpy()[0, 9:]
    ours = np.asarray(jax.device_get(decode.generate(
        params, jnp.asarray(prompt), config, max_new_tokens=6, max_len=15)))[0]
    np.testing.assert_array_equal(ours, ref)


def test_unknown_model_type_rejected():
    cfg = transformers.GPT2Config()
    with pytest.raises(ValueError, match="unsupported model_type"):
        config_from_hf(cfg)


@pytest.mark.slow
def test_gemma_chunked_ce_matches_full(gemma_pair):
    """ce_chunks and the DPO chunked logprobs must apply the (1+w) final
    norm like the unchunked head — pinned on a real Gemma import."""
    import dataclasses

    _, params, config = gemma_pair
    rng = np.random.default_rng(10)
    tokens = jnp.asarray(rng.integers(1, config.vocab_size, size=(2, 12)))
    full = llama.loss_fn(params, tokens, config)
    chunked = llama.loss_fn(
        params, tokens, dataclasses.replace(config, ce_chunks=4))
    np.testing.assert_allclose(float(chunked), float(full), rtol=1e-5)

    from kubedl_tpu.train.preference import sequence_logprobs

    pl = jnp.asarray([2, 3])
    sl = jnp.asarray([10, 12])
    lp_full = sequence_logprobs(params, tokens, pl, sl, config)
    lp_chunk = sequence_logprobs(
        params, tokens, pl, sl, dataclasses.replace(config, ce_chunks=4))
    np.testing.assert_allclose(np.asarray(lp_chunk), np.asarray(lp_full),
                               rtol=2e-5, atol=2e-5)


def test_gemma_fresh_init_effective_norm_gain_is_one():
    config = llama.LlamaConfig.tiny(norm_offset=1.0)
    params = llama.init(config, jax.random.PRNGKey(0))
    # stored weight 0 -> (w + offset) == 1 at step 0, like HF Gemma
    assert float(jnp.max(jnp.abs(params["layers"][0]["attn_norm"]))) == 0.0
    assert float(jnp.max(jnp.abs(params["final_norm"]))) == 0.0


@pytest.mark.slow
def test_rope_scaling_llama3_logits_parity():
    """Llama-3.1-style rope scaling: logits must match transformers'
    reference implementation of the 'llama3' frequency rescale."""
    hf_config = transformers.LlamaConfig(
        vocab_size=96, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, rope_theta=10000.0,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 64},
        attn_implementation="eager",
    )
    torch.manual_seed(3)
    model = transformers.LlamaForCausalLM(hf_config).eval()
    config = config_from_hf(hf_config, dtype=jnp.float32, use_flash=False)
    assert config.rope_scaling is not None
    assert config.rope_scaling.kind == "llama3"
    params = params_from_state_dict(model.state_dict(), config)
    rng = np.random.default_rng(5)
    # positions past original_max/factor boundaries exercise all three
    # frequency bands
    tokens = rng.integers(0, config.vocab_size, size=(2, 100))
    with torch.no_grad():
        ref = model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(llama.forward(params, jnp.asarray(tokens), config))
    np.testing.assert_allclose(ours, ref, atol=3e-4, rtol=3e-3)

    # cached decode shares the same rope: greedy continuation matches
    prompt = tokens[:1, :40]
    with torch.no_grad():
        hf_gen = model.generate(
            torch.tensor(prompt), max_new_tokens=5, do_sample=False,
            pad_token_id=0).numpy()[0, 40:]
    ours_gen = np.asarray(jax.device_get(decode.generate(
        params, jnp.asarray(prompt), config, max_new_tokens=5,
        max_len=45)))[0]
    np.testing.assert_array_equal(ours_gen, hf_gen)


def test_rope_scaling_linear_and_rejections():
    from kubedl_tpu.models.llama import RopeScaling, _rope_freqs

    base = _rope_freqs(8, 10000.0, None)
    lin = _rope_freqs(8, 10000.0, RopeScaling(kind="linear", factor=4.0))
    np.testing.assert_allclose(lin, base / 4.0, rtol=1e-6)

    l3 = _rope_freqs(
        8, 10000.0, RopeScaling(kind="llama3", factor=8.0,
                                original_max_position_embeddings=64))
    # highest frequency (short wavelength) untouched; lowest divided
    assert l3[0] == pytest.approx(base[0])
    assert l3[-1] == pytest.approx(base[-1] / 8.0)
    # monotype guard: unknown kinds refuse loudly (yarn is a kind since
    # latent attention trains: tests/test_latent_model.py has its table)
    with pytest.raises(ValueError, match="unknown rope scaling"):
        _rope_freqs(8, 10000.0, RopeScaling(kind="dynamic", factor=2.0))
    yarn = _rope_freqs(8, 10000.0, RopeScaling(
        kind="yarn", factor=2.0, original_max_position_embeddings=64))
    assert yarn[0] == pytest.approx(base[0])
    assert yarn[-1] == pytest.approx(base[-1] / 2.0)

    hf_config = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2,
        max_position_embeddings=64,
        rope_scaling={"rope_type": "dynamic", "factor": 2.0},
        attn_implementation="eager",
    )
    with pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf(hf_config)


def test_rope_scaling_linear_config_mapping_and_required_keys():
    """The linear branch maps through config_from_hf; llama3 with
    missing required keys refuses instead of guessing boundaries."""
    hf_config = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2,
        max_position_embeddings=64,
        rope_scaling={"rope_type": "linear", "factor": 2.0},
        attn_implementation="eager",
    )
    config = config_from_hf(hf_config)
    assert config.rope_scaling is not None
    assert (config.rope_scaling.kind, config.rope_scaling.factor) == (
        "linear", 2.0)

    # transformers itself may validate llama3 keys at construction, so
    # use a duck-typed config (config_from_hf only getattr's) to pin
    # OUR refusal for hand-edited/partial configs
    import types

    partial = types.SimpleNamespace(
        model_type="llama", vocab_size=64, hidden_size=32,
        intermediate_size=64, num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=2, max_position_embeddings=64,
        rope_scaling={"rope_type": "llama3", "factor": 8.0},
    )
    with pytest.raises(ValueError, match="missing"):
        config_from_hf(partial)


# ---------------------------------------------------------------------------
# Qwen2: Llama layout + biased q/k/v projections
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def qwen2_pair():
    hf_config = transformers.Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=144,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=10000.0,
        use_sliding_window=False, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(4)
    model = transformers.Qwen2ForCausalLM(hf_config).eval()
    config = config_from_hf(hf_config, dtype=jnp.float32, use_flash=False)
    params = params_from_state_dict(model.state_dict(), config)
    return model, params, config


def test_qwen2_config_and_bias_import(qwen2_pair):
    model, params, config = qwen2_pair
    assert config.attn_qkv_bias
    # use_sliding_window=False: the config's carried window must NOT map
    assert config.sliding_window is None
    layer = params["layers"][0]
    assert layer["bq"].shape == (64,) and layer["bk"].shape == (32,)
    # biases were actually LOADED from the checkpoint, not synthesized
    hf_bias = model.state_dict()[
        "model.layers.0.self_attn.q_proj.bias"].numpy()
    np.testing.assert_allclose(np.asarray(layer["bq"]), hf_bias, rtol=1e-6)

    # use_sliding_window=True maps HF's per-layer scheme: full attention
    # below max_window_layers, windowed at and above it
    windowed = transformers.Qwen2Config(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=3, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=64, use_sliding_window=True,
        sliding_window=16, max_window_layers=1,
        attn_implementation="eager")
    wcfg = config_from_hf(windowed)
    assert wcfg.layer_windows == (None, 16, 16)
    assert wcfg.sliding_window is None


@pytest.mark.slow
def test_qwen2_per_layer_windows_logits_parity():
    """use_sliding_window Qwen2: sequences longer than the window must
    match HF's eager reference, which windows only the layers at/above
    max_window_layers."""
    hf_config = transformers.Qwen2Config(
        vocab_size=96, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, use_sliding_window=True,
        sliding_window=8, max_window_layers=1,
        attn_implementation="eager")
    torch.manual_seed(6)
    model = transformers.Qwen2ForCausalLM(hf_config).eval()
    config = config_from_hf(hf_config, dtype=jnp.float32, use_flash=False)
    assert config.layer_windows == (None, 8, 8)
    params = params_from_state_dict(model.state_dict(), config)
    rng = np.random.default_rng(10)
    tokens = rng.integers(0, config.vocab_size, size=(2, 30))  # 30 >> 8
    with torch.no_grad():
        ref = model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(llama.forward(params, jnp.asarray(tokens), config))
    np.testing.assert_allclose(ours, ref, atol=3e-4, rtol=3e-3)
    # the per-layer pattern genuinely differs from windowing every layer
    import dataclasses

    uniform = dataclasses.replace(config, layer_windows=(8, 8, 8))
    uni = np.asarray(llama.forward(params, jnp.asarray(tokens), uniform))
    assert np.abs(uni - ours).max() > 1e-3

    # cached greedy decode shares the per-layer masks. The reference is
    # HF's TEACHER-FORCED forward (argmax of model(toks).logits each
    # step): transformers' generate() produces different tokens than
    # its own forward for use_sliding_window configs (verified with
    # use_cache=False too — an upstream mask-construction inconsistency,
    # not a cache effect), and the forward is the model's definition.
    prompt = tokens[:1, :20]
    toks = prompt.copy()
    with torch.no_grad():
        for _ in range(6):
            step_logits = model(torch.tensor(toks)).logits.numpy()
            toks = np.concatenate(
                [toks, [[int(np.argmax(step_logits[0, -1]))]]], axis=1)
    hf_gen = toks[0, 20:]
    ours_gen = np.asarray(jax.device_get(decode.generate(
        params, jnp.asarray(prompt), config, max_new_tokens=6,
        max_len=26)))[0]
    np.testing.assert_array_equal(ours_gen, hf_gen)


def test_qwen2_logits_match_transformers(qwen2_pair):
    model, params, config = qwen2_pair
    rng = np.random.default_rng(8)
    tokens = rng.integers(0, config.vocab_size, size=(2, 14))
    with torch.no_grad():
        ref = model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(llama.forward(params, jnp.asarray(tokens), config))
    np.testing.assert_allclose(ours, ref, atol=3e-4, rtol=3e-3)


def test_qwen2_greedy_decode_matches_transformers(qwen2_pair):
    model, params, config = qwen2_pair
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, config.vocab_size, size=(1, 9))
    with torch.no_grad():
        ref = model.generate(
            torch.tensor(prompt), max_new_tokens=7, do_sample=False,
            pad_token_id=0,
        ).numpy()[0, 9:]
    ours = np.asarray(jax.device_get(decode.generate(
        params, jnp.asarray(prompt), config, max_new_tokens=7,
        max_len=16)))[0]
    np.testing.assert_array_equal(ours, ref)


def test_qwen2_disabled_window_spellings_collapse_to_full():
    """use_sliding_window=True with sliding_window None/0, or with
    max_window_layers covering every layer, is full attention — not a
    crash, not an all-None layer_windows tuple."""
    import types

    base = dict(
        model_type="qwen2", vocab_size=64, hidden_size=32,
        intermediate_size=64, num_hidden_layers=2, num_attention_heads=2,
        num_key_value_heads=2, max_position_embeddings=64)
    for extra in (
        {"use_sliding_window": True, "sliding_window": None},
        {"use_sliding_window": True, "sliding_window": 0},
        {"use_sliding_window": True, "sliding_window": 16,
         "max_window_layers": 2},  # == n_layers: nothing windowed
    ):
        cfg = config_from_hf(types.SimpleNamespace(**base, **extra))
        assert cfg.layer_windows is None and cfg.sliding_window is None, extra


# ---------------------------------------------------------------------------
# Gemma-2: sandwich norms, logit softcapping, query_pre_attn_scalar,
# decoupled head_dim, alternating local/global attention
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gemma2_pair():
    hf_config = transformers.Gemma2Config(
        vocab_size=96, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=24,  # deliberately != hidden/heads = 16
        max_position_embeddings=128, rms_norm_eps=1e-5,
        query_pre_attn_scalar=32.0, attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0, sliding_window=8,
        attn_implementation="eager",
    )
    torch.manual_seed(7)
    model = transformers.Gemma2ForCausalLM(hf_config).eval()
    config = config_from_hf(hf_config, dtype=jnp.float32)
    params = params_from_state_dict(model.state_dict(), config)
    return model, params, config


def test_gemma2_config_mapping(gemma2_pair):
    _, params, config = gemma2_pair
    assert config.post_block_norms and config.head_dim == 24
    assert config.attn_logit_softcap == 50.0
    assert config.final_logit_softcap == 30.0
    assert config.query_pre_attn_scalar == 32.0
    assert config.q_prescale == pytest.approx((24 / 32.0) ** 0.5)
    # alternating local/global windows came from layer_types
    assert config.layer_windows is not None
    assert any(w is not None for w in config.layer_windows)
    assert any(w is None for w in config.layer_windows)
    layer = params["layers"][0]
    assert "post_attn_norm" in layer and "post_mlp_norm" in layer


@pytest.mark.slow
def test_gemma2_logits_match_transformers(gemma2_pair):
    model, params, config = gemma2_pair
    rng = np.random.default_rng(11)
    tokens = rng.integers(0, config.vocab_size, size=(2, 24))  # 24 >> 8
    with torch.no_grad():
        ref = model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(llama.forward(params, jnp.asarray(tokens), config))
    np.testing.assert_allclose(ours, ref, atol=3e-4, rtol=3e-3)


@pytest.mark.slow
def test_gemma2_greedy_decode_matches_teacher_forced(gemma2_pair):
    """Cached decode shares the softcap/prescale/sandwich-norm math:
    greedy continuation equals argmax over the full forward each step
    (the model's definition; see the Qwen2 note on HF generate)."""
    model, params, config = gemma2_pair
    rng = np.random.default_rng(12)
    prompt = rng.integers(0, config.vocab_size, size=(1, 14))
    toks = prompt.copy()
    with torch.no_grad():
        for _ in range(6):
            step_logits = model(torch.tensor(toks)).logits.numpy()
            toks = np.concatenate(
                [toks, [[int(np.argmax(step_logits[0, -1]))]]], axis=1)
    ours = np.asarray(jax.device_get(decode.generate(
        params, jnp.asarray(prompt), config, max_new_tokens=6,
        max_len=20)))[0]
    np.testing.assert_array_equal(ours, toks[0, 14:])


def test_gemma2_flash_kernel_matches_xla_path(gemma2_pair):
    """The Pallas kernel's native softcap: a Gemma-2 forward with
    use_flash=True matches the XLA reference path."""
    import dataclasses

    _, params, config = gemma2_pair
    flash_cfg = dataclasses.replace(config, use_flash=True)
    rng = np.random.default_rng(13)
    tokens = rng.integers(0, config.vocab_size, size=(2, 24))
    ref = np.asarray(llama.forward(params, jnp.asarray(tokens), config))
    out = np.asarray(llama.forward(params, jnp.asarray(tokens), flash_cfg))
    np.testing.assert_allclose(out, ref, atol=3e-4, rtol=3e-3)
