"""A model of state-space (Mamba-2) and NoPE attention layers with scalar
multipliers, at a tiny size on the CPU: the program's chunked scan against
the token-by-token recurrence, the program against the plain reference
(`benchmarks/reference/granite_ref.py`), what the layers guarantee
(causality, no position), the loss's two doors, the counters, the
refusals of the paths that have no state for such a layer, the trainer."""
import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights_ssm
from benchmarks.reference import granite_ref
from benchmarks.reference.llama_ref import make_mm
from benchmarks.runners.train_ssm import ssm_config
from kubedl_tpu.models import llama, ssm
from kubedl_tpu.parallel.mesh import ShardingRules, build_mesh

SEQ = 32
ODD = 27  # no multiple of the chunk

# hidden 64; layers mamba, mamba, attention, mamba; 4 state-space heads of
# 16 (inner 64), state 16, chunk 8, 4 taps; 4 query and 2 key/value heads
CFG = {
    "hidden_size": 64, "intermediate_size": 128, "shared_intermediate_size": 128,
    "num_hidden_layers": 4, "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "attention_bias": False,
    "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8,
    "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_expand": 1,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "mamba_conv_bias": True, "mamba_proj_bias": False, "num_local_experts": 0,
    "hidden_act": "silu", "normalization_function": "rmsnorm",
    "position_embedding_type": "nope", "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "vocab_size": 128, "initializer_range": 0.02,
    "torch_dtype": "float32", "remat": "full", "ce_chunks": 4,
}
# the tolerance the bf16 program is held to against the float32 reference,
# per leaf (norm of the gradients' difference over the reference's norm):
# on four seeds bf16 reads up to 0.063 on its worst leaf (a 4-entry A_log)
# and 0.011 on the median one, the fp8 control 0.26-0.43 and 0.088-0.096,
# a state dropped between chunks 0.96-2.2 on its worst leaf
BF16_TOLERANCE = 0.1
BF16_TOLERANCE_MEDIAN = 0.03


def weights(cfg, seed, dtype=jnp.float32):
    """Seeded by weights_ssm.py, at a larger spread than the benchmark's
    0.02 so that at hidden 64 every leaf's gradient is well above zero."""
    tree = weights_ssm.make_fn(dict(cfg, initializer_range=0.2))(jax.random.PRNGKey(seed))
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if a.dtype == jnp.bfloat16 else a, tree)


def tokens_of(seed, seq=SEQ, batch=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                              CFG["vocab_size"])


def program(cfg=CFG, seq=SEQ, **kw):
    return dataclasses.replace(ssm_config(cfg, seq), use_flash=False, **kw)


def leaf_gaps(got, want):
    got = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    assert got.keys() == want.keys()
    out = {}
    for path, w in want.items():
        assert float(jnp.linalg.norm(w)) > 0, jax.tree_util.keystr(path)
        g = got[path].astype(jnp.float32)
        out[jax.tree_util.keystr(path)] = float(
            jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
    return out


def reference_grads(params32, tokens, mode="f32", no_carry=False):
    return jax.jit(jax.value_and_grad(lambda p: granite_ref.loss(
        p, tokens, CFG, make_mm(mode), no_carry)))(params32)


# -- the scan ---------------------------------------------------------------------


def scan_inputs(seq, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    b, h, p, n = 2, 4, 16, 16
    x = jax.random.normal(ks[0], (b, seq, h, p), jnp.float32)
    dt = jnp.exp(jax.random.uniform(ks[1], (b, seq, h), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    a = -jax.random.uniform(ks[2], (h,), jnp.float32, 1.0, 16.0)
    b_ = jax.random.normal(ks[3], (b, seq, n), jnp.float32)
    c_ = jax.random.normal(ks[4], (b, seq, n), jnp.float32)
    return x, dt, a, b_, c_


@pytest.mark.parametrize("seq", [SEQ, ODD])
@pytest.mark.parametrize("chunk", [4, 8, SEQ])
def test_chunked_scan_is_the_token_by_token_recurrence(chunk, seq):
    x, dt, a, b_, c_ = scan_inputs(seq)
    with jax.default_matmul_precision("highest"):
        y, through = jax.jit(ssm.chunked_scan, static_argnums=5)(x, dt, a, b_, c_, chunk)
    want = granite_ref.recurrence(x, jnp.exp(dt * a), dt, b_, c_, block=8)
    assert y.shape == want.shape == x.shape
    assert float(jnp.max(jnp.abs(y - want))) < 2e-5 * float(jnp.max(jnp.abs(want)))
    # the share of a chunk's incoming state that leaves it: the product of
    # its decays, the tail's over the tokens the sequence has
    q = min(chunk, seq)
    first = jnp.exp(jnp.sum((dt * a)[:, :q], axis=1))
    np.testing.assert_allclose(through[:, 0], first, rtol=1e-5)
    assert through.shape == (2, -(-seq // q), 4)


def test_the_scans_gradients_are_the_recurrences():
    x, dt, a, b_, c_ = scan_inputs(ODD, seed=1)

    def ours(*args):
        return jnp.sum(jnp.sin(ssm.chunked_scan(*args, 8)[0]))

    def theirs(x, dt, a, b_, c_):
        return jnp.sum(jnp.sin(granite_ref.recurrence(
            x, jnp.exp(dt * a), dt, b_, c_, block=8)))

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(ours, argnums=(0, 1, 2, 3, 4)))(x, dt, a, b_, c_)
    want = jax.jit(jax.grad(theirs, argnums=(0, 1, 2, 3, 4)))(x, dt, a, b_, c_)
    for g, w in zip(got, want):
        assert float(jnp.linalg.norm(g - w)) < 1e-4 * float(jnp.linalg.norm(w))


# -- the program against the reference ---------------------------------------------


@pytest.mark.parametrize("seq", [SEQ, ODD])
def test_float32_loss_and_every_gradient_leaf_match_the_reference(seq):
    params, tokens = weights(CFG, 3), tokens_of(4, seq)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: llama.loss_fn(p, tokens, program(seq=seq))))(params)
    ref_loss, ref_grads = reference_grads(params, tokens)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    gaps = leaf_gaps(grads, ref_grads)
    assert len(gaps) == 3 * 13 + 9 + 2
    assert max(gaps.values()) < 2e-4, max(gaps.items(), key=lambda kv: kv[1])


@pytest.fixture(scope="module")
def bf16_case():
    """The program in bfloat16 on weights_ssm.py's own dtypes, and the
    float32 reference on the same numbers."""
    params, tokens = weights(CFG, 5, jnp.bfloat16), tokens_of(6, batch=4)
    params32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    config = program(dict(CFG, torch_dtype="bfloat16"))
    got = jax.jit(jax.value_and_grad(lambda p: llama.loss_fn(p, tokens, config)))(params)
    return params32, tokens, got, reference_grads(params32, tokens)


def test_bfloat16_program_is_within_its_tolerance_of_the_reference(bf16_case):
    _, _, (loss, grads), (ref_loss, ref_grads) = bf16_case
    assert abs(float(loss) - float(ref_loss)) < 2e-3 * float(ref_loss)
    gaps = leaf_gaps(grads, ref_grads)
    assert max(gaps.values()) < BF16_TOLERANCE, max(gaps.items(), key=lambda kv: kv[1])
    assert np.median(list(gaps.values())) < BF16_TOLERANCE_MEDIAN


def test_fp8_control_is_outside_the_bfloat16_tolerance(bf16_case):
    params32, tokens, _, (_, ref_grads) = bf16_case
    _, fp8_grads = reference_grads(params32, tokens, mode="fp8")
    gaps = leaf_gaps(fp8_grads, ref_grads)
    assert max(gaps.values()) > 1.5 * BF16_TOLERANCE
    assert np.median(list(gaps.values())) > 2 * BF16_TOLERANCE_MEDIAN


def test_no_carry_is_far_outside_the_tolerance(bf16_case):
    params32, tokens, _, (ref_loss, ref_grads) = bf16_case
    loss, grads = reference_grads(params32, tokens, no_carry=True)
    gaps = leaf_gaps(grads, ref_grads)
    # the leaves through which the loss feels how long a head remembers
    decay = [v for k, v in gaps.items() if k.endswith(("['ssm_A_log']", "['ssm_dt_bias']"))]
    assert len(decay) == 6 and min(decay) > BF16_TOLERANCE
    assert max(decay) == max(gaps.values()) > 5 * BF16_TOLERANCE
    assert float(loss) != float(ref_loss)


# -- what the layers guarantee --------------------------------------------------------


def test_no_output_before_a_changed_token_moves():
    """Convolution and scan are causal, through every kind of layer."""
    config, params = program(), weights(CFG, 7)
    tokens = tokens_of(8)[:, :SEQ]
    j = 13  # inside a chunk, so the chunk's own masked product is tested too
    changed = tokens.at[:, j].set((tokens[:, j] + 1) % CFG["vocab_size"])
    fwd = jax.jit(lambda t: llama.forward(params, t, config))
    a, b = fwd(tokens), fwd(changed)
    np.testing.assert_array_equal(np.asarray(a[:, :j]), np.asarray(b[:, :j]))
    assert float(jnp.max(jnp.abs(a[:, j:] - b[:, j:]))) > 1e-3


def test_attention_layer_takes_no_position():
    config, params = program(), weights(CFG, 9)
    layer = params["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 64), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(SEQ, dtype=jnp.int32)[None], (2, SEQ))
    rules = ShardingRules()
    out = lambda p, c: llama._attention_block(x, layer, c, p, None, rules, 1)[0]
    np.testing.assert_array_equal(np.asarray(out(pos, config)),
                                  np.asarray(out(pos * 7 + 100, config)))
    roped = dataclasses.replace(config, use_rope=True)
    assert float(jnp.max(jnp.abs(out(pos, roped) - out(pos * 7 + 100, roped)))) > 1e-3


def test_chunked_loss_is_the_whole_logits_loss_under_logits_scaling():
    params, tokens = weights(CFG, 10), tokens_of(11)
    assert program().ce_chunks == 4 and program().logits_scaling == 8
    grad = lambda c: jax.jit(jax.value_and_grad(lambda p: llama.loss_fn(p, tokens, c)))(params)
    (chunked, g_chunked), (whole, g_whole) = grad(program()), grad(program(ce_chunks=0))
    assert float(chunked) == pytest.approx(float(whole), rel=1e-6)
    assert max(leaf_gaps(g_chunked, g_whole).values()) < 1e-5
    # and the scaling is in both: without it the loss is another
    unscaled = grad(program(logits_scaling=1.0))[0]
    assert abs(float(unscaled) - float(chunked)) > 1e-3


def test_chunked_loss_sums_its_chunks_cotangents_in_float32():
    """The loop over vocabulary pieces sums each piece's cotangent of the
    normed state in the dtype of what it closes over: float32 also where
    the model is bfloat16 (a bf16 running sum drops a piece's part where
    it is under half an ulp of the target row's, every token alike)."""
    config = program(dict(CFG, torch_dtype="bfloat16"))
    params, tokens = weights(CFG, 18, jnp.bfloat16), tokens_of(19)
    text = str(jax.make_jaxpr(jax.grad(lambda p: llama.loss_fn(p, tokens, config)))(params))
    carried = [line for line in text.split("\n") if "= scan[" in line]
    assert any(f"f32[2,{SEQ},64]" in line for line in carried)
    assert not any(f"bf16[2,{SEQ},64]" in line.split("= scan[")[0] for line in carried)


def test_remat_on_and_off_agree():
    params, tokens = weights(CFG, 12), tokens_of(13)
    grad = lambda c: jax.jit(jax.value_and_grad(lambda p: llama.loss_fn(p, tokens, c)))(params)
    (on, g_on), (off, g_off) = grad(program()), grad(program(remat=False))
    assert float(on) == pytest.approx(float(off), rel=1e-6)
    assert max(leaf_gaps(g_on, g_off).values()) < 1e-5


def test_multipliers_of_one_rope_on_and_no_ssm_layer_are_the_plain_decoder():
    """Bit for bit, and with no multiply emitted."""
    plain = llama.LlamaConfig.tiny(use_flash=False, dtype=jnp.float32)
    spelled = dataclasses.replace(
        plain, layer_types=("attention",) * plain.n_layers, use_rope=True,
        residual_multiplier=1.0, logits_scaling=1.0, embed_scale=1.0)
    params = llama.init(plain, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, plain.vocab_size)
    grad = lambda c: jax.jit(jax.value_and_grad(lambda p: llama.loss_fn(p, tokens, c)))(params)
    (a, ga), (b, gb) = grad(plain), grad(spelled)
    assert float(a) == float(b)
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)), ga, gb)
    ops = lambda c: re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(
        lambda p: llama.loss_fn(p, tokens, c))(params)))
    assert ops(plain) == ops(spelled)
    # each scalar adds its own operations and nothing else
    count = lambda c, op: ops(c).count(f" {op} ")
    scaled = dataclasses.replace(plain, residual_multiplier=0.22)
    assert count(scaled, "mul") == count(plain, "mul") + 2 * plain.n_layers
    assert count(dataclasses.replace(plain, logits_scaling=8.0), "div") == count(plain, "div") + 1


def test_two_devices_under_fsdp_give_the_one_device_loss():
    config, params, tokens = program(), weights(CFG, 14), tokens_of(15, batch=4)
    one = float(jax.jit(lambda p: llama.loss_fn(p, tokens, config))(params))
    mesh, rules = build_mesh({"fsdp": 2}, devices=jax.devices()[:2]), ShardingRules()
    specs = llama.param_specs(config, rules)
    placed = jax.tree_util.tree_map(
        lambda a, s: jax.device_put(a, jax.sharding.NamedSharding(mesh, s)), params, specs)
    two = float(jax.jit(lambda p, t: llama.loss_fn(p, t, config, mesh=mesh, rules=rules))(
        placed, jax.device_put(tokens, jax.sharding.NamedSharding(
            mesh, rules.spec("batch", None)))))
    assert two == pytest.approx(one, rel=1e-5)


# -- sizes and counters ------------------------------------------------------------------


@pytest.mark.parametrize("layers,count", [(40, 3_191_396_096), (10, 951_991_232)])
def test_published_sizes_count_their_parameters(layers, count):
    config = llama.LlamaConfig.granite_4_0_h_micro()
    assert config.layer_types.count("attention") == 4 and len(config.layer_types) == 40
    config = dataclasses.replace(
        config, n_layers=layers, layer_types=config.layer_types[:layers])
    shapes = jax.eval_shape(lambda: llama.init(config, jax.random.PRNGKey(0)))
    assert llama.param_count(shapes) == count
    specs = llama.param_specs(config)
    is_spec = lambda s: isinstance(s, jax.sharding.PartitionSpec)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda s: 0, specs, is_leaf=is_spec)
    ) == jax.tree_util.tree_structure(jax.tree_util.tree_map(lambda s: 0, shapes))
    with open("benchmarks/configs/granite-4.0-h-micro-d10.json") as f:
        cut = ssm_config(json.load(f), 8192)
    assert dataclasses.replace(cut, max_seq_len=131072, ce_chunks=0) == dataclasses.replace(
        llama.LlamaConfig.granite_4_0_h_micro(), n_layers=10, layer_types=cut.layer_types)


@pytest.mark.parametrize("seq,chunks", [(SEQ, 4), (ODD, 4), (8, 1)])
def test_counters_count_layers_chunks_and_what_a_chunk_carries(seq, chunks):
    params, tokens = weights(CFG, 16), tokens_of(17, seq)
    _, stats = jax.jit(lambda p: llama.loss_and_stats(p, tokens, program(seq=seq)))(params)
    assert float(stats["ssm_layers"]) == 3
    assert float(stats["ssm_chunks"]) == 3 * 2 * chunks
    assert 0 < float(stats["ssm_state_carry"]) < 1
    assert 1e-3 < float(stats["ssm_dt_mean"]) < 1e-1 * np.e ** 3
    plain = llama.LlamaConfig.tiny(use_flash=False)
    assert llama.loss_and_stats(llama.init(plain, jax.random.PRNGKey(0)),
                                jnp.zeros((1, 9), jnp.int32), plain)[1] == {}


def test_ssm_layers_need_their_sizes():
    with pytest.raises(ValueError, match="ssm_heads"):
        llama.LlamaConfig.tiny(layer_types=("ssm", "attention"))
    with pytest.raises(ValueError, match="attention, conv, ssm"):
        llama.LlamaConfig.tiny(layer_types=("mamba", "attention"))


# -- the paths that have no state for such a layer -------------------------------------


def refuse_cached_decode(config):
    from kubedl_tpu.models import decode

    decode.init_kv_cache(config, 1, 64)


def refuse_serving(config):
    from kubedl_tpu.models.serving import ServingEngine

    ServingEngine(llama.init(config, jax.random.PRNGKey(0)), config, slots=2, max_len=64)


def refuse_pipelined(config):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("stage",))
    llama.forward_pipelined_and_aux({}, jnp.zeros((2, 8), jnp.int32), config, mesh)


def refuse_import(config):
    from types import SimpleNamespace

    from kubedl_tpu.models.import_hf import config_from_hf

    config_from_hf(SimpleNamespace(model_type="granitemoehybrid",
                                   layer_types=CFG["layer_types"]))


def refuse_context_mesh(config):
    mesh = build_mesh({"context": 2}, devices=jax.devices()[:2])
    llama.loss_fn(llama.init(config, jax.random.PRNGKey(0)), tokens_of(1), config,
                  mesh=mesh, rules=ShardingRules())


def conv_model():
    return llama.LlamaConfig.tiny(layer_types=("conv", "attention"), use_flash=False)


@pytest.mark.parametrize("path,config,error,says", [
    (refuse_cached_decode, program, NotImplementedError,
     r"init_kv_cache\) has no state for a state-space \(ssm\) layer"),
    (refuse_serving, program, NotImplementedError,
     r"ServingEngine has no state for a state-space \(ssm\) layer"),
    (refuse_pipelined, program, NotImplementedError,
     r"the pipelined forward has no state for a state-space \(ssm\) layer"),
    (refuse_import, program, ValueError, r"3 state-space \(mamba\) layers"),
    (refuse_context_mesh, program, NotImplementedError,
     r"context: 2 splits the sequence over devices: a ssm layer"),
    (refuse_context_mesh, conv_model, NotImplementedError,
     r"context: 2 splits the sequence over devices: a conv layer"),
], ids=["cached_decode", "serving", "pipelined", "hf_import", "context_mesh",
        "context_mesh_conv"])
def test_paths_with_no_state_for_the_layer_refuse_it_by_name(path, config, error, says):
    with pytest.raises(error, match=says):
        path(config())


# -- through the trainer -------------------------------------------------------------------


def test_trainer_main_trains_the_preset_and_records_the_ssm_counters(
        tmp_path, monkeypatch):
    from kubedl_tpu.obs import load_spans
    from kubedl_tpu.train import trainer

    published = llama.LlamaConfig.granite_4_0_h_micro
    monkeypatch.setattr(llama.LlamaConfig, "granite_4_0_h_micro", staticmethod(
        lambda **kw: published(
            vocab_size=256, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, d_ff=128,
            max_seq_len=64, layer_types=("ssm", "ssm", "attention", "ssm"),
            ssm_heads=4, ssm_head_dim=16, ssm_state=16, ssm_chunk=8,
            query_pre_attn_scalar=256.0, **kw)))
    trace_dir = str(tmp_path / "trace")
    monkeypatch.setenv("KUBEDL_MESH", "data=-1")
    monkeypatch.setenv("KUBEDL_TRACE_DIR", trace_dir)
    monkeypatch.setenv("KUBEDL_TRACE_ID", "0" * 32)
    monkeypatch.setenv("POD_NAME", "ssm-worker-0")
    assert trainer.main(["--model", "granite-4.0-h-micro", "--batch", "8", "--seq-len",
                         "21", "--steps", "2", "--log-every", "1", "--ce-chunks", "4"]) == 0
    steps = [s for s in load_spans(trace_dir)
             if s["name"] in ("train.compile", "train.step")]
    assert len(steps) == 2
    for s in steps:
        a = s["attrs"]
        assert a["ssm_layers"] == 3 and a["ssm_chunks"] == 3 * 8 * 3
        assert 0 < a["ssm_state_carry"] < 1 and a["ssm_dt_mean"] > 0
    from kubedl_tpu.cli import _span_detail

    detail = _span_detail(steps[-1]["attrs"])
    assert detail.startswith("step=2 ssm_layers=3 chunks=72 carry=0.")
