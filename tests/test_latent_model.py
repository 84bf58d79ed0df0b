"""A model of latent-attention (MLA) blocks on four residual streams mixed
by manifold-constrained hyper-connections, with a shared expert beside
the routed ones and a multi-token prediction module, against the plain
reference `benchmarks/reference/xing_ref.py`, on seeded weights at a
small size; its parts alone; the parameter counts of the published model
and of the benchmark's cut; the refusals of the paths that cannot run
such a model yet; the trainer."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights_xing
from benchmarks.reference import xing_ref
from benchmarks.reference.llama_ref import make_mm
from benchmarks.runners.train_latent import latent_config
from kubedl_tpu.models import hyper, llama, moe

SEQ = 32

# hidden 64, 4 heads, keys 16 + 8, values 16, q rank 24, kv rank 16, 4
# streams, one dense and two expert blocks of 8 experts top 2 with a shared
# expert, one MTP module
CFG = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "n_routed_experts": 8, "router_outputs": 8,
    "first_expert": 0, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1, "hidden_act": "silu",
    "attention_bias": False, "moe_layer_freq": 1, "tie_word_embeddings": False,
    "kv_lora_rank": 16, "q_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rope_theta": 10000, "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096, "type": "yarn"},
    "num_nextn_predict_layers": 1, "mtp_loss_weight": 0.3, "router_norm_eps": 1e-20,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "initializer_range": 0.02,
    "torch_dtype": "float32", "remat": "full",
}

CASES = {
    "all_experts_held": {},
    "two_of_eight_held": {"n_routed_experts": 2, "first_expert": 4},
    "no_mtp_module": {"num_nextn_predict_layers": 0},
}


def weights(cfg, seed, dtype=jnp.float32):
    # a larger spread than the benchmark's 0.02, so that at hidden 64 the
    # router's scores differ and every leaf's gradient is well above zero
    tree = weights_xing.make_fn(dict(cfg, initializer_range=0.2))(jax.random.PRNGKey(seed))
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if a.dtype == jnp.bfloat16 else a, tree)


def tokens_of(cfg, seed, batch=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ + 1), 0,
                              cfg["vocab_size"])


def program_config(cfg, **kw):
    return dataclasses.replace(latent_config(cfg, SEQ), use_flash=False, **kw)


def leaf_gaps(got, want):
    """Each leaf's |got - want| / |want|, but for the leaves whose
    reference gradient is under a hundredth of the median leaf's, which
    are so by structure and hold round-off alone: the first mapping of
    the stack and of the module reads streams that are still equal, the
    last residual mapping before the streams are summed cannot move a sum
    whose columns sum to 1, and no bias of a router takes a gradient."""
    got = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    assert got.keys() == want.keys()
    norms = {p: float(jnp.linalg.norm(w.astype(jnp.float32))) for p, w in want.items()}
    floor = 1e-2 * float(np.median(list(norms.values())))
    return {jax.tree_util.keystr(p): float(jnp.linalg.norm(
        got[p].astype(jnp.float32) - want[p].astype(jnp.float32))) / norms[p]
        for p in want if norms[p] > floor}


def reference_grads(cfg, params, tokens, mode="f32", fault=None):
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    return jax.jit(jax.value_and_grad(lambda p: xing_ref.loss(
        p, tokens, cfg, mm=make_mm(mode), fault=fault)))(f32)


# -- the whole model against the reference ------------------------------------------------


@pytest.mark.parametrize("case", sorted(CASES))
def test_float32_loss_and_every_gradient_leaf_match_the_reference(case):
    cfg = dict(CFG, **CASES[case])
    params, tokens = weights(cfg, 3), tokens_of(cfg, 4)
    config = program_config(cfg)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: llama.loss_fn(p, tokens, config)))(params)
    ref_loss, ref_grads = reference_grads(cfg, params, tokens)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    gaps = leaf_gaps(grads, ref_grads)
    assert len(gaps) > 60
    assert max(gaps.values()) < 5e-4, max(gaps.items(), key=lambda kv: kv[1])
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        if jax.tree_util.keystr(path).endswith("['router_bias']"):
            assert not np.any(np.asarray(g))


# bfloat16 weights and activations against the float32 reference: the
# median leaf's gradient within BF16_MEDIAN (bfloat16 keeps 8 bits: 4e-3 a
# rounding, a few of them in a row), which float8's 4 bits do not meet.
# Every expert is chosen (8 of 8, weighed by its score): at hidden 64 and
# this spread a top-2 choice flips under bfloat16 on a tenth of the tokens
# and moves the median leaf by 0.2, which is the router's doing and no
# precision's (the benchmark's grad_gap_steady sets the routers aside too)
BF16_MEDIAN = 0.04


@pytest.fixture(scope="module")
def bf16_case():
    cfg = dict(CFG, torch_dtype="bfloat16", num_experts_per_tok=8)
    params, tokens = weights(cfg, 5, jnp.bfloat16), tokens_of(cfg, 6)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, tokens, program_config(cfg))))(params)
    ref_loss, ref_grads = reference_grads(cfg, params, tokens)
    return cfg, params, tokens, (loss, grads), (ref_loss, ref_grads)


def test_bfloat16_program_is_within_its_tolerance_of_the_reference(bf16_case):
    _, _, _, (loss, grads), (ref_loss, ref_grads) = bf16_case
    assert abs(float(loss) - float(ref_loss)) < 5e-3 * float(ref_loss)
    assert np.median(list(leaf_gaps(grads, ref_grads).values())) < BF16_MEDIAN


def test_fp8_control_is_outside_the_bfloat16_tolerance(bf16_case):
    cfg, params, tokens, _, (_, ref_grads) = bf16_case
    _, fp8 = reference_grads(cfg, params, tokens, mode="fp8")
    assert np.median(list(leaf_gaps(fp8, ref_grads).values())) > 2 * BF16_MEDIAN


@pytest.mark.parametrize("fault", ["no_mix", "no_mtp", "no_rope_key"])
def test_each_fault_is_far_outside_the_tolerance(bf16_case, fault):
    cfg, params, tokens, _, (ref_loss, ref_grads) = bf16_case
    loss, grads = reference_grads(cfg, params, tokens, fault=fault)
    gaps = leaf_gaps(grads, ref_grads)
    assert max(gaps.values()) > 10 * BF16_MEDIAN
    if fault == "no_mtp":  # the loss without its second term
        assert float(ref_loss) - float(loss) > 1.0
        assert all(v == 1.0 for k, v in gaps.items() if k.startswith("['mtp']"))
    elif fault == "no_mix":  # no gradient reaches what makes H_res
        res = [v for k, v in gaps.items() if k.endswith(("['p_res']", "['a_res']", "['b_res']"))]
        assert len(res) >= 4 and all(v == 1.0 for v in res)
        assert float(loss) != float(ref_loss)
    else:
        assert np.median(list(gaps.values())) > BF16_MEDIAN


def test_reference_walk_gives_the_whole_models_loss_and_gradient_norms():
    cfg = dict(CFG)
    cell = {"optimizer": {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9, "b2": 0.999,
                          "eps": 1e-8, "weight_decay": 0.01},
            "reference": {"steps": 1, "row_block": 1}}
    tokens = np.asarray(tokens_of(cfg, 8))
    ref = xing_ref.Reference(cfg, cell, 11, jax.devices()[:1])
    start = jax.tree_util.tree_map(jnp.copy, ref.params)
    out = ref.run([tokens], 1)
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        lambda p: xing_ref.loss_and_counters(p, tokens, cfg), has_aux=True))(start)
    assert abs(out["loss"][0] - float(loss)) < 1e-5 * float(loss)
    want = jax.tree_util.tree_map(lambda g: float(jnp.linalg.norm(g)), grads)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(out["grad_norm"])[0],
                            jax.tree_util.tree_leaves(want)):
        assert abs(a - b) <= 2e-4 * b + 1e-9, jax.tree_util.keystr(path)
    for k, v in counters.items():
        assert abs(out["counters"][k] - float(v)) < 1e-4 * abs(float(v)), k


# -- the parts ---------------------------------------------------------------------------


def expert_layer(seed, outputs=64, ff=32, d=64, rows=96):
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    n = lambda k, shape, s=0.2: jax.random.normal(k, shape, jnp.float32) * s
    params = {"router": n(keys[0], (d, outputs)), "router_bias": n(keys[1], (outputs,), 0.02),
              "w1": n(keys[2], (outputs, d, ff)), "w3": n(keys[3], (outputs, d, ff)),
              "w2": n(keys[4], (outputs, ff, d)), "shared_w1": n(keys[5], (d, ff)),
              "shared_w3": n(keys[6], (d, ff)), "shared_w2": n(keys[7], (ff, d))}
    return params, jax.random.normal(keys[8], (1, rows, d), jnp.float32)


def share_of(params, lo, hi):
    return {k: v[lo:hi] if k in ("w1", "w3", "w2") else v for k, v in params.items()}


def test_eight_shares_of_a_layer_add_up_to_the_uncut_layer_of_64():
    """The guide's section 4: experts 0-7, 8-15, ... each on a chip of
    its own, the shared expert on every one and counted once."""
    params, u = expert_layer(0)
    cfg = dict(CFG, router_outputs=64, n_routed_experts=64, num_experts_per_tok=4)
    kw = dict(top_k=4, routed_scale=2.0, norm_eps=1e-20)
    with jax.default_matmul_precision("highest"):
        whole, _, stats = moe.moe_layer(u, params, **kw)
        shared = moe._shared_expert(u[0], params)[None]
        parts = [moe.moe_layer(u, share_of(params, lo, lo + 8), first_expert=lo, **kw)
                 for lo in range(0, 64, 8)]
        ref_whole, _ = xing_ref.expert_ffn(u, params, cfg, make_mm("f32"))
        ref_parts = [xing_ref.expert_ffn(
            u, share_of(params, lo, lo + 8), dict(cfg, n_routed_experts=8, first_expert=lo),
            make_mm("f32"))[0] for lo in range(0, 64, 8)]
    assert float(jnp.max(jnp.abs(shared))) > 1e-3
    np.testing.assert_allclose(sum(p[0] for p in parts) - 7 * shared, whole,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(sum(ref_parts) - 7 * shared, ref_whole,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(whole, ref_whole, atol=2e-5)
    for (y, _, _), r in zip(parts, ref_parts):
        np.testing.assert_allclose(y, r, atol=2e-5)
    # every choice is computed by exactly one share; the shared expert is in no counter
    assert sum(float(p[2]["moe_rows_held"]) for p in parts) == float(
        stats["moe_rows_held"]) == float(stats["moe_rows_routed"]) == 4 * 96


def test_routed_weights_are_the_scores_over_their_sum_times_the_scale():
    logits = jnp.array([[2.0, 1.0, 0.5, 0.0, -1.0, -2.0]])
    experts, weights_ = moe._sigmoid_gating(logits, jnp.zeros((6,)), 2, 2.0, 1e-20)
    s = jax.nn.sigmoid(logits[0, :2])
    assert experts[:, 0].tolist() == [0, 1]
    np.testing.assert_allclose(weights_[:, 0], 2.0 * s / jnp.sum(s), rtol=1e-6)
    _, plain = moe._sigmoid_gating(logits, jnp.zeros((6,)), 2)
    np.testing.assert_allclose(plain[:, 0], s / (jnp.sum(s) + 1e-6), rtol=1e-6)


@pytest.mark.parametrize("iters,summed", [(20, True), (1, False)])
def test_sinkhorn_rows_and_columns_sum_to_one_after_20_iterations_not_after_1(iters, summed):
    # the seeded mappings' range: 2 on the diagonal, a dynamic part of 0.24
    logits = 2.0 * jnp.eye(4) + 0.3 * jax.random.normal(
        jax.random.PRNGKey(0), (3, 50, 4, 4))
    m = hyper.sinkhorn(logits, iters, 1e-6, (-30.0, 30.0))
    off = max(float(jnp.max(jnp.abs(jnp.sum(m, axis=-1) - 1))),
              float(jnp.max(jnp.abs(jnp.sum(m, axis=-2) - 1))))
    assert (off < 1e-4) == summed
    assert float(jnp.min(m)) > 0
    np.testing.assert_allclose(
        m, xing_ref.sinkhorn(logits, dict(CFG, hc_sinkhorn_iters=iters)), rtol=1e-5)


def test_sinkhorn_clamps_before_the_exponential():
    m = hyper.sinkhorn(jnp.full((4, 4), 1e4).at[0, 0].set(-1e4), 20, 1e-6, (-30.0, 30.0))
    assert bool(jnp.all(jnp.isfinite(m)))


def test_one_stream_no_rank_no_shared_expert_no_module_is_the_plain_decoder():
    """With hc_mult 1, no MLA rank, no shared expert and no MTP module
    the program is the one it was: the leaves it had, none of the new
    scopes in its step, and the neutral values written out change no bit."""
    plain = llama.LlamaConfig.tiny(dtype=jnp.float32, n_experts=4, n_dense_layers=1,
                                   moe_router="sigmoid", use_flash=False)
    spelled = dataclasses.replace(
        plain, hc_mult=1, kv_lora_rank=None, q_lora_rank=None, n_shared_experts=0,
        num_nextn_predict_layers=0, routed_scaling_factor=1.0, moe_norm_eps=None)
    params = llama.init(plain, jax.random.PRNGKey(0))
    assert sorted(params) == ["embed", "final_norm", "layers", "lm_head"]
    assert sorted(params["layers"][0]) == [
        "attn_norm", "mlp_norm", "w1", "w2", "w3", "wk", "wo", "wq", "wv"]
    assert sorted(params["layers"][1]["moe"]) == ["router", "router_bias", "w1", "w2", "w3"]
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, plain.vocab_size)
    fn = lambda c: jax.jit(jax.value_and_grad(lambda p: llama.loss_fn(p, tokens, c)))
    text = fn(plain).lower(params).as_text(debug_info=True)
    assert "/mlp/" in text and "/attn/" in text
    for scope in ("hc_map", "hc_mix", "mla_q", "mla_kv", "shared_expert", "mtp"):
        assert f"/{scope}/" not in text, scope
    (a, ga), (b, gb) = fn(plain)(params), fn(spelled)(params)
    assert float(a) == float(b)
    for x, y in zip(jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)):
        np.testing.assert_array_equal(x, y)
    stats = llama.loss_and_stats(params, tokens, plain)[1]
    assert not [k for k in stats if k.startswith(("hc_", "mtp_")) or k == "ce"]


def test_no_logit_before_a_changed_token_moves():
    """A token changed at position j leaves every main logit before j and
    every MTP logit before j - 1 as it was."""
    cfg, j = dict(CFG), 20
    params, tokens = weights(cfg, 3), tokens_of(cfg, 4, batch=1)
    config = program_config(cfg)
    changed = tokens.at[0, j].set((tokens[0, j] + 1) % cfg["vocab_size"])

    def logits(fed):
        rules = llama.ShardingRules()
        h, _, _ = llama._backbone(params, fed[:, :-1], config, None, rules)
        z, _, _ = llama._mtp_hidden(h, params, fed, config, None, rules)
        norm = lambda x, w: llama.rms_norm(x, w, config.rms_eps)
        return (llama._head_logits(norm(h, params["final_norm"]), params, config),
                llama._head_logits(norm(z, params["mtp"]["final_norm"]), params, config))

    (main_a, mtp_a), (main_b, mtp_b) = logits(tokens), logits(changed)
    np.testing.assert_array_equal(main_a[:, :j], main_b[:, :j])
    np.testing.assert_array_equal(mtp_a[:, :j - 1], mtp_b[:, :j - 1])
    assert float(jnp.max(jnp.abs(main_a[:, j] - main_b[:, j]))) > 1e-6
    assert float(jnp.max(jnp.abs(mtp_a[:, j - 1] - mtp_b[:, j - 1]))) > 1e-6


def test_yarn_frequencies_and_the_scores_scale_of_the_published_keys():
    config = llama.LlamaConfig.xing4_0_29b_a4b()
    freqs = llama._rope_freqs(32, config.rope_theta, config.rope_scaling)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    # correction dims: 64 ln(4096 / (32 * 2 pi)) / (2 ln 10000) = 10.47 -> 10,
    # 64 ln(4096 / (2 pi)) / (2 ln 10000) = 22.51 -> 23: dims 0..10 keep
    # their frequency, 23..31 are divided by 64, between them a ramp over 13
    np.testing.assert_allclose(freqs[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freqs[23:], plain[23:] / 64.0, rtol=1e-6)
    hand = {11: 1 / 13, 16: 6 / 13, 22: 12 / 13}
    for i, ramp in hand.items():
        np.testing.assert_allclose(
            freqs[i], plain[i] * (1 - ramp) + plain[i] / 64.0 * ramp, rtol=1e-5)
    np.testing.assert_allclose(freqs[16], 0.01 * (7 / 13 + 6 / 13 / 64), rtol=1e-5)
    np.testing.assert_allclose(freqs, xing_ref.yarn_inv_freq({
        "qk_rope_head_dim": 64, "rope_theta": 10000, "rope_scaling": {
            "beta_fast": 32, "beta_slow": 1, "factor": 64,
            "original_max_position_embeddings": 4096}}), rtol=1e-6)
    assert round(config.softmax_scale, 5) == 0.14468
    assert round(192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2, 5) == 0.14468
    assert llama.LlamaConfig.tiny().softmax_scale is None


def test_remat_on_and_off_agree():
    cfg = dict(CFG)
    params, tokens = weights(cfg, 3), tokens_of(cfg, 4)
    out = [jax.jit(jax.value_and_grad(lambda p: llama.loss_fn(
        p, tokens, program_config(cfg, remat=remat))))(params) for remat in (True, False)]
    assert abs(float(out[0][0]) - float(out[1][0])) < 1e-6
    assert max(leaf_gaps(out[0][1], out[1][1]).values()) < 1e-4


def test_two_devices_under_fsdp_give_the_one_device_loss():
    from kubedl_tpu.parallel.mesh import ShardingRules, build_mesh
    from jax.sharding import NamedSharding

    cfg = dict(CFG)
    # on a mesh the expert layer takes capacity slots: room for every choice
    config = program_config(cfg, expert_capacity_factor=4.0)
    params, tokens = weights(cfg, 3), tokens_of(cfg, 4)
    one = float(jax.jit(lambda p: llama.loss_fn(p, tokens, config))(params))
    mesh, rules = build_mesh({"fsdp": 2}, devices=jax.devices()[:2]), ShardingRules()
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), llama.param_specs(config, rules))
    placed = jax.device_put(params, shardings)
    fed = jax.device_put(tokens, NamedSharding(mesh, rules.spec("batch", None)))
    two = float(jax.jit(lambda p, t: llama.loss_fn(
        p, t, config, mesh=mesh, rules=rules))(placed, fed))
    assert abs(one - two) < 1e-5 * one


@pytest.mark.parametrize("overrides,count", [
    ({}, 30_276_195_174),  # the published model: 30.3B with its MTP module
    ({"num_nextn_predict_layers": 0}, 29_505_505_264),
    ({"n_layers": 5, "n_dense_layers": 1, "n_experts_held": 8, "vocab_size": 16384},
     913_473_668),  # the benchmark's cut (benchmarks/configs/xing4.0-29b-a4b-d5e8.json)
])
def test_published_sizes_count_their_parameters(overrides, count):
    config = llama.LlamaConfig.xing4_0_29b_a4b(**overrides)
    shapes = jax.eval_shape(lambda k: llama.init(config, k), jax.random.PRNGKey(0))
    assert llama.param_count(shapes) == count
    specs = llama.param_specs(config)
    assert jax.tree_util.tree_structure(shapes) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda s: 0, specs,
                               is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)))


def test_the_cuts_parts_are_the_tables():
    config = llama.LlamaConfig.xing4_0_29b_a4b(
        n_layers=5, n_dense_layers=1, n_experts_held=8, vocab_size=16384)
    shapes = jax.eval_shape(lambda k: llama.init(config, k), jax.random.PRNGKey(0))
    count = lambda t: llama.param_count(t)
    dense, expert = shapes["layers"][0], shapes["layers"][1]
    mla = [k for k in dense if k.startswith(("wq", "wkv", "wo", "q_a", "kv_a"))]
    assert count({k: dense[k] for k in mla}) == 28_411_136
    assert count(dense["hc_mixer"]) == count(expert["hc_mlp"]) == 344_091
    assert count(dense) == 128_196_918 and count(expert) == 128_426_358
    assert count(expert["moe"]["shared_w1"]) * 3 == 11_010_048
    assert count(shapes["mtp"]) == 25_690_112 + 3 * 3584 + 128_426_358
    assert count(shapes["embed"]) == count(shapes["lm_head"]) == 58_720_256


def test_benchmark_weights_have_the_programs_shapes():
    cfg = dict(CFG)
    config = latent_config(cfg, SEQ)
    ours = jax.eval_shape(lambda k: llama.init(config, k), jax.random.PRNGKey(0))
    theirs = jax.eval_shape(weights_xing.make_fn(dict(cfg, torch_dtype="bfloat16")),
                            jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(ours) == jax.tree_util.tree_structure(theirs)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(ours)[0],
                            jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape, jax.tree_util.keystr(path)


def test_counters_count_mappings_mixing_and_the_modules_loss():
    cfg = dict(CFG)
    params, tokens = weights(cfg, 3), tokens_of(cfg, 4)
    loss, stats = jax.jit(lambda p: llama.loss_and_stats(
        p, tokens, program_config(cfg)))(params)
    assert float(stats["hc_mappings"]) == 2 * (3 + 1)
    assert 0 < float(stats["hc_res_offdiag"]) < 0.75
    assert 0 <= float(stats["hc_sinkhorn_residual"]) < 1e-4
    assert 0.4 < float(stats["hc_pre_mean"]) < 0.6 and 0.8 < float(stats["hc_post_mean"]) < 1.2
    assert float(stats["mtp_ce"]) > 0 and float(stats["mtp_positions"]) == 2 * (SEQ - 1)
    np.testing.assert_allclose(
        float(loss), float(stats["ce"]) + 0.3 * float(stats["mtp_ce"]), rtol=1e-6)
    # three expert layers (the module's among them), 2 choices a token, all held
    assert float(stats["moe_rows_held"]) == 3 * 2 * 2 * SEQ


# a record from before the hyper-connections' kernels has no hc_kernel_mappings
@pytest.mark.parametrize("kernel_mappings,shown", [(None, ""), (12.0, " kernel_mappings=12"),
                                                   (0.0, " kernel_mappings=0")])
def test_span_detail_prints_the_new_counters(kernel_mappings, shown):
    from kubedl_tpu.cli import _span_detail

    attrs = {"step": 2, "hc_mappings": 12.0, "hc_res_offdiag": 0.2912,
             "hc_sinkhorn_residual": 3e-7, "hc_pre_mean": 0.5,
             "hc_post_mean": 1.0, "ce": 9.7, "mtp_ce": 9.71, "mtp_positions": 16382.0}
    if kernel_mappings is not None:
        attrs["hc_kernel_mappings"] = kernel_mappings
    assert _span_detail(attrs) == (
        "step=2 hc_mappings=12 offdiag=0.291 sinkhorn_residual=3.0e-07 "
        f"pre=0.500 post=1.000{shown} ce=9.7000 mtp_ce=9.7100 mtp_positions=16382")


# -- what cannot run such a model yet ------------------------------------------------------


PUBLISHED = llama.LlamaConfig.xing4_0_29b_a4b


def tiny_preset(**kw):
    sizes = dict(
        vocab_size=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4, d_ff=128,
        max_seq_len=64, kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_experts=8, expert_top_k=2,
        n_dense_layers=1, d_ff_expert=32)
    return PUBLISHED(**{**sizes, **kw})


def _init_kv_cache(config):
    from kubedl_tpu.models import decode
    return decode.init_kv_cache(config, 1, 16)


def _serving_engine(config):
    from kubedl_tpu.models.serving import ServingEngine
    return ServingEngine({}, config, slots=1, max_len=16)


def _pipelined(config):
    mesh = types.SimpleNamespace(shape={"stage": 2})
    return llama.forward_pipelined_and_aux({}, jnp.zeros((2, 8), jnp.int32), config, mesh)


def _importer(config):
    from kubedl_tpu.models.import_hf import config_from_hf
    return config_from_hf(types.SimpleNamespace(
        model_type="xing4_0", kv_lora_rank=config.kv_lora_rank, hc_mult=config.hc_mult))


def _context_mesh(config):
    from kubedl_tpu.parallel.mesh import build_mesh
    mesh = build_mesh({"context": 2}, devices=jax.devices()[:2])
    params = llama.init(config, jax.random.PRNGKey(0))
    return llama.loss_fn(params, jnp.zeros((2, 17), jnp.int32), config, mesh=mesh)


@pytest.mark.parametrize("path,config,error,says", [
    (_init_kv_cache, lambda: tiny_preset(hc_mult=1, num_nextn_predict_layers=0),
     NotImplementedError, "latent-attention"),
    (_init_kv_cache, lambda: tiny_preset(kv_lora_rank=None), NotImplementedError,
     "several-stream"),
    (_serving_engine, lambda: tiny_preset(hc_mult=1), NotImplementedError,
     "ServingEngine.*latent-attention"),
    (_serving_engine, lambda: tiny_preset(kv_lora_rank=None), NotImplementedError,
     "ServingEngine.*several-stream"),
    (_pipelined, lambda: tiny_preset(n_dense_layers=0), NotImplementedError,
     "pipelined forward.*several-stream"),
    (_pipelined, lambda: tiny_preset(hc_mult=1, n_dense_layers=0), NotImplementedError,
     "latent-attention"),
    (_importer, tiny_preset, ValueError, "latent-attention.*several-stream"),
    (_context_mesh, tiny_preset, NotImplementedError, "context: 2.*latent-attention"),
], ids=["kv_cache_latent", "kv_cache_streams", "engine_latent", "engine_streams",
        "pipelined_streams", "pipelined_latent", "hf_importer", "context_mesh"])
def test_paths_that_cannot_take_these_layers_refuse_them_by_name(path, config, error, says):
    with pytest.raises(error, match=says):
        path(config())


def test_config_refuses_what_it_cannot_combine():
    with pytest.raises(ValueError, match="hc_mult"):
        llama.LlamaConfig.tiny(hc_mult=0)
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        llama.LlamaConfig.tiny(num_nextn_predict_layers=2)
    with pytest.raises(ValueError, match="looped"):
        llama.LlamaConfig.tiny(total_ut_steps=2, hc_mult=4)


# -- through the trainer -------------------------------------------------------------------


def test_trainer_main_trains_the_preset_and_records_its_counters(tmp_path, monkeypatch):
    from kubedl_tpu.obs import load_spans
    from kubedl_tpu.train import trainer

    monkeypatch.setattr(llama.LlamaConfig, "xing4_0_29b_a4b", staticmethod(
        lambda **kw: tiny_preset(**kw)))
    trace_dir = str(tmp_path / "trace")
    monkeypatch.setenv("KUBEDL_MESH", "data=-1")
    monkeypatch.setenv("KUBEDL_TRACE_DIR", trace_dir)
    monkeypatch.setenv("KUBEDL_TRACE_ID", "0" * 32)
    monkeypatch.setenv("POD_NAME", "latent-worker-0")
    assert trainer.main(["--model", "xing4.0-29b-a4b", "--batch", "8", "--seq-len",
                         "21", "--steps", "2", "--log-every", "1"]) == 0
    steps = [s for s in load_spans(trace_dir)
             if s["name"] in ("train.compile", "train.step")]
    assert len(steps) == 2
    for s in steps:
        a = s["attrs"]
        assert a["hc_mappings"] == 8 and 0 < a["hc_res_offdiag"] < 0.75
        assert a["mtp_ce"] > 0 and a["ce"] > 0 and a["mtp_positions"] == 8 * 19
    from kubedl_tpu.cli import _span_detail

    assert " hc_mappings=8 offdiag=0." in _span_detail(steps[-1]["attrs"])
