"""The hyper-connections' passes as Pallas kernels
(`kubedl_tpu/ops/hyper_mix.py`) in interpret mode on the CPU, at small
shapes of whole tiles: one mapping's u, logits, new streams, counters and
every gradient against the float32 equations; two mappings' gradients
against the XLA form's autodiff; which form `hc_branch` takes, by shape,
backend, dtype and mesh; `hc_kernel_mappings` through a whole step; a
one-stream model untouched; remat; two devices. What Mosaic refuses is
`tests/test_tpu_compile.py`'s to see.

On the CPU `hc_branch` takes the XLA form whatever the shape
(`mix_takes_kernel` asks `ops.interpret`): the `kernel_form` fixture
steers that one question in the test, and the kernels themselves still
run interpreted."""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights_xing
from benchmarks.runners.train_latent import latent_config
from kubedl_tpu.models import hyper, llama
from kubedl_tpu.ops import hyper_mix

KERNELS = ("hc_pre_fwd", "hc_post_fwd", "hc_post_bwd", "hc_pre_bwd")
N = 4
ARGS = (20, 1e-6, (-30.0, 30.0))  # Sinkhorn's iterations, eps, clamp
BF16_ULP = 2.0 ** -8  # the largest relative rounding to bfloat16


@pytest.fixture
def kernel_form(monkeypatch):
    """`hc_branch` chooses as it would on a TPU."""
    monkeypatch.setattr(hyper, "interpret", lambda: False)


def leaves(d, seed):
    """A mapping's leaves, the dynamic part large enough that every token's
    mappings differ and every leaf's gradient is well above round-off."""
    hc = hyper.hc_init(jax.random.PRNGKey(seed), d, N)
    return {k: v * (5.0 if k.startswith("p_") else 10.0 if k.startswith("a_") else 1.0)
            for k, v in hc.items()}


def normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def one_mapping(x, y, hc):
    """u and the streams after the sublayer's output y, and the counters."""
    u, onto = hyper.hc_branch(x, hc, N, *ARGS)
    return u, hyper.hc_merge(onto, y), onto.mapping["stats"]


def gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def close(got, want, rtol):
    """Within `rtol` of the float32 value, near zero within a
    ten-thousandth of the largest."""
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=rtol,
                               atol=1e-4 * np.abs(want).max())


# stream width, tokens a sequence, tokens a program: one program a
# sequence and several, two widths of a pass
SHAPES = [
    pytest.param(256, 64, 32, id="d256_two_programs"),
    pytest.param(256, 128, 128, id="d256_one_program"),
    pytest.param(384, 96, 32, id="d384_three_programs"),
    pytest.param(384, 64, 64, id="d384_one_program"),
]


@pytest.mark.parametrize("d,seq,block", SHAPES)
def test_one_mapping_is_the_float32_equations(kernel_form, monkeypatch, d, seq, block):
    """The kernels' u, new streams, dX and dy are the float32 form's
    rounded once to bfloat16 (dX's three shares are summed in float32 by
    one kernel); the logits, the counters and the leaves' gradients are
    the float32 form's to float32 rounding."""
    monkeypatch.setattr(hyper_mix, "TOKEN_BLOCK", block)
    ks = jax.random.split(jax.random.PRNGKey(d + seq), 4)
    x, y = normal(ks[0], (2, seq, N * d)), normal(ks[1], (2, seq, d))
    cts = (normal(ks[2], (2, seq, d)), normal(ks[3], (2, seq, N * d)))
    hc = leaves(d, seq)
    assert hyper.mix_takes_kernel(seq, N, d, jnp.bfloat16)
    fn = lambda *a: one_mapping(*a)[:2]
    text = str(jax.make_jaxpr(lambda *a: jax.vjp(fn, *a)[1](cts))(x, y, hc))
    assert all(f"name={k}" in text for k in KERNELS)

    (u, xp), vjp = jax.vjp(fn, x, y, hc)
    # float32 streams take the XLA form (the split needs bfloat16 streams)
    (u_e, xp_e), vjp_e = jax.vjp(fn, *f32((x, y)), hc)
    stats_e = one_mapping(*f32((x, y)), hc)[2]
    close(u, u_e, BF16_ULP)
    close(xp, xp_e, BF16_ULP)
    dx, dy, dhc = vjp(cts)
    dx_e, dy_e, dhc_e = vjp_e(f32(cts))
    close(dx, dx_e, BF16_ULP)
    close(dy, dy_e, BF16_ULP)
    for name in hc:
        assert gap(dhc[name], dhc_e[name]) < 1e-5, (name, gap(dhc[name], dhc_e[name]))
    stats = one_mapping(x, y, hc)[2]
    assert float(stats["hc_kernel_mappings"]) == 1 and float(stats_e["hc_kernel_mappings"]) == 0
    for name in ("hc_res_offdiag", "hc_sinkhorn_residual", "hc_pre_mean", "hc_post_mean"):
        assert float(stats[name]) == pytest.approx(float(stats_e[name]), rel=1e-4, abs=1e-7)
    # the logits and inv, as the kernel hands them to XLA
    w = jnp.concatenate([hc["p_pre"], hc["p_post"], hc["p_res"]], axis=1)
    maps_of = functools.partial(hyper._post_res, n=N, iters=ARGS[0], eps=ARGS[1],
                                clamp=ARGS[2])
    lp = hyper_mix.pre(x, w, hc["a_pre"], hc["b_pre"], hc, maps_of, ARGS[1])[1]
    flat = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + ARGS[1])
    m = N * (N + 2)
    close(lp[..., :m], jnp.dot(flat, w, precision="highest") * inv, 1e-5)
    close(lp[..., m:m + 1], inv, 1e-6)


def two_mappings(x, w, hc_a, hc_b, r):
    """A layer's two sublayers under hyper-connections, a matmul each,
    summed over the streams against r."""
    for hc in (hc_a, hc_b):
        u, onto = hyper.hc_branch(x, hc, N, *ARGS)
        y = jnp.dot(u, w.astype(u.dtype), preferred_element_type=jnp.float32)
        x = hyper.hc_merge(onto, jnp.tanh(y).astype(u.dtype))
    return jnp.sum(hyper.hc_sum(x, N).astype(jnp.float32) * r)


@pytest.mark.parametrize("d,seq", [(256, 64), (384, 96)])
def test_two_mappings_gradients_are_nearer_the_float32_gradient_than_autodiffs(
        kernel_form, monkeypatch, d, seq):
    """Through jax.grad of a layer with two mappings, the gradients of the
    streams, of the sublayers' weight (and so y's) and of each mapping's
    P_* lie within the bfloat16 XLA form's own distance of the float32
    gradient. a_* and b_* are sums over tokens that cancel, whose
    bfloat16 noise is of their own size in either form and falls either
    way: they are held under a half (a wrong rule in any kernel reads 1
    or more; `test_one_mapping_is_the_float32_equations` holds them to
    1e-5 given the same cotangents)."""
    monkeypatch.setattr(hyper_mix, "TOKEN_BLOCK", 32)
    ks = jax.random.split(jax.random.PRNGKey(d), 3)
    x = normal(ks[0], (2, seq, N * d))
    w = jax.random.normal(ks[1], (d, d), jnp.float32) / np.sqrt(d)
    r = jax.random.normal(ks[2], (2, seq, d), jnp.float32)
    args = (x, w, leaves(d, 1), leaves(d, 2), r)
    grad = jax.jit(jax.grad(two_mappings, argnums=(0, 1, 2, 3)))
    got = grad(*args)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(hyper, "interpret", lambda: True)
        xla = jax.jit(jax.grad(two_mappings, argnums=(0, 1, 2, 3)))(*args)
        exact = jax.jit(jax.grad(two_mappings, argnums=(0, 1, 2, 3)))(
            x.astype(jnp.float32), *args[1:])
    flat = lambda g: dict(jax.tree_util.tree_flatten_with_path(g)[0])
    for path, e in flat(exact).items():
        name = jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(e)) > 0, name
        ours, theirs = gap(flat(got)[path], e), gap(flat(xla)[path], e)
        if "'a_" in name or "'b_" in name:
            assert ours < 0.5, (name, ours, theirs)
        else:
            assert ours <= 1.1 * theirs + 1e-3, (name, ours, theirs)


@pytest.mark.parametrize("seq,d,dtype,mesh_axes,takes", [
    (8192, 3584, jnp.bfloat16, None, True),  # the Xing4.0 cell's
    (64, 256, jnp.bfloat16, {"fsdp": 4}, True),  # over batch: a shard_map
    (64, 256, jnp.bfloat16, {"fsdp": 2, "tensor": 2}, False),
    (72, 256, jnp.bfloat16, None, False),  # no whole token tile
    (64, 200, jnp.bfloat16, None, False),  # no whole 128-lane stream
    (64, 256, jnp.float32, None, False),  # streams the split cannot take exactly
])
def test_the_form_is_chosen_from_shapes_backend_dtype_and_mesh(
        monkeypatch, seq, d, dtype, mesh_axes, takes):
    mesh = None if mesh_axes is None else type(
        "Mesh", (), {"shape": mesh_axes, "size": 4})()
    assert not hyper.mix_takes_kernel(seq, N, d, dtype, mesh)  # the CPU
    monkeypatch.setattr(hyper, "interpret", lambda: False)
    assert hyper.mix_takes_kernel(seq, N, d, dtype, mesh) == takes


# a several-stream model at stream width 128 in bfloat16: one dense and
# one expert block, the module's block, 6 mappings
CFG = {
    "hidden_size": 128, "intermediate_size": 256, "moe_intermediate_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4, "num_hidden_layers": 2,
    "first_k_dense_replace": 1, "n_routed_experts": 8, "router_outputs": 8,
    "first_expert": 0, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1, "hidden_act": "silu",
    "attention_bias": False, "moe_layer_freq": 1, "tie_word_embeddings": False,
    "kv_lora_rank": 32, "q_lora_rank": 48, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "v_head_dim": 32, "hc_mult": N, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rope_theta": 10000, "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096, "type": "yarn"},
    "num_nextn_predict_layers": 1, "mtp_loss_weight": 0.3, "router_norm_eps": 1e-20,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "initializer_range": 0.2,
    "torch_dtype": "bfloat16", "remat": "full",
}
SEQ = 64


def model(**kw):
    config = dataclasses.replace(latent_config(CFG, SEQ), use_flash=False, **kw)
    params = weights_xing.make_fn(CFG)(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ + 1), 0, CFG["vocab_size"])
    return config, params, tokens


def steady_gaps(got, want, exact):
    """Each leaf's distance from the float32 gradient `exact`, `got`'s and
    `want`'s, over the leaves whose float32 gradient is at least a
    hundredth of the median leaf's: the first mapping of the stack and of
    the module read streams that are still equal, and their pre leaves'
    gradients are zero but for round-off, which bfloat16 makes hundreds
    of times their float32 size in either form."""
    flat = lambda g: dict(jax.tree_util.tree_flatten_with_path(g)[0])
    got, want, exact = flat(got), flat(want), flat(exact)
    norms = {p: float(jnp.linalg.norm(e)) for p, e in exact.items()}
    floor = 1e-2 * float(np.median(list(norms.values())))
    kept = [p for p in exact if norms[p] > floor]
    return ([gap(got[p], exact[p]) for p in kept], [gap(want[p], exact[p]) for p in kept],
            [jax.tree_util.keystr(p) for p in kept])


def test_a_step_counts_its_kernel_mappings_and_is_as_near_the_float32_step_as_xlas(
        monkeypatch):
    """`hc_kernel_mappings`: 0 where the XLA form ran (the CPU), all of
    `hc_mappings` where the kernels did; the loss and the other counters
    as the XLA form's, and the gradient as near the float32 model's, with
    remat on and off."""
    monkeypatch.setattr(hyper_mix, "TOKEN_BLOCK", 32)
    config, params, tokens = model()
    step = lambda c, p=params: jax.jit(jax.value_and_grad(
        lambda p: llama.loss_and_stats(p, tokens, c), has_aux=True))(p)
    (loss_x, stats_x), g_x = step(config)
    assert float(stats_x["hc_kernel_mappings"]) == 0 and float(stats_x["hc_mappings"]) == 6
    _, g_e = step(dataclasses.replace(config, dtype=jnp.float32), f32(params))
    monkeypatch.setattr(hyper, "interpret", lambda: False)
    text = str(jax.make_jaxpr(jax.grad(lambda p: llama.loss_fn(p, tokens, config)))(params))
    assert all(f"name={k}" in text for k in KERNELS)
    results = [step(config), step(dataclasses.replace(config, remat=False))]
    for (loss, stats), grads in results:
        assert float(stats["hc_kernel_mappings"]) == float(stats["hc_mappings"]) == 6
        # both forms round the streams to bfloat16, not always alike
        assert float(loss) == pytest.approx(float(loss_x), rel=1e-3)
        for name in ("hc_res_offdiag", "hc_pre_mean", "hc_post_mean", "mtp_ce", "ce"):
            assert float(stats[name]) == pytest.approx(float(stats_x[name]), rel=1e-3), name
        ours, theirs, names = steady_gaps(grads, g_x, g_e)
        assert np.median(ours) <= 1.05 * np.median(theirs), (np.median(ours), np.median(theirs))
        # leaf by leaf: the mappings' scalars and biases are sums over every
        # token of bfloat16 cotangents that cancel, whose distance reads up
        # to 0.2 in either form and 2.3 in both on the last FFN mapping's
        # a_post; a wrong rule reads 1 and more
        worse = [(k, o, t) for k, o, t in zip(names, ours, theirs) if o > max(3 * t, 0.25)]
        assert not worse, worse
    (on, _), _ = results[0]
    (off, _), _ = results[1]
    assert float(on) == pytest.approx(float(off), rel=1e-5)


def test_a_one_stream_models_program_is_the_same_whatever_the_choice(monkeypatch):
    """hc_mult 1: no mapping, no kernel, and the jaxpr of the step the
    same whether or not the kernels would be taken."""
    config = llama.LlamaConfig.tiny(d_model=256, dtype=jnp.bfloat16)
    assert config.hc_mult == 1
    params = llama.init(config, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 129), jnp.int32)
    jaxpr = lambda: re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(jax.value_and_grad(
        lambda p: llama.loss_fn(p, tokens, config)))(params)))
    plain = jaxpr()
    monkeypatch.setattr(hyper, "interpret", lambda: False)
    monkeypatch.setattr(hyper, "mix_takes_kernel", lambda *a, **kw: True)
    assert jaxpr() == plain
    assert not any(k in plain for k in KERNELS) and "hc_" not in plain


def test_two_devices_under_fsdp_ride_a_shard_map_and_give_the_xla_forms_loss(
        kernel_form, monkeypatch):
    from kubedl_tpu.parallel.mesh import ShardingRules, build_mesh

    monkeypatch.setattr(hyper_mix, "TOKEN_BLOCK", 32)
    config, params, tokens = model()
    mesh, rules = build_mesh({"fsdp": 2}, devices=jax.devices()[:2]), ShardingRules()
    fn = jax.value_and_grad(lambda p: llama.loss_fn(p, tokens, config, mesh=mesh, rules=rules))
    text = str(jax.make_jaxpr(fn)(params))
    assert "shard_map" in text and all(f"name={k}" in text for k in KERNELS)
    two = jax.jit(fn)(params)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(hyper, "interpret", lambda: True)
        xla = jax.jit(fn)(params)
    assert float(two[0]) == pytest.approx(float(xla[0]), rel=1e-4)
    # the mappings' leaves take the sum of both devices' sequences: one
    # device's share alone would read a half
    gaps = [gap(a, b) for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(two[1])[0], jax.tree_util.tree_leaves(xla[1]))
        if "hc_" in jax.tree_util.keystr(path)]
    assert np.median(gaps) < 0.05, np.median(gaps)
