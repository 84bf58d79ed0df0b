"""A model of unlike layers (gated short convolutions among attention
layers, a dense FFN before routed ones, a sigmoid router with a selection
bias, an expert layer that holds part of its experts) against the plain
reference `benchmarks/reference/lfm2_ref.py`, on seeded weights in
float32 at a small size, and the refusals of the paths that cannot run
such a model yet."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights_hybrid
from benchmarks.reference import lfm2_ref
from benchmarks.runners.train_hybrid import hybrid_config
from kubedl_tpu.models import llama, moe
from kubedl_tpu.models.short_conv import causal_taps, short_conv
from kubedl_tpu.ops import gmm as G

SEQ = 48

# hidden 64, heads of 16, layer 0 and two periods, 8 router outputs
CFG = {
    "hidden_size": 64, "intermediate_size": 256, "moe_intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_hidden_layers": 9,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv"],
    "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 4,
    "router_outputs": 8, "first_expert": 0, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "use_expert_bias": True, "conv_L_cache": 3,
    "conv_bias": False, "vocab_size": 128, "norm_eps": 1e-5,
    "rope_theta": 1000000, "initializer_range": 0.02,
    "tie_word_embeddings": True, "torch_dtype": "float32", "remat": "full",
}

CASES = {
    "all_experts_held": {},
    "two_of_eight_held": {"num_experts": 2, "first_expert": 4},
    "attention_layers_only": {"layer_types": ["full_attention"] * 9},
    "convolution_layers_only": {"layer_types": ["conv"] * 9},
}


def float32_weights(cfg, seed):
    # a larger spread than the benchmark's 0.02, so that at hidden 64 the
    # router's scores differ and every leaf's gradient is well above zero
    cfg = dict(cfg, initializer_range=0.2)
    tree = weights_hybrid.make_fn(cfg)(jax.random.PRNGKey(seed))
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def tokens_of(cfg, seed, batch=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ + 1), 0,
                              cfg["vocab_size"])


@pytest.mark.parametrize("case", sorted(CASES))
def test_loss_and_every_gradient_leaf_match_the_reference(case):
    cfg = dict(CFG, **CASES[case])
    params, tokens = float32_weights(cfg, 3), tokens_of(cfg, 4)
    config = dataclasses.replace(hybrid_config(cfg, SEQ), use_flash=False)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: llama.loss_fn(p, tokens, config)))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: lfm2_ref.loss(p, tokens, cfg)))(params)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    got = dict(jax.tree_util.tree_flatten_with_path(grads)[0])
    want = dict(jax.tree_util.tree_flatten_with_path(ref_grads)[0])
    assert got.keys() == want.keys()
    for path, w in want.items():
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):
            assert not np.any(np.asarray(got[path])) and not np.any(np.asarray(w))
            continue
        assert float(jnp.linalg.norm(w)) > 0, name
        gap = float(jnp.linalg.norm(got[path] - w) / jnp.linalg.norm(w))
        assert gap < 2e-4, (name, gap)


def test_the_walk_gives_the_whole_models_gradient_norms():
    """`Reference.run` (block by block, layer by layer) against jax.grad
    of the reference's own loss in one piece."""
    cfg = dict(CFG, num_experts=2, torch_dtype="bfloat16")
    cell = {"optimizer": {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.999,
                          "eps": 1e-8, "weight_decay": 0.01},
            "reference": {"row_block": 1, "steps": 1}, "chips": 1}
    tokens = np.asarray(tokens_of(cfg, 9))
    ref = lfm2_ref.Reference(cfg, cell, 11, jax.devices()[:1])
    start = ref.params
    want = jax.grad(lambda p: lfm2_ref.loss(p, tokens, cfg))(start)
    out = ref.run([tokens], 1)
    got = dict(jax.tree_util.tree_flatten_with_path(out["grad_norm"])[0])
    for path, w in jax.tree_util.tree_flatten_with_path(want)[0]:
        norm = float(jnp.linalg.norm(w))
        assert abs(got[path] - norm) <= 1e-4 * norm + 1e-12, jax.tree_util.keystr(path)
    assert 0.0 <= out["route_flip_share"] < 0.05


# -- one expert layer ---------------------------------------------------------


def expert_layer(seed, d=64, ff=128, n_out=8, tokens=96):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    params = {
        "router": jax.random.normal(ks[0], (d, n_out)) * 0.3,
        "router_bias": jax.random.normal(ks[1], (n_out,)) * 0.05,
        "w1": jax.random.normal(ks[2], (n_out, d, ff)) * 0.1,
        "w3": jax.random.normal(ks[3], (n_out, d, ff)) * 0.1,
        "w2": jax.random.normal(ks[4], (n_out, ff, d)) * 0.1,
    }
    u = jax.random.normal(ks[5], (2, tokens // 2, d))
    return params, u


def share_of(params, lo, hi):
    return dict(params, **{k: params[k][lo:hi] for k in ("w1", "w3", "w2")})


def test_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    params, u = expert_layer(0)
    cfg = dict(CFG, num_experts=8, first_expert=0)
    mm = lfm2_ref.make_mm("f32")
    with jax.default_matmul_precision("highest"):
        whole, _, stats = moe.moe_layer(u, params, top_k=4)
        parts = [moe.moe_layer(u, share_of(params, lo, lo + 2), top_k=4,
                               first_expert=lo) for lo in (0, 2, 4, 6)]
    ref_whole, _ = lfm2_ref.expert_ffn(u, params, cfg, mm)
    ref_parts = [lfm2_ref.expert_ffn(
        u, share_of(params, lo, lo + 2), dict(cfg, num_experts=2, first_expert=lo),
        mm)[0] for lo in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(p[0] for p in parts), whole, atol=2e-6)
    np.testing.assert_allclose(sum(ref_parts), ref_whole, atol=2e-6)
    np.testing.assert_allclose(whole, ref_whole, atol=2e-5)
    for (y, _, _), r in zip(parts, ref_parts):
        np.testing.assert_allclose(y, r, atol=2e-5)
    # every choice is computed by exactly one share, none dropped
    assert sum(float(p[2]["moe_rows_held"]) for p in parts) == float(
        stats["moe_rows_held"]) == float(stats["moe_rows_routed"]) == 4 * 96
    assert all(float(p[1]) == 0.0 for p in parts)  # no auxiliary loss


def test_bias_moves_the_selection_and_never_the_weight():
    logits = jnp.array([[2.0, 1.0, 0.5, 0.0, -1.0, -2.0]])
    plain_e, plain_w = moe._sigmoid_gating(logits, jnp.zeros(6), 2)
    assert sorted(np.asarray(plain_e[:, 0])) == [0, 1]
    bias = jnp.zeros(6).at[5].set(5.0)
    experts, weights = moe._sigmoid_gating(logits, bias, 2)
    assert sorted(np.asarray(experts[:, 0])) == [0, 5]
    s = jax.nn.sigmoid(logits[0])
    want = {0: s[0] / (s[0] + s[5] + 1e-6), 5: s[5] / (s[0] + s[5] + 1e-6)}
    for e, w in zip(np.asarray(experts[:, 0]), np.asarray(weights[:, 0])):
        np.testing.assert_allclose(w, want[int(e)], rtol=1e-6)
    # and the bias takes no gradient
    g = jax.grad(lambda b: moe._sigmoid_gating(logits, b, 2)[1].sum())(bias)
    assert not np.any(np.asarray(g))


def test_normaliser_runs_over_all_choices_held_or_not():
    params, u = expert_layer(1)
    with jax.default_matmul_precision("highest"):
        y, _, _ = moe.moe_layer(u, share_of(params, 0, 2), top_k=4)
    hf = u.reshape(-1, u.shape[-1])
    s = jax.nn.sigmoid(hf @ params["router"])
    _, sel = jax.lax.top_k(s + params["router_bias"], 4)
    chosen = jnp.take_along_axis(s, sel, axis=-1)
    denom = chosen.sum(-1) + 1e-6  # all four, wherever their experts live
    want = jnp.zeros_like(hf)
    for e in (0, 1):
        h = jax.nn.silu(hf @ params["w1"][e]) * (hf @ params["w3"][e])
        w = jnp.where((sel == e).any(-1), s[:, e] / denom, 0.0)
        want = want + w[:, None] * (h @ params["w2"][e])
    np.testing.assert_allclose(y.reshape(want.shape), want, atol=2e-5)


def test_tie_free_seed_gives_the_references_choices():
    params, u = expert_layer(2)
    hf = u.reshape(-1, u.shape[-1])
    logits = moe._router_logits(hf, params["router"])
    experts, _ = moe._sigmoid_gating(logits, params["router_bias"], 4)
    chosen, s = lfm2_ref.route(hf, params["router"], params["router_bias"], 4)
    ranked = jnp.sort(s + params["router_bias"], axis=-1)
    assert float(jnp.min(ranked[:, 4] - ranked[:, 3])) > 1e-5  # no near tie
    plane = np.zeros(chosen.shape)
    for k in range(4):
        plane[np.arange(hf.shape[0]), np.asarray(experts[k])] += 1
    np.testing.assert_array_equal(plane, np.asarray(chosen))


def test_held_experts_under_an_expert_mesh_refuse():
    params, u = expert_layer(3)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("expert",))
    with pytest.raises(NotImplementedError, match="all-to-all over the `expert` mesh axis"):
        moe.moe_layer(u, share_of(params, 0, 4), top_k=4, mesh=mesh)


# -- grouped matmuls over a layout that is mostly sentinel ---------------------


def sentinel_dispatch(seed, e=2, d=128, ff=256, tokens=512):
    """k*S entries (entry f is token f % S) of which three quarters name
    an absent expert."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    src = jax.random.normal(ks[0], (tokens, d))
    eid = jax.random.randint(ks[1], (4 * tokens,), 0, 4 * e)
    eid = jnp.where(eid < e, eid, e)
    params = {"w1": jax.random.normal(ks[2], (e, d, ff)) * 0.1,
              "w3": jax.random.normal(ks[3], (e, d, ff)) * 0.1,
              "w2": jax.random.normal(ks[4], (e, ff, d)) * 0.1}
    return src, eid, params, e


def test_sentinel_entries_cost_no_tile_and_change_no_live_row():
    """Against the same live entries laid out with no sentinel among them
    (every token once, so that entry f is still token f % S): the same
    rows through the same experts give the same bits."""
    src, eid, params, e = sentinel_dispatch(5)
    live = np.asarray(eid) < e
    assert 0.2 < live.mean() < 0.3
    rows_of = np.tile(np.arange(src.shape[0]), 4)

    def run(src, params, eid):
        return moe._gmm_ffn(src, eid, params, e)

    y = run(src, params, eid)
    assert not np.any(np.asarray(y)[~live])
    # the live entries alone, as the one choice of their own rows
    picked = src[rows_of[live]]
    y_live = run(picked, params, eid[live])
    np.testing.assert_array_equal(np.asarray(y)[live], np.asarray(y_live))

    loss = lambda src, params, eid: jnp.sum(run(src, params, eid) ** 2)
    g_src, g_w = jax.grad(loss, argnums=(0, 1))(src, params, eid)
    g_picked, g_w_live = jax.grad(loss, argnums=(0, 1))(picked, params, eid[live])
    for a, b in zip(jax.tree_util.tree_leaves(g_w), jax.tree_util.tree_leaves(g_w_live)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    want = np.zeros(src.shape, np.float32)
    np.add.at(want, rows_of[live], np.asarray(g_picked))
    np.testing.assert_allclose(np.asarray(g_src), want, rtol=1e-5, atol=1e-6)
    untouched = np.setdiff1d(np.arange(src.shape[0]), rows_of[live])
    assert untouched.size and not np.any(np.asarray(g_src)[untouched])
    _, _, _, tile_expert, _ = moe._dispatch_plan(eid, e)
    n_live = int(jnp.sum(tile_expert < e))
    assert n_live < 0.4 * tile_expert.shape[0]
    assert np.all(np.asarray(tile_expert)[n_live:] == e)  # dead tiles come last


def test_a_layer_that_is_sent_no_row_computes_zeros():
    """Every entry a sentinel (a router that has drifted off the experts
    held here): one tile is launched, with a valid expert id."""
    src, _, params, e = sentinel_dispatch(6, tokens=128)
    eid = jnp.full((4 * src.shape[0],), e, jnp.int32)
    _, _, _, tile_expert, _ = moe._dispatch_plan(eid, e)
    assert np.all(np.asarray(tile_expert) == e)
    assert int(G._live_tiles(tile_expert, e)) == 1
    assert int(jnp.max(G._visited_ids(tile_expert, e))) == e - 1
    loss = lambda src, params: jnp.sum(moe._gmm_ffn(src, eid, params, e) ** 2)
    assert not np.any(np.asarray(moe._gmm_ffn(src, eid, params, e)))
    for g in jax.tree_util.tree_leaves(jax.grad(loss, argnums=(0, 1))(src, params)):
        assert not np.any(np.asarray(g))


def test_rows_move_by_gathers_and_the_transpose_is_exact():
    """_take_rows' hand-written transpose against autodiff's scatter-add."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (6, 4))
    idx = jnp.array([2, 6, 0, 2, 5, 6, 1], jnp.int32)  # 6 = the zero row
    back = jnp.array([[2, 6, 0, 7, 7, 4], [7, 7, 3, 7, 7, 7]], jnp.int32)  # 7 = none
    w = jax.random.normal(ks[1], (7, 4))
    got = jax.grad(lambda x: jnp.sum(moe._take_rows(x, idx, back) * w))(x)
    want = jax.grad(lambda x: jnp.sum(moe._take(x, idx) * w))(x)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert not np.any(np.asarray(moe._take_rows(x, idx, back))[[1, 5]])


def take_for_every_move(x, idx, live_rows=None):
    """`moe._move_rows` by XLA's gather alone, over all rows: the oracle."""
    return moe._take_sum(x, idx)


@pytest.mark.parametrize("held_of", [4, 1], ids=["quarter_held", "every_expert_held"])
def test_the_dispatch_moves_the_rows_xlas_gather_moves(held_of, monkeypatch):
    """_gmm_ffn's output and its gradients to src and to the three
    weights, element for element, against the same function with `_take`
    over all rows in the place of every move: one entry in four held (the
    bounded loop and the kernel) and every entry held (the loop and one
    XLA gather)."""
    src, _, params, e = sentinel_dispatch(8)
    eid = jax.random.randint(jax.random.PRNGKey(8), (4 * src.shape[0],), 0, held_of * e)
    eid = jnp.where(eid < e, eid, e)
    w = jax.random.normal(jax.random.PRNGKey(9), (eid.shape[0], src.shape[1]))

    def run():
        # a function of its own each time: a jit would keep the first trace
        loss = lambda src, params: jnp.sum(moe._gmm_ffn(src, eid, params, e) * w)
        return moe._gmm_ffn(src, eid, params, e), jax.grad(loss, argnums=(0, 1))(src, params)

    got = run()
    monkeypatch.setattr(moe, "_move_rows", take_for_every_move)
    want = run()
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.any(np.asarray(got[0])) and np.any(np.asarray(got[1][0]))


def test_no_matrix_product_for_a_dead_tile():
    """Rows of dead tiles hold NaN: a product over them would poison the
    weight gradient (a sum over rows) and, were it written, the output."""
    e, d, ff, tile = 2, 128, 256, G.TILE_M
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    tile_expert = jnp.array([0, 1, 1, e, e, e, e, e], jnp.int32)
    m, live = tile_expert.shape[0] * tile, 3 * tile
    x = jax.random.normal(ks[0], (m, d)).at[live:].set(jnp.nan)
    w1 = jax.random.normal(ks[1], (e, d, ff)) * 0.1
    w3 = jax.random.normal(ks[2], (e, d, ff)) * 0.1
    w2 = jax.random.normal(ks[3], (e, ff, d)) * 0.1
    ones = jnp.ones((e, ff))

    def ffn(x, w1, w3, w2, te):
        h = G.gmm_swiglu(x, w1, w3, te, ones, ones)
        return G.gmm(h, w2, te)

    def loss(x, w1, w3, w2, te):
        return jnp.sum(ffn(x, w1, w3, w2, te)[:live] ** 2)

    y = ffn(x, w1, w3, w2, tile_expert)
    y_live = ffn(x[:live], w1, w3, w2, tile_expert[:3])
    np.testing.assert_array_equal(np.asarray(y[:live]), np.asarray(y_live))
    g = jax.grad(loss, argnums=(0, 1, 2, 3))(x, w1, w3, w2, tile_expert)
    g_live = jax.grad(
        lambda *a: jnp.sum(ffn(*a) ** 2), argnums=(0, 1, 2, 3))(
            x[:live], w1, w3, w2, tile_expert[:3])
    np.testing.assert_array_equal(np.asarray(g[0][:live]), np.asarray(g_live[0]))
    for a, b in zip(g[1:], g_live[1:]):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_counters_follow_the_rows_routed_here():
    params, u = expert_layer(4)
    _, _, stats = moe.moe_layer(u, share_of(params, 0, 2), top_k=4)
    s = jax.nn.sigmoid(u.reshape(-1, 64) @ params["router"]) + params["router_bias"]
    _, sel = jax.lax.top_k(s, 4)
    counts = [int((sel == e).sum()) for e in (0, 1)]
    assert float(stats["moe_rows_routed"]) == 4 * 96
    assert float(stats["moe_rows_held"]) == sum(counts)
    assert float(stats["moe_rows_fullest"]) == max(counts)
    tile = G.TILE_M
    assert float(stats["gmm_live_tiles"]) == sum(-(-c // tile) for c in counts)
    assert float(stats["gmm_grid_tiles"]) == -(-4 * 96 // tile) + 2
    assert stats["gmm_live_tiles"] <= stats["gmm_grid_tiles"]
    # the forward's two row moves: the live tiles' rows in, the held
    # entries' rows out, of the rows the padded layout and the entries span
    assert float(stats["moe_rows_moved"]) == (
        sum(-(-c // tile) for c in counts) * tile + sum(counts))
    assert float(stats["moe_rows_spanned"]) == (
        (-(-4 * 96 // tile) + 2) * tile + 4 * 96)


# -- the short convolution -----------------------------------------------------


def test_convolution_sees_no_token_after_its_own():
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    d, t, at = 32, 24, 11
    layer = {"conv_in": jax.random.normal(ks[0], (d, 3 * d)) * 0.2,
             "conv_w": jax.random.normal(ks[1], (d, 3)),
             "conv_out": jax.random.normal(ks[2], (d, d)) * 0.2}
    u = jax.random.normal(ks[3], (2, t, d))
    moved = u.at[:, at].add(jax.random.normal(ks[4], (2, d)))
    a, b = short_conv(u, layer)[0], short_conv(moved, layer)[0]
    np.testing.assert_array_equal(np.asarray(a[:, :at]), np.asarray(b[:, :at]))
    # it reaches exactly two tokens back: t, t+1, t+2 move, t+3 does not
    changed = np.any(np.asarray(a != b), axis=(0, 2))
    assert list(np.nonzero(changed)[0]) == [at, at + 1, at + 2]
    # tap K-1 weighs the token itself
    g = jnp.ones((1, 4, d))
    np.testing.assert_allclose(causal_taps(g, layer["conv_w"])[0, 0],
                               layer["conv_w"][:, 2], rtol=1e-6)


# -- the published sizes, and the paths that refuse them ------------------------


def test_published_sizes_count_8_34b_parameters_1_56b_active():
    config = llama.LlamaConfig.config_for("lfm2-8b-a1b")
    shapes = jax.eval_shape(lambda k: llama.init(config, k), jax.random.PRNGKey(0))
    size = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(t))
    total = size(shapes)
    one_expert = 3 * 2048 * 1792
    experts = sum(size({k: l["moe"][k] for k in ("w1", "w3", "w2")})
                  for l in shapes["layers"] if "moe" in l)
    assert experts == 22 * 32 * one_expert
    active = total - experts + 22 * 4 * one_expert
    assert round(total / 1e9, 2) == 8.34
    assert round(active / 1e9, 2) == 1.56
    kinds = [("conv_in" in l, "moe" in l) for l in shapes["layers"]]
    assert sum(c for c, _ in kinds) == 18 and sum(m for _, m in kinds) == 22
    assert shapes["layers"][2]["q_norm"].shape == (64,)
    # the sharding contract covers every leaf
    specs = llama.param_specs(config)
    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda s: 0, specs, is_leaf=is_spec)
    ) == jax.tree_util.tree_structure(jax.tree_util.tree_map(lambda s: 0, shapes))


def small_hybrid():
    return dataclasses.replace(
        hybrid_config(dict(CFG, num_experts=2), SEQ), use_flash=False)


def test_cached_decode_refuses_a_convolution_layer():
    from kubedl_tpu.models import decode

    with pytest.raises(NotImplementedError, match="no state for a short-convolution"):
        decode.init_kv_cache(small_hybrid(), 1, 64)


def test_serving_engine_refuses_a_convolution_layer():
    from kubedl_tpu.models.serving import ServingEngine

    config = small_hybrid()
    params = llama.init(config, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="ServingEngine has no state"):
        ServingEngine(params, config, slots=2, max_len=64)


def test_pipelined_forward_refuses_layers_of_unlike_leaves():
    config = small_hybrid()
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("stage",))
    tokens = jnp.zeros((2, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="no per-stage layer kinds"):
        llama.forward_pipelined_and_aux({}, tokens, config, mesh)
