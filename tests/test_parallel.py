"""Mesh construction, ring attention vs reference, and the sharded Llama
train step — all on the 8-virtual-CPU-device mesh (SURVEY.md §4: multi-host
logic exercised without TPUs)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from kubedl_tpu.models import llama
from kubedl_tpu.ops.flash_attention import attention_reference
from kubedl_tpu.ops.ring_attention import ring_attention
from kubedl_tpu.parallel.mesh import ShardingRules, build_mesh, parse_mesh_env
from kubedl_tpu.parallel.train_step import make_train_step


def test_parse_mesh_env():
    axes = parse_mesh_env("data=2,fsdp=4")
    assert axes["data"] == 2 and axes["fsdp"] == 4 and axes["tensor"] == 1
    with pytest.raises(ValueError):
        parse_mesh_env("bogus=2")


def test_build_mesh_8_devices():
    mesh = build_mesh({"data": 2, "fsdp": 2, "tensor": 2})
    assert dict(mesh.shape) == {
        "data": 2, "fsdp": 2, "stage": 1, "tensor": 2, "context": 1, "expert": 1,
    }


def test_build_mesh_wildcard():
    mesh = build_mesh({"data": -1, "tensor": 2})
    assert mesh.shape["data"] == 4


def test_build_mesh_mismatch_raises():
    with pytest.raises(ValueError):
        build_mesh({"data": 3})


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    mesh = build_mesh({"context": 8})
    b, h, t, d = 2, 4, 256, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, t, d))
    k = jax.random.normal(ks[1], (b, h, t, d))
    v = jax.random.normal(ks[2], (b, h, t, d))
    out = ring_attention(q, k, v, mesh=mesh, causal=causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)


@pytest.mark.slow
def test_ring_attention_gradients():
    mesh = build_mesh({"context": 4, "data": 2})
    b, h, t, d = 2, 2, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, h, t, d))
    k = jax.random.normal(ks[1], (b, h, t, d))
    v = jax.random.normal(ks[2], (b, h, t, d))

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh=mesh, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gr, gref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=5e-3, rtol=5e-3, err_msg=f"d{name}"
        )


def tiny_cfg(**kw):
    # f32 + no flash on CPU tests; remat on to exercise the checkpoint path
    return llama.LlamaConfig.tiny(dtype=jnp.float32, use_flash=False, **kw)


def test_llama_forward_shapes_and_finite():
    cfg = tiny_cfg()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_llama_loss_decreases_single_device():
    cfg = tiny_cfg()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
    tx = optax.adam(1e-2)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt, tokens):
        loss, g = jax.value_and_grad(llama.loss_fn)(params, tokens, cfg)
        up, opt = tx.update(g, opt)
        return optax.apply_updates(params, up), opt, loss

    losses = []
    for _ in range(8):
        params, opt, loss = step(params, opt, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("use_flash", [False, True])
def test_llama_sharded_train_step_dp_fsdp_tp(use_flash):
    """use_flash=True is the presets' default: GSPMD cannot partition the
    Mosaic kernel, so on a mesh it runs inside a shard_map over the batch
    and heads axes (interpret mode here; tests/test_tpu_compile.py holds
    the same step to the TPU's compiler)."""
    mesh = build_mesh({"data": 2, "fsdp": 2, "tensor": 2})
    rules = ShardingRules()
    cfg = dataclasses.replace(tiny_cfg(), use_flash=use_flash)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    spec_tree = llama.param_specs(cfg, rules)

    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, mesh=mesh, rules=rules)

    tx = optax.adamw(1e-3)
    init_state, train_step = make_train_step(
        loss, tx, mesh, spec_tree, rules.spec("batch", None), rules
    )
    state = init_state(params)
    # the moments start where the params are, not replicated: else every
    # device holds them whole and the step compiles a second time
    mu = state.opt_state[0].mu
    assert mu["embed"].sharding.spec == rules.spec("vocab", "embed")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
    want = float(llama.loss_fn(params, tokens, tiny_cfg()))
    state, metrics = train_step(state, tokens)
    np.testing.assert_allclose(float(metrics["loss"]), want, rtol=1e-4)
    assert int(state.step) == 1
    # params actually sharded: embed spec P("tensor", "fsdp")
    emb_shard = state.params["embed"].sharding
    assert emb_shard.spec == rules.spec("vocab", "embed")


@pytest.mark.slow
def test_llama_train_step_with_context_parallelism():
    mesh = build_mesh({"data": 2, "context": 4})
    rules = ShardingRules()
    cfg = tiny_cfg()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    spec_tree = llama.param_specs(cfg, rules)

    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, mesh=mesh, rules=rules)

    init_state, train_step = make_train_step(
        loss, optax.adam(1e-3), mesh, spec_tree, rules.spec("batch", None), rules
    )
    state = init_state(params)
    # seq-1 must divide by context axis: 129 tokens -> 128 positions
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, cfg.vocab_size)
    state, metrics = train_step(state, tokens)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.slow
def test_grad_accumulation_matches_big_batch():
    """accum_steps=2 on half batches must equal one step on the full batch."""
    import optax

    from kubedl_tpu.models import llama
    from kubedl_tpu.parallel.train_step import make_train_step

    config = llama.LlamaConfig.tiny(dtype=jnp.float32, use_flash=False)
    mesh = build_mesh({"data": 8})
    rules = ShardingRules()
    params = llama.init(config, jax.random.PRNGKey(0))
    spec_tree = llama.param_specs(config, rules)

    def loss(p, tokens):
        return llama.loss_fn(p, tokens, config, mesh=mesh, rules=rules)

    tokens = jax.random.randint(jax.random.PRNGKey(1), (16, 17), 0, config.vocab_size)

    init_a, step_a = make_train_step(
        loss, optax.sgd(1e-2), mesh, spec_tree, rules.spec("batch", None), rules
    )
    state_a = init_a(params)
    state_a, _ = step_a(state_a, tokens)

    init_b, step_b = make_train_step(
        loss, optax.sgd(1e-2), mesh, spec_tree, rules.spec("batch", None), rules,
        accum_steps=2,
    )
    state_b = init_b(params)
    state_b, _ = step_b(state_b, tokens[:8])
    state_b, _ = step_b(state_b, tokens[8:])

    a = jax.tree_util.tree_leaves(state_a.params)
    b = jax.tree_util.tree_leaves(state_b.params)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_chunked_ce_matches_full_loss_and_grads():
    """ce_chunks must be a pure optimization: same loss, same gradients."""
    import dataclasses

    import numpy as np

    from kubedl_tpu.models import llama

    config = llama.LlamaConfig.tiny(dtype=jnp.float32, use_flash=False)
    params = llama.init(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, config.vocab_size)

    full = jax.value_and_grad(lambda p: llama.loss_fn(p, tokens, config))
    cfg_c = dataclasses.replace(config, ce_chunks=4)
    chunked = jax.value_and_grad(lambda p: llama.loss_fn(p, tokens, cfg_c))

    l0, g0 = full(params)
    l1, g1 = chunked(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


def test_chunked_ce_rejects_indivisible_vocab():
    import dataclasses

    import pytest

    from kubedl_tpu.models import llama

    config = dataclasses.replace(
        llama.LlamaConfig.tiny(dtype=jnp.float32, use_flash=False), ce_chunks=7
    )
    params = llama.init(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, config.vocab_size)
    with pytest.raises(ValueError, match="not divisible"):
        llama.loss_fn(params, tokens, config)


def test_remat_policy_dots_matches_full_remat():
    import dataclasses

    import numpy as np

    from kubedl_tpu.models import llama

    config = llama.LlamaConfig.tiny(dtype=jnp.float32, use_flash=False)
    params = llama.init(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, config.vocab_size)

    base = jax.value_and_grad(lambda p: llama.loss_fn(p, tokens, config))
    cfg_d = dataclasses.replace(config, remat_policy="dots")
    dots = jax.value_and_grad(lambda p: llama.loss_fn(p, tokens, cfg_d))

    l0, g0 = base(params)
    l1, g1 = dots(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Ulysses all-to-all sequence parallelism (ops/ulysses.py) — the second
# long-context strategy alongside the ring.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_reference(causal):
    from kubedl_tpu.ops.ulysses import ulysses_attention

    mesh = build_mesh({"context": 4, "data": 2})
    b, h, t, d = 2, 4, 256, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, t, d))
    k = jax.random.normal(ks[1], (b, h, t, d))
    v = jax.random.normal(ks[2], (b, h, t, d))
    out = ulysses_attention(q, k, v, mesh=mesh, causal=causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)


@pytest.mark.slow
def test_ulysses_attention_gradients():
    from kubedl_tpu.ops.ulysses import ulysses_attention

    mesh = build_mesh({"context": 4, "data": 2})
    b, h, t, d = 2, 4, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, h, t, d))
    k = jax.random.normal(ks[1], (b, h, t, d))
    v = jax.random.normal(ks[2], (b, h, t, d))

    def loss_uly(q, k, v):
        return jnp.sum(ulysses_attention(q, k, v, mesh=mesh, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    gu = jax.grad(loss_uly, argnums=(0, 1, 2))(q, k, v)
    gref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gu, gref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=5e-3, rtol=5e-3, err_msg=f"d{name}"
        )


def test_ulysses_rejects_indivisible_heads():
    from kubedl_tpu.ops.ulysses import ulysses_attention

    mesh = build_mesh({"context": 8})
    q = jnp.zeros((1, 4, 64, 16))  # 4 heads over 8 context shards
    with pytest.raises(ValueError):
        ulysses_attention(q, q, q, mesh=mesh)


@pytest.mark.slow
def test_llama_train_step_with_ulysses_context_parallelism():
    mesh = build_mesh({"data": 2, "context": 4})
    rules = ShardingRules()
    cfg = tiny_cfg(context_parallel="ulysses")
    params = llama.init(cfg, jax.random.PRNGKey(0))
    spec_tree = llama.param_specs(cfg, rules)

    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, mesh=mesh, rules=rules)

    init_state, train_step = make_train_step(
        loss, optax.adam(1e-3), mesh, spec_tree, rules.spec("batch", None), rules
    )
    state = init_state(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, cfg.vocab_size)
    state, metrics = train_step(state, tokens)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.slow
def test_llama_qkv_bias_sharded_train_step():
    """Qwen2-style biased projections: init and param_specs agree on
    tree structure, and a dp x tp sharded step trains the biases."""
    mesh = build_mesh({"data": 4, "tensor": 2})
    rules = ShardingRules()
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32, use_flash=False,
                                 attn_qkv_bias=True)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    spec_tree = llama.param_specs(cfg, rules)
    jax.tree.map(lambda *_: None, params, spec_tree)  # same structure
    assert "bq" in params["layers"][0]

    def loss(params, batch):
        return llama.loss_fn(params, batch, cfg, mesh=mesh, rules=rules)

    init_state, train_step = make_train_step(
        loss, optax.adamw(1e-2), mesh, spec_tree,
        rules.spec("batch", None), rules)
    state = init_state(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                cfg.vocab_size)
    state, metrics = train_step(state, tokens)
    assert np.isfinite(float(metrics["loss"]))
    # the bias actually receives gradient (zeros-init but trained)
    assert float(jnp.sum(jnp.abs(state.params["layers"][0]["bq"]))) > 0.0
