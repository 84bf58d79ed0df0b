"""The chunked scan's Pallas kernels (`kubedl_tpu/ops/ssm_scan.py`) in
interpret mode on the CPU, at small shapes of whole tiles: `y` and all
five gradients against the token-by-token recurrence
(`benchmarks/reference/granite_ref.py`) and against the XLA form; which
form `chunked_scan` takes, by shape and backend; `ssm_kernel_chunks`;
remat. What Mosaic refuses is `tests/test_tpu_compile.py`'s to see.

On the CPU `chunked_scan` takes the XLA form whatever the shape
(`scan_takes_kernel` asks `ops.takes_kernel`): the `kernel_form` fixture
(tests/conftest.py) steers that one rule in the test, and the kernels
themselves still run interpreted."""
import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights_ssm
from benchmarks.reference import granite_ref
from benchmarks.runners.train_ssm import ssm_config
from kubedl_tpu.models import llama, ssm
from kubedl_tpu.ops import ssm_scan

STATE = 128
KERNELS = ("ssm_scan_fwd", "ssm_scan_bwd")
INPUTS = ("x", "dt", "a", "B", "C")


@contextlib.contextmanager
def xla_form():
    """`chunked_scan` takes its XLA form whatever it would choose."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ssm, "scan_takes_kernel", lambda *a, **kw: False)
        yield


def scan_inputs(batch, seq, heads, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (batch, seq, heads, 64), jnp.float32)
    dt = jnp.exp(jax.random.uniform(ks[1], (batch, seq, heads), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    a = -jax.random.uniform(ks[2], (heads,), jnp.float32, 1.0, 16.0)
    b_ = jax.random.normal(ks[3], (batch, seq, STATE), jnp.float32)
    c_ = jax.random.normal(ks[4], (batch, seq, STATE), jnp.float32)
    return x.astype(dtype), dt, a, b_.astype(dtype), c_.astype(dtype)


def recurrence(x, dt, a, b_, c_):
    return granite_ref.recurrence(x, jnp.exp(dt * a), dt, b_, c_, block=128)


def jaxpr_of(fn, *args):
    return str(jax.make_jaxpr(fn)(*args))


# one chunk (nothing carried), three (carried), a tail that is no multiple
# of the chunk (640 = 2.5 x 256, 300 = 2.34 x 128); 8 and 16 heads of 64
SHAPES = [
    pytest.param(1, 256, 8, 256, id="one_chunk"),
    pytest.param(2, 384, 8, 128, id="three_chunks"),
    pytest.param(1, 512, 16, 256, id="two_chunks_16_heads"),
    pytest.param(1, 640, 8, 256, id="tail_of_half_a_chunk"),
    pytest.param(2, 300, 16, 128, id="tail_of_44_tokens"),
]


@pytest.mark.parametrize("batch,seq,heads,chunk", SHAPES)
def test_kernel_is_the_recurrence_and_the_xla_form(kernel_form, batch, seq, heads, chunk):
    args = scan_inputs(batch, seq, heads)
    assert ssm.scan_takes_kernel(args[0].shape, STATE, chunk)
    with jax.default_matmul_precision("highest"):
        y, through, _ = jax.jit(ssm.chunked_scan, static_argnums=5)(*args, chunk)
        with xla_form():
            y_xla, through_xla, _ = jax.jit(
                lambda *a: ssm.chunked_scan(*a, chunk))(*args)
    want = recurrence(*args)
    assert y.shape == want.shape == args[0].shape and y.dtype == jnp.float32
    top = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(y - want))) < 2e-5 * top
    assert float(jnp.max(jnp.abs(y - y_xla))) < 2e-6 * top
    np.testing.assert_array_equal(through, through_xla)


@pytest.mark.parametrize("batch,seq,heads,chunk", SHAPES)
def test_kernels_gradients_are_the_recurrences(kernel_form, batch, seq, heads, chunk):
    args = scan_inputs(batch, seq, heads, seed=1)

    def ours(*a):
        return jnp.sum(jnp.sin(ssm.chunked_scan(*a, chunk)[0]))

    def theirs(*a):
        return jnp.sum(jnp.sin(recurrence(*a)))

    every = tuple(range(5))
    assert all(k in jaxpr_of(jax.grad(ours, argnums=every), *args) for k in KERNELS)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(ours, argnums=every))(*args)
    want = jax.jit(jax.grad(theirs, argnums=every))(*args)
    for name, g, w in zip(INPUTS, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        gap = float(jnp.linalg.norm(g - w)) / float(jnp.linalg.norm(w))
        assert gap < 1e-4, (name, gap)


def test_bf16_operands_stay_within_their_rounding_of_the_xla_form(kernel_form):
    """The model's dtype: both forms round the same operands to bf16 and
    sum in float32; the kernel keeps dW and dG float32 where autodiff
    rounds them, so the gradients agree to bf16's step and not closer."""
    args = scan_inputs(2, 384, 8, seed=2, dtype=jnp.bfloat16)
    dy = jax.random.normal(jax.random.PRNGKey(3), args[0].shape, jnp.float32)

    def both(*a):
        y, vjp = jax.vjp(lambda *b: ssm.chunked_scan(*b, 128)[0], *a)
        return (y,) + vjp(dy)

    got = jax.jit(both)(*args)
    with xla_form():
        want = jax.jit(lambda *a: both(*a))(*args)
    exact = jax.jit(lambda *a: jax.vjp(recurrence, *a)[1](dy))(
        *(v.astype(jnp.float32) for v in args))
    for name, g, w, e in zip(("y",) + INPUTS, got, want, (None,) + exact):
        assert g.dtype == w.dtype, name
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert float(jnp.linalg.norm(g - w)) < 8e-3 * float(jnp.linalg.norm(w)), name
        if e is not None:  # and as near the float32 recurrence as XLA's (on the
            # CPU XLA's float32 cotangents reach their matmuls unrounded)
            off = lambda v: float(jnp.linalg.norm(v - e)) / float(jnp.linalg.norm(e))
            assert off(g) < 2 * off(w) + 1e-3, (name, off(g), off(w))


# -- which form runs -------------------------------------------------------------


@pytest.mark.parametrize("heads,head_dim,state,chunk,seq,takes", [
    (64, 64, 128, 256, 8192, True),    # the benchmark's cell
    (8, 64, 128, 128, 128, True),      # the least the kernels take
    (16, 64, 128, 256, 255, False),    # a sequence under one chunk
    (4, 16, 16, 8, 32, False),         # tests/test_ssm_model.py's size
    (16, 64, 128, 192, 768, False),    # a chunk of 1.5 lane tiles
    (16, 64, 64, 128, 512, False),     # a state of half a lane tile
    (12, 64, 128, 128, 512, False),    # heads in no blocks of 8
    (8, 8, 128, 128, 512, False),      # eight heads fill half a lane tile
])
def test_the_form_is_chosen_from_shapes_backend_and_mesh(
        request, heads, head_dim, state, chunk, seq, takes):
    shape = (2, seq, heads, head_dim)
    assert ssm_scan.supports(heads, head_dim, state, chunk, seq) == takes
    assert not ssm.scan_takes_kernel(shape, state, chunk)  # the CPU: XLA's
    request.getfixturevalue("kernel_form")
    assert ssm.scan_takes_kernel(shape, state, chunk) == takes
    mesh = lambda **axes: type("Mesh", (), {"shape": axes, "size": 4})()
    assert ssm.scan_takes_kernel(shape, state, chunk, mesh(fsdp=4)) == takes
    assert not ssm.scan_takes_kernel(shape, state, chunk, mesh(fsdp=2, tensor=2))


def test_an_unaligned_shape_traces_the_xla_form_and_an_aligned_one_the_kernels(
        request):
    """No pallas_call where the shapes are not whole tiles, whatever the
    backend: the same equations the CPU traces. At whole tiles the two
    kernel names, and no [b, c, h, q, q] array."""
    # a function a trace: make_jaxpr remembers what a function traced to
    scan = lambda: lambda *a: ssm.chunked_scan(*a, 8)[0]
    grad = lambda: jax.grad(
        lambda *a: jnp.sum(ssm.chunked_scan(*a, 128)[0]), argnums=(0, 1))
    small, aligned = scan_inputs(2, 27, 4), scan_inputs(1, 256, 8)
    on_cpu = jaxpr_of(scan(), *small), jaxpr_of(grad(), *aligned)
    assert not any("pallas_call" in text for text in on_cpu)
    assert "f32[1,2,8,128,128]" in on_cpu[1]
    request.getfixturevalue("kernel_form")
    assert jaxpr_of(scan(), *small) == on_cpu[0]
    text = jaxpr_of(grad(), *aligned)
    assert all(f"name={k}" in text for k in KERNELS)
    assert "[1,2,8,128,128]" not in text


# -- the model ---------------------------------------------------------------------

# hidden 64; layers mamba, attention, mamba; 8 state-space heads of 64
# (inner 512), state 128, chunk 128: the least the kernels take
CFG = {
    "hidden_size": 64, "intermediate_size": 128, "shared_intermediate_size": 128,
    "num_hidden_layers": 3, "layer_types": ["mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "attention_bias": False,
    "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8,
    "mamba_n_heads": 8, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 8,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_proj_bias": False, "num_local_experts": 0,
    "hidden_act": "silu", "normalization_function": "rmsnorm",
    "position_embedding_type": "nope", "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "vocab_size": 128, "initializer_range": 0.2,
    "torch_dtype": "float32", "remat": "full", "ce_chunks": 4,
}


def model(seq, **kw):
    config = dataclasses.replace(ssm_config(CFG, seq), use_flash=False, **kw)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        weights_ssm.make_fn(CFG)(jax.random.PRNGKey(4)))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, seq + 1), 0, 128)
    return config, params, tokens


@pytest.mark.parametrize("seq,chunks", [(256, 2), (300, 3), (100, 1)])
def test_kernel_chunks_are_counted_where_the_kernels_ran(request, seq, chunks):
    config, params, tokens = model(seq)
    stats_of = lambda: jax.jit(
        lambda p: llama.loss_and_stats(p, tokens, config))(params)
    loss, stats = stats_of()
    assert float(stats["ssm_chunks"]) == 2 * 2 * chunks
    assert float(stats["ssm_kernel_chunks"]) == 0  # the CPU: XLA's form
    request.getfixturevalue("kernel_form")
    loss_k, stats_k = stats_of()
    took = seq >= 128  # a sequence under one chunk stays XLA's
    assert float(stats_k["ssm_kernel_chunks"]) == took * 2 * 2 * chunks
    assert float(stats_k["ssm_chunks"]) == 2 * 2 * chunks
    assert float(loss_k) == pytest.approx(float(loss), rel=1e-5)


def test_kernel_chunks_are_read_from_the_form_each_scan_took(monkeypatch):
    """`ssm_kernel_chunks` is what each layer's scan did, not a second
    answer of the predicate: one that says yes to its first question
    alone leaves the count at the chunks of the one scan that ran as the
    kernels."""
    config, params, tokens = model(256)
    fn = lambda p: llama.loss_and_stats(p, tokens, config)

    def first_yes():
        answers = iter([True])
        monkeypatch.setattr(ssm, "scan_takes_kernel", lambda *a, **kw: next(answers, False))

    first_yes()
    assert jaxpr_of(fn, params).count("name=ssm_scan_fwd") == 1
    first_yes()
    _, stats = jax.jit(fn)(params)
    assert float(stats["ssm_chunks"]) == 2 * 2 * 2  # two layers of two sequences
    assert float(stats["ssm_kernel_chunks"]) == 2 * 2


def test_remat_on_and_off_agree_and_both_are_the_xla_forms_gradient(request):
    config, params, tokens = model(256)
    grad = lambda c: jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, tokens, c)))(params)
    loss_xla, g_xla = grad(config)
    request.getfixturevalue("kernel_form")
    assert "ssm_scan_bwd" in jaxpr_of(
        jax.grad(lambda p: llama.loss_fn(p, tokens, config)), params)
    (on, g_on), (off, g_off) = grad(config), grad(dataclasses.replace(config, remat=False))
    assert float(on) == pytest.approx(float(off), rel=1e-6)
    assert float(on) == pytest.approx(float(loss_xla), rel=1e-5)
    flat = lambda g: dict(jax.tree_util.tree_flatten_with_path(g)[0])
    for path, want in flat(g_xla).items():
        scale = float(jnp.linalg.norm(want))
        assert scale > 0, jax.tree_util.keystr(path)
        for got, limit in ((flat(g_on)[path], 1e-3), (flat(g_off)[path], 1e-3)):
            gap = float(jnp.linalg.norm(got - want)) / scale
            assert gap < limit, (jax.tree_util.keystr(path), gap)
        on_off = float(jnp.linalg.norm(flat(g_on)[path] - flat(g_off)[path])) / scale
        assert on_off < 1e-4, jax.tree_util.keystr(path)


def test_two_devices_under_fsdp_ride_a_shard_map_and_give_the_one_device_loss(
        request):
    from kubedl_tpu.parallel.mesh import ShardingRules, build_mesh

    config, params, tokens = model(256)
    one = jax.jit(jax.value_and_grad(lambda p: llama.loss_fn(p, tokens, config)))(params)
    request.getfixturevalue("kernel_form")
    mesh, rules = build_mesh({"fsdp": 2}, devices=jax.devices()[:2]), ShardingRules()
    fn = lambda p: llama.loss_fn(p, tokens, config, mesh=mesh, rules=rules)
    text = jaxpr_of(jax.grad(fn), params)
    assert "shard_map" in text and all(f"name={k}" in text for k in KERNELS)
    two = jax.jit(jax.value_and_grad(fn))(params)
    assert float(two[0]) == pytest.approx(float(one[0]), rel=1e-5)
    gaps = jax.tree_util.tree_map(
        lambda g, w: float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)), two[1], one[1])
    assert max(jax.tree_util.tree_leaves(gaps)) < 1e-3


@pytest.mark.parametrize("kernel_chunks,tail", [(576, " kernel_chunks=576"), (None, "")])
def test_trace_shows_the_kernels_chunks_after_what_it_showed_before(kernel_chunks, tail):
    """`kubedl-tpu trace`'s DETAIL of a state-space model's step; a record
    written before the kernels has no such counter."""
    from kubedl_tpu.cli import _span_detail

    attrs = {"step": 7, "ssm_layers": 9.0, "ssm_chunks": 576.0,
             "ssm_state_carry": 0.0168, "ssm_dt_mean": 0.0317}
    if kernel_chunks is not None:
        attrs["ssm_kernel_chunks"] = float(kernel_chunks)
    assert _span_detail(attrs) == (
        "step=7 ssm_layers=9 chunks=576 carry=0.017 dt=0.0317" + tail)
