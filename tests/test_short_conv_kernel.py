"""The convolution layer's gates and taps as Pallas kernels
(`kubedl_tpu/ops/causal_conv.py`: `short_conv_fwd`, `short_conv_bwd`) in
interpret mode on the CPU, at small shapes of whole tiles: the forward
against the XLA form (`C * causal_taps(B * z, w)`) bit for bit, the
backward against its autodiff; that no token reads a later one or another
sequence; which form `gated_taps` takes, by shape, backend and mesh; the
`short_conv_*` counters; remat; two devices. What Mosaic refuses is
`tests/test_tpu_compile.py`'s to see.

On the CPU `gated_taps` takes the XLA form whatever the shape
(`conv_takes_kernel` asks `ops.interpret`): the `kernel_form` fixture
steers that one question in the test, and the kernels themselves still
run interpreted. A program's token block is cut to 128 tokens in passes
of 32, so that a sequence of 384 crosses two block edges."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubedl_tpu.models import llama, short_conv
from kubedl_tpu.models.short_conv import causal_taps
from kubedl_tpu.ops import causal_conv

KERNELS = ("short_conv_fwd", "short_conv_bwd")
D = 256


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(causal_conv, "TOKEN_BLOCK", 128)
    monkeypatch.setattr(causal_conv, "ROWS", 32)


@pytest.fixture
def kernel_form(monkeypatch):
    """`gated_taps` chooses as it would on a TPU."""
    monkeypatch.setattr(short_conv, "interpret", lambda: False)


def xla_form(u, w):
    """What `short_conv` held before the kernels, written out again."""
    b_, c_, z = jnp.split(u, 3, axis=-1)
    return c_ * causal_taps(b_ * z, w)


def kernels(u, w):
    y, took = short_conv.gated_taps(u, w)
    assert took
    return y


def conv_inputs(batch, seq, taps, dtype=jnp.bfloat16, seed=0):
    """The in projection's output and taps in the model's dtype, and a
    cotangent of the gated output."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    u = jax.random.normal(ks[0], (batch, seq, 3 * D), jnp.float32).astype(dtype)
    w = (jax.random.normal(ks[1], (D, taps), jnp.float32) / taps).astype(dtype)
    dy = jax.random.normal(ks[2], (batch, seq, D), jnp.float32).astype(dtype)
    return u, w, dy


def vjp_of(fn, u, w, dy):
    y, vjp = jax.vjp(fn, u, w)
    return (y,) + vjp(dy)


def jaxpr_of(fn, *args):
    return str(jax.make_jaxpr(fn)(*args))


def operations(fn, *args):
    """Every operation of fn's jaxpr, a called function's once a call, not
    inside a kernel's body: the primitives' names, a kernel by its own."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["name"])
                continue
            found.append(eqn.primitive.name)
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) else (value,):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def gap(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want)) / float(jnp.linalg.norm(want))


# K taps; one and two sequences; a sequence of one token block (nothing
# before it) and of three (the K - 1 tokens before a block lie in the last)
SHAPES = [
    pytest.param(2, 2, 256, id="k2_two_sequences_two_blocks"),
    pytest.param(3, 2, 256, id="k3_two_sequences_two_blocks"),
    pytest.param(4, 2, 256, id="k4_two_sequences_two_blocks"),
    pytest.param(3, 1, 384, id="k3_one_sequence_three_blocks"),
    pytest.param(8, 1, 128, id="k8_the_most_taps"),
]


@pytest.mark.parametrize("taps,batch,seq", SHAPES)
def test_forward_is_the_xla_forms_bit_for_bit(kernel_form, taps, batch, seq):
    u, w, _ = conv_inputs(batch, seq, taps)
    assert short_conv.conv_takes_kernel(seq, D, taps)
    assert "name=short_conv_fwd" in jaxpr_of(kernels, u, w)
    got, want = jax.jit(kernels)(u, w), jax.jit(xla_form)(u, w)
    assert got.shape == want.shape == (batch, seq, D) and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("taps,batch,seq", SHAPES[:4])
def test_backward_rounds_where_autodiff_does(kernel_form, taps, batch, seq):
    """du is autodiff's of the XLA form taken one operation at a time bit
    for bit (each bf16 product and the taps' bf16 sum rounded where the
    jaxpr puts them); dw is a float32 sum in another order, rounded to
    bf16. XLA's fusion of that autodiff keeps the taps' cotangent float32
    where the jaxpr rounds it: within bf16 rounding of that."""
    args = conv_inputs(batch, seq, taps, seed=1)
    assert all(f"name={k}" in jaxpr_of(
        lambda u, w, dy: vjp_of(kernels, u, w, dy), *args) for k in KERNELS)
    got = jax.jit(lambda *a: vjp_of(kernels, *a))(*args)
    with jax.disable_jit():
        stepwise = vjp_of(xla_form, *args)
    fused = jax.jit(lambda *a: vjp_of(xla_form, *a))(*args)
    for name, g, s, f in zip(("y", "du", "dw"), got, stepwise, fused):
        assert g.shape == s.shape and g.dtype == s.dtype, name
        if name == "dw":  # a bf16 rounding that the order may flip, no more
            assert gap(g, s) < 1e-3, gap(g, s)
        else:
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(s, np.float32), err_msg=name)
        assert gap(g, f) < 6e-3, (name, gap(g, f))


@pytest.mark.parametrize("taps,batch,seq", SHAPES[1:4])
def test_float32_gradients_are_autodiffs_up_to_the_sums_order(
        kernel_form, taps, batch, seq):
    args = conv_inputs(batch, seq, taps, jnp.float32, seed=2)
    got = jax.jit(lambda *a: vjp_of(kernels, *a))(*args)
    want = jax.jit(lambda *a: vjp_of(xla_form, *a))(*args)
    for name, g, w in zip(("y", "du", "dw"), got, want):
        assert g.dtype == w.dtype == jnp.float32, name
        # a multiply-add the CPU's compiler contracts in one form alone
        assert gap(g, w) < 1e-6, (name, gap(g, w))


def test_no_token_reads_a_later_one_or_another_sequence(kernel_form):
    """Token 130 is the third of the second block of sequence 1: the
    outputs before it hold, the K - 1 after it move (across no edge
    here; 127 moves 127-129 across one), sequence 0 holds; and each
    sequence's output and gradients are what it gives alone, with zeros
    before its start."""
    u, w, dy = conv_inputs(2, 384, 3, seed=3)
    base = jax.jit(kernels)(u, w)
    for token in (130, 127):
        moved = jax.jit(kernels)(u.at[1, token].add(1.0), w)
        np.testing.assert_array_equal(base[0], moved[0])
        np.testing.assert_array_equal(base[1, :token], moved[1, :token])
        np.testing.assert_array_equal(base[1, token + 3:], moved[1, token + 3:])
        assert float(jnp.min(jnp.max(jnp.abs(
            base[1, token:token + 3] - moved[1, token:token + 3]), axis=-1))) > 0
    both = jax.jit(lambda *a: vjp_of(kernels, *a))(u, w, dy)
    for i in range(2):
        alone = jax.jit(lambda *a: vjp_of(kernels, *a))(u[i:i + 1], w, dy[i:i + 1])
        np.testing.assert_array_equal(both[0][i:i + 1], alone[0])
        np.testing.assert_array_equal(both[1][i:i + 1], alone[1])
    # a cotangent on sequence 1 alone reaches no column of sequence 0
    du = jax.jit(lambda *a: vjp_of(kernels, *a)[1])(u, w, dy.at[0].set(0))
    assert not np.any(np.asarray(du[0], np.float32))


# -- which form runs -------------------------------------------------------------


@pytest.mark.parametrize("seq,d,taps,takes", [
    (8192, 2048, 3, True),     # the benchmark's cell
    (128, 128, 2, True),       # the least the kernels take
    (8192, 2048, 9, False),    # taps that reach back more than a tile
    (8192, 2048, 1, False),    # no tap before the token: nothing to carry
    (8192, 2000, 3, False),    # C starts inside a 128-lane block
    (300, 256, 3, False),      # a sequence of 2.3 token blocks
    (48, 64, 3, False),        # tests/test_hybrid_model.py's size
])
def test_the_form_is_chosen_from_shapes_backend_and_mesh(
        monkeypatch, seq, d, taps, takes):
    assert causal_conv.supports(seq, 0, (d, d, d), taps) == takes
    assert not short_conv.conv_takes_kernel(seq, d, taps)  # the CPU: XLA's
    monkeypatch.setattr(short_conv, "interpret", lambda: False)
    assert short_conv.conv_takes_kernel(seq, d, taps) == takes
    mesh = lambda **axes: type("Mesh", (), {"shape": axes, "size": 4})()
    assert short_conv.conv_takes_kernel(seq, d, taps, mesh(fsdp=4)) == takes
    assert not short_conv.conv_takes_kernel(seq, d, taps, mesh(fsdp=2, tensor=2))


def test_an_unaligned_shape_or_nine_taps_trace_the_xla_form(monkeypatch):
    """No pallas_call where the shapes are not whole tiles, whatever the
    backend: the same equations the CPU traces."""
    def grad_of(d, taps):
        u, w = jnp.ones((1, 128, 3 * d), jnp.bfloat16), jnp.ones((d, taps), jnp.bfloat16)
        fn = lambda u, w: jnp.sum(short_conv.gated_taps(u, w)[0].astype(jnp.float32))
        return jaxpr_of(jax.grad(fn, argnums=(0, 1)), u, w)

    cases = [(200, 3), (D, 9), (D, 3)]
    on_cpu = [grad_of(*case) for case in cases]
    assert not any("pallas_call" in text for text in on_cpu)
    monkeypatch.setattr(short_conv, "interpret", lambda: False)
    assert [grad_of(*case) for case in cases[:2]] == on_cpu[:2]
    assert all(f"name={k}" in grad_of(*cases[2]) for k in KERNELS)
    # no split of u, no padded copy of the gates, no concatenation of du
    ops = operations(jax.grad(lambda u, w: jnp.sum(short_conv.gated_taps(u, w)[0].astype(
        jnp.float32)), argnums=(0, 1)), jnp.ones((1, 128, 3 * D), jnp.bfloat16),
        jnp.ones((D, 3), jnp.bfloat16))
    assert [op for op in ops if op in KERNELS] == list(KERNELS)
    assert not {"pad", "concatenate", "split", "slice"} & set(ops), ops


# -- the model ---------------------------------------------------------------------


def model(seq, **kw):
    """Hidden 128: two convolution layers around an attention layer,
    float32."""
    config = llama.LlamaConfig.tiny(
        n_layers=3, layer_types=("conv", "attention", "conv"), conv_kernel=3,
        use_flash=False, dtype=jnp.float32, max_seq_len=512, **kw)
    params = llama.init(config, jax.random.PRNGKey(4))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, seq + 1), 0, 256)
    return config, params, tokens


@pytest.mark.parametrize("seq,took", [(256, True), (300, False)])
def test_kernel_layers_are_counted_where_the_kernels_ran(monkeypatch, seq, took):
    config, params, tokens = model(seq)
    stats_of = lambda: jax.jit(
        lambda p: llama.loss_and_stats(p, tokens, config))(params)
    loss, stats = stats_of()
    assert float(stats["short_conv_layers"]) == 2
    assert float(stats["short_conv_kernel_layers"]) == 0  # the CPU: XLA's form
    monkeypatch.setattr(short_conv, "interpret", lambda: False)
    loss_k, stats_k = stats_of()
    assert float(stats_k["short_conv_kernel_layers"]) == 2 * took
    assert float(stats_k["short_conv_layers"]) == 2
    assert float(loss_k) == pytest.approx(float(loss), rel=1e-6)


@pytest.mark.parametrize("remat,calls", [
    (True, {"short_conv_fwd": 2, "short_conv_bwd": 1}),
    (False, {"short_conv_fwd": 1, "short_conv_bwd": 1}),
])
def test_under_remat_the_forward_runs_twice_and_the_backward_once(
        kernel_form, remat, calls):
    """A layer's kernels in the jaxpr of the loss's gradient: under the
    layer's checkpoint the backward pass runs the forward kernel again."""
    config, params, tokens = model(256, remat=remat)
    ops = operations(jax.grad(lambda p: llama.loss_fn(p, tokens, config)), params)
    assert {k: ops.count(k) for k in KERNELS} == {k: 2 * n for k, n in calls.items()}


def test_remat_on_and_off_agree_and_both_are_the_xla_forms_gradient(kernel_form):
    config, params, tokens = model(256)
    grad = lambda c: jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, tokens, c)))(params)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(short_conv, "conv_takes_kernel", lambda *a, **kw: False)
        loss_xla, g_xla = grad(config)
    (on, g_on), (off, g_off) = grad(config), grad(dataclasses.replace(config, remat=False))
    assert float(on) == pytest.approx(float(off), rel=1e-6)
    assert float(on) == pytest.approx(float(loss_xla), rel=1e-6)
    flat = lambda g: dict(jax.tree_util.tree_flatten_with_path(g)[0])
    for path, want in flat(g_xla).items():
        assert float(jnp.linalg.norm(want)) > 0, jax.tree_util.keystr(path)
        for got in (flat(g_on)[path], flat(g_off)[path]):
            assert gap(got, want) < 1e-5, (jax.tree_util.keystr(path), gap(got, want))


def test_two_devices_under_fsdp_ride_a_shard_map_and_give_the_one_device_loss(
        kernel_form):
    from kubedl_tpu.parallel.mesh import ShardingRules, build_mesh

    config, params, tokens = model(256)
    one = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, tokens, config)))(params)
    mesh, rules = build_mesh({"fsdp": 2}, devices=jax.devices()[:2]), ShardingRules()
    fn = lambda p: llama.loss_fn(p, tokens, config, mesh=mesh, rules=rules)
    text = jaxpr_of(jax.grad(fn), params)
    assert "shard_map" in text and all(f"name={k}" in text for k in KERNELS)
    two = jax.jit(jax.value_and_grad(fn))(params)
    assert float(two[0]) == pytest.approx(float(one[0]), rel=1e-6)
    # the taps' gradients are sums over both devices' sequences
    gaps = jax.tree_util.tree_map(gap, two[1], one[1])
    assert max(jax.tree_util.tree_leaves(gaps)) < 1e-5


@pytest.mark.parametrize("layers,tail", [
    (7, " short_conv_layers=7 short_conv_kernel_layers=7"),
    (0, " short_conv_layers=7 short_conv_kernel_layers=0"),
])
def test_trace_shows_how_many_convolution_layers_ran_as_kernels(layers, tail):
    """`kubedl-tpu trace`'s DETAIL of a step of a model with convolution
    layers."""
    from kubedl_tpu.cli import _span_detail

    attrs = {"step": 7, "short_conv_layers": 7.0,
             "short_conv_kernel_layers": float(layers)}
    assert _span_detail(attrs) == "step=7" + tail
