"""The state-space layer's convolution as Pallas kernels
(`kubedl_tpu/ops/causal_conv.py`) in interpret mode on the CPU, at small
shapes of whole tiles: x, B and C and all three gradients against the XLA
form (`silu(causal_taps(xBC, w) + b)` and its autodiff); that no token
reads a later one; which form `split_conv` takes, by shape, backend and
mesh; `ssm_conv_kernel_layers`; remat; two devices. What Mosaic refuses
is `tests/test_tpu_compile.py`'s to see.

On the CPU `split_conv` takes the XLA form whatever the shape
(`conv_takes_kernel` asks `ops.interpret`): the `kernel_form` fixture
steers that one question in the test, and the kernels themselves still
run interpreted. The scan stays XLA's here (`tests/test_ssm_scan_kernel.py`
has its kernels), and a program's token block is cut to 128 tokens in
passes of 32, so that a sequence of 384 crosses two block edges."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights_ssm
from benchmarks.runners.train_ssm import ssm_config
from kubedl_tpu.models import llama, ssm
from kubedl_tpu.models.short_conv import causal_taps
from kubedl_tpu.ops import causal_conv

KERNELS = ("ssm_conv_fwd", "ssm_conv_bwd")
D_INNER, STATE, HEADS = 256, 128, 8
D_CONV = D_INNER + 2 * STATE
OUTPUTS = ("z", "x", "B", "C", "dt")


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(causal_conv, "TOKEN_BLOCK", 128)
    monkeypatch.setattr(causal_conv, "ROWS", 32)


@pytest.fixture
def kernel_form(monkeypatch):
    """`split_conv` chooses as it would on a TPU; the scan stays XLA's."""
    monkeypatch.setattr(ssm, "interpret", lambda: False)
    monkeypatch.setattr(ssm, "scan_takes_kernel", lambda *a, **kw: False)


def xla_form(h, w, bias):
    """What `ssm_mixer` held before the kernels, written out again."""
    z, xbc, dt = jnp.split(h, [D_INNER, D_INNER + D_CONV], axis=-1)
    xbc = jax.nn.silu(causal_taps(xbc, w).astype(jnp.float32) + bias).astype(h.dtype)
    return (z, *jnp.split(xbc, [D_INNER, D_INNER + STATE], axis=-1), dt)


def kernels(h, w, bias):
    return ssm.split_conv(h, w, bias, D_INNER, STATE)[0]


def conv_inputs(batch, seq, taps, dtype=jnp.float32, seed=0):
    """The in projection's output, and the benchmark's ranges of taps and
    bias (uniform(-1/2, 1/2), float32)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(ks[0], (batch, seq, 2 * D_INNER + 2 * STATE + HEADS),
                          jnp.float32).astype(dtype)
    w = jax.random.uniform(ks[1], (D_CONV, taps), jnp.float32, -0.5, 0.5)
    bias = jax.random.uniform(ks[2], (D_CONV,), jnp.float32, -0.5, 0.5)
    return h, w, bias


def jaxpr_of(fn, *args):
    return str(jax.make_jaxpr(fn)(*args))


def gap(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want)) / float(jnp.linalg.norm(want))


# K taps; one and two sequences; a sequence of one token block (nothing
# before it) and of three (the K - 1 tokens before a block lie in the last)
SHAPES = [
    pytest.param(3, 1, 128, id="k3_one_sequence_one_block"),
    pytest.param(3, 2, 384, id="k3_two_sequences_three_blocks"),
    pytest.param(4, 1, 384, id="k4_one_sequence_three_blocks"),
    pytest.param(4, 2, 128, id="k4_two_sequences_one_block"),
    pytest.param(8, 1, 256, id="k8_the_most_taps"),
]


@pytest.mark.parametrize("taps,batch,seq", SHAPES)
def test_forward_is_the_xla_forms(kernel_form, taps, batch, seq):
    args = conv_inputs(batch, seq, taps)
    assert ssm.conv_takes_kernel(seq, D_INNER, STATE, taps)
    assert "name=ssm_conv_fwd" in jaxpr_of(kernels, *args)
    got, want = jax.jit(kernels)(*args), jax.jit(xla_form)(*args)
    for name, g, w in zip(OUTPUTS, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        # a multiply-add the CPU's compiler contracts in one form alone
        np.testing.assert_allclose(g, w, rtol=0, atol=2e-6, err_msg=name)
    np.testing.assert_array_equal(got[0], want[0])  # z and dt pass through
    np.testing.assert_array_equal(got[4], want[4])


@pytest.mark.parametrize("dtype,limit", [
    pytest.param(jnp.float32, 2e-6, id="float32"),
    # both forms round xBC, the taps' sum and the outputs to bf16 alike; the
    # kernel keeps ds float32 where autodiff rounds it on its way to the taps
    pytest.param(jnp.bfloat16, 6e-3, id="bfloat16"),
])
@pytest.mark.parametrize("taps,batch,seq", SHAPES[1:3])
def test_gradients_are_autodiffs_of_the_xla_form(kernel_form, taps, batch, seq,
                                                 dtype, limit):
    args = conv_inputs(batch, seq, taps, dtype, seed=1)
    shapes = jax.eval_shape(xla_form, *args)
    cotangents = tuple(
        jax.random.normal(k, s.shape, jnp.float32).astype(dtype) for k, s in zip(
            jax.random.split(jax.random.PRNGKey(2), len(shapes)), shapes))
    grads = lambda fn: jax.jit(lambda *a: jax.vjp(fn, *a)[1](cotangents))(*args)
    assert all(f"name={k}" in jaxpr_of(
        lambda *a: jax.vjp(kernels, *a)[1](cotangents), *args) for k in KERNELS)
    for name, g, w in zip(("dh", "dw", "db"), grads(kernels), grads(xla_form)):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert gap(g, w) < limit, (name, gap(g, w))
    if dtype == jnp.bfloat16:  # and nearer the float32 gradient than autodiff's
        exact = jax.jit(lambda *a: jax.vjp(xla_form, *a)[1](tuple(
            c.astype(jnp.float32) for c in cotangents)))(
                *(a.astype(jnp.float32) for a in args))
        for name, g, w, e in zip(("dh", "dw", "db"), grads(kernels),
                                 grads(xla_form), exact):
            assert gap(g, e) < gap(w, e) + 1e-3, name


def test_a_tokens_change_reaches_no_output_before_it(kernel_form):
    """Token 130 is the third of the second block: the outputs before it
    hold, the K - 1 after it move (across no edge here; 127 moves 128-130
    across one)."""
    h, w, bias = conv_inputs(2, 384, 4, seed=3)
    base = jax.jit(kernels)(h, w, bias)
    for token in (130, 127):
        moved = jax.jit(kernels)(h.at[1, token].add(1.0), w, bias)
        for name, a, b in zip(OUTPUTS[1:4], base[1:4], moved[1:4]):
            np.testing.assert_array_equal(a[0], b[0], err_msg=name)
            np.testing.assert_array_equal(a[1, :token], b[1, :token], err_msg=name)
            np.testing.assert_array_equal(a[1, token + 4:], b[1, token + 4:])
            assert float(jnp.min(jnp.max(jnp.abs(
                a[1, token:token + 4] - b[1, token:token + 4]), axis=-1))) > 0, name


# -- which form runs -------------------------------------------------------------


@pytest.mark.parametrize("seq,d_inner,state,taps,takes", [
    (8192, 4096, 128, 4, True),    # the benchmark's cell
    (128, 128, 128, 2, True),      # the least the kernels take
    (8192, 4096, 128, 9, False),   # taps that reach back more than a tile
    (8192, 4096, 128, 1, False),   # no tap before the token: nothing to carry
    (8192, 4000, 128, 4, False),   # xBC starts inside a 128-lane block
    (8192, 4096, 64, 4, False),    # B and C of half a lane block each
    (300, 256, 128, 4, False),     # a sequence of 2.3 token blocks
    (27, 64, 16, 4, False),        # tests/test_ssm_model.py's size
])
def test_the_form_is_chosen_from_shapes_backend_and_mesh(
        monkeypatch, seq, d_inner, state, taps, takes):
    assert causal_conv.supports(seq, d_inner, (d_inner, state, state), taps) == takes
    assert not ssm.conv_takes_kernel(seq, d_inner, state, taps)  # the CPU: XLA's
    monkeypatch.setattr(ssm, "interpret", lambda: False)
    assert ssm.conv_takes_kernel(seq, d_inner, state, taps) == takes
    mesh = lambda **axes: type("Mesh", (), {"shape": axes, "size": 4})()
    assert ssm.conv_takes_kernel(seq, d_inner, state, taps, mesh(fsdp=4)) == takes
    assert not ssm.conv_takes_kernel(seq, d_inner, state, taps, mesh(fsdp=2, tensor=2))


def test_an_unaligned_width_or_nine_taps_trace_the_xla_form(monkeypatch):
    """No pallas_call where the shapes are not whole tiles, whatever the
    backend: the same equations the CPU traces."""
    def grad_of(d_inner, taps):
        h = jnp.ones((1, 128, 2 * d_inner + 2 * STATE + HEADS), jnp.float32)
        w, bias = jnp.ones((d_inner + 2 * STATE, taps)), jnp.ones((d_inner + 2 * STATE,))
        fn = lambda *a: sum(jnp.sum(v) for v in ssm.split_conv(*a, d_inner, STATE)[0])
        return jaxpr_of(jax.grad(fn, argnums=(0, 1, 2)), h, w, bias)

    cases = [(200, 4), (D_INNER, 9), (D_INNER, 4)]
    on_cpu = [grad_of(*case) for case in cases]
    assert not any("pallas_call" in text for text in on_cpu)
    monkeypatch.setattr(ssm, "interpret", lambda: False)
    assert [grad_of(*case) for case in cases[:2]] == on_cpu[:2]
    text = grad_of(*cases[2])
    assert all(f"name={k}" in text for k in KERNELS)
    assert "pad" not in text  # no padded copy of xBC


# -- the model ---------------------------------------------------------------------

# hidden 64; layers mamba, attention, mamba; 8 state-space heads of 32
# (inner 256), state 128, 4 taps over 512 channels, chunk 128
CFG = {
    "hidden_size": 64, "intermediate_size": 128, "shared_intermediate_size": 128,
    "num_hidden_layers": 3, "layer_types": ["mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "attention_bias": False,
    "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8,
    "mamba_n_heads": HEADS, "mamba_d_head": 32, "mamba_d_state": STATE,
    "mamba_expand": 4, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "num_local_experts": 0, "hidden_act": "silu",
    "normalization_function": "rmsnorm", "position_embedding_type": "nope",
    "rope_theta": 10000, "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "vocab_size": 128, "initializer_range": 0.2, "torch_dtype": "float32",
    "remat": "full", "ce_chunks": 4,
}


def model(seq, **kw):
    config = dataclasses.replace(ssm_config(CFG, seq), use_flash=False, **kw)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        weights_ssm.make_fn(CFG)(jax.random.PRNGKey(4)))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, seq + 1), 0, 128)
    return config, params, tokens


@pytest.mark.parametrize("seq,took", [(256, True), (300, False)])
def test_kernel_layers_are_counted_where_the_kernels_ran(monkeypatch, seq, took):
    config, params, tokens = model(seq)
    stats_of = lambda: jax.jit(
        lambda p: llama.loss_and_stats(p, tokens, config))(params)
    loss, stats = stats_of()
    assert float(stats["ssm_layers"]) == 2
    assert float(stats["ssm_conv_kernel_layers"]) == 0  # the CPU: XLA's form
    monkeypatch.setattr(ssm, "interpret", lambda: False)
    monkeypatch.setattr(ssm, "scan_takes_kernel", lambda *a, **kw: False)
    loss_k, stats_k = stats_of()
    assert float(stats_k["ssm_conv_kernel_layers"]) == 2 * took
    assert float(stats_k["ssm_layers"]) == 2
    assert float(loss_k) == pytest.approx(float(loss), rel=1e-5)


def test_remat_on_and_off_agree_and_both_are_the_xla_forms_gradient(kernel_form):
    config, params, tokens = model(256)
    grad = lambda c: jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, tokens, c)))(params)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ssm, "conv_takes_kernel", lambda *a, **kw: False)
        loss_xla, g_xla = grad(config)
    text = jaxpr_of(jax.grad(lambda p: llama.loss_fn(p, tokens, config)), params)
    assert all(f"name={k}" in text for k in KERNELS)
    (on, g_on), (off, g_off) = grad(config), grad(dataclasses.replace(config, remat=False))
    assert float(on) == pytest.approx(float(off), rel=1e-6)
    assert float(on) == pytest.approx(float(loss_xla), rel=1e-5)
    flat = lambda g: dict(jax.tree_util.tree_flatten_with_path(g)[0])
    for path, want in flat(g_xla).items():
        assert float(jnp.linalg.norm(want)) > 0, jax.tree_util.keystr(path)
        for got in (flat(g_on)[path], flat(g_off)[path]):
            assert gap(got, want) < 1e-4, (jax.tree_util.keystr(path), gap(got, want))


def test_two_devices_under_fsdp_ride_a_shard_map_and_give_the_one_device_loss(
        kernel_form):
    from kubedl_tpu.parallel.mesh import ShardingRules, build_mesh

    config, params, tokens = model(256)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ssm, "conv_takes_kernel", lambda *a, **kw: False)
        one = jax.jit(jax.value_and_grad(
            lambda p: llama.loss_fn(p, tokens, config)))(params)
    mesh, rules = build_mesh({"fsdp": 2}, devices=jax.devices()[:2]), ShardingRules()
    fn = lambda p: llama.loss_fn(p, tokens, config, mesh=mesh, rules=rules)
    text = jaxpr_of(jax.grad(fn), params)
    assert "shard_map" in text and all(f"name={k}" in text for k in KERNELS)
    two = jax.jit(jax.value_and_grad(fn))(params)
    assert float(two[0]) == pytest.approx(float(one[0]), rel=1e-5)
    # the taps' and the bias's gradients are sums over both devices' sequences
    gaps = jax.tree_util.tree_map(gap, two[1], one[1])
    assert max(jax.tree_util.tree_leaves(gaps)) < 1e-4


@pytest.mark.parametrize("layers,tail", [
    (9, " kernel_chunks=576 conv_kernel_layers=9"),
    (0, " kernel_chunks=576 conv_kernel_layers=0"),
    (None, " kernel_chunks=576"),
])
def test_trace_shows_the_convolutions_kernels_after_what_it_showed_before(layers, tail):
    """`kubedl-tpu trace`'s DETAIL of a state-space model's step; a record
    written before the convolution's kernels has no such counter."""
    from kubedl_tpu.cli import _span_detail

    attrs = {"step": 7, "ssm_layers": 9.0, "ssm_chunks": 576.0,
             "ssm_state_carry": 0.0168, "ssm_dt_mean": 0.0317,
             "ssm_kernel_chunks": 576.0}
    if layers is not None:
        attrs["ssm_conv_kernel_layers"] = float(layers)
    assert _span_detail(attrs) == (
        "step=7 ssm_layers=9 chunks=576 carry=0.017 dt=0.0317" + tail)
