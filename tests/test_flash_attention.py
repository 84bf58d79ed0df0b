"""Flash attention kernel vs plain-XLA reference: forward and gradients,
causal/full, GQA, ragged (padded) lengths. Runs in pallas interpret mode on
CPU (conftest forces JAX_PLATFORMS=cpu); the same code compiles for TPU."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubedl_tpu.ops import flash_attention as fa
from kubedl_tpu.ops.flash_attention import attention_reference, flash_attention


def rand_qkv(b=2, hq=4, hkv=4, s=256, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = rand_qkv()
    out = flash_attention(q, k, v, causal=causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)


def test_gqa_forward():
    q, k, v = rand_qkv(hq=8, hkv=2)
    out = flash_attention(q, k, v, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)


def test_ragged_length_padding():
    # seq=200 is not a multiple of the 128 block: exercises the padded tail
    q, k, v = rand_qkv(s=200)
    out = flash_attention(q, k, v, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_reference(causal):
    q, k, v = rand_qkv(b=1, hq=2, hkv=2, s=256, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-3, rtol=5e-3, err_msg=f"d{name}")


def test_gradients_ragged():
    q, k, v = rand_qkv(b=1, hq=2, hkv=2, s=160, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a in g:
        assert np.all(np.isfinite(np.asarray(a)))

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(a, b, atol=5e-3, rtol=5e-3)


@pytest.mark.parametrize("s", [256, 1024])
def test_head_dim_64_pads_onto_fused_kernel(s):
    """ViT-B/16-class head_dim (64) lane-aligns by zero padding: fwd and
    grads must match the reference exactly (pad columns contribute zero).
    s=1024 clears FLASH_MIN_SEQ so the dispatch that ships on TPU is the
    one under test; s=256 covers the short-seq policy path."""
    b, h, d = 2, 3, 64
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)

    out = flash_attention(q, k, v, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)

    g = jax.grad(lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True)),
                 argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(attention_reference(q, k, v, causal=True)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_streamed_fwd_matches_default_kernel(monkeypatch):
    """The K-streaming 3D-grid forward (seq > STREAM_MIN_SEQ) must agree
    with the default full-K/V kernel and the reference — forced here by
    dropping the threshold so interpret mode exercises the streamed path."""
    from kubedl_tpu.ops import flash_attention as fa

    b, h, s, d = 1, 2, 512, 128
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)

    baseline = flash_attention(q, k, v, causal=True)
    monkeypatch.setattr(fa, "STREAM_MIN_SEQ", 128)
    streamed = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(streamed), np.asarray(baseline), rtol=1e-5, atol=1e-5
    )
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(streamed), np.asarray(ref), rtol=2e-3, atol=2e-3
    )
    # ragged tail (seq not a block multiple) through the streamed masks
    q2, k2, v2 = q[:, :, :333], k[:, :, :333], v[:, :, :333]
    streamed2 = flash_attention(q2, k2, v2, causal=True)
    ref2 = attention_reference(q2, k2, v2, causal=True)
    np.testing.assert_allclose(
        np.asarray(streamed2), np.asarray(ref2), rtol=2e-3, atol=2e-3
    )

    # gradients consume the STREAMED kernel's lse — an lse bug would pass
    # the forward-only checks above
    g = jax.grad(lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True)),
                 argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(attention_reference(q, k, v, causal=True)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-3, atol=2e-3)

    # mismatched block sizes pad q and k/v to one COMMON length
    mixed = flash_attention(q, k, v, causal=True, block_q=256, block_k=384)
    np.testing.assert_allclose(
        np.asarray(mixed), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_snap_block_bounds_padded_length():
    """Exotic block sizes must not let lcm padding exceed the
    whole-sequence kernels' VMEM budget (STREAM_MIN_SEQ)."""
    import math

    from kubedl_tpu.ops.flash_attention import STREAM_MIN_SEQ, _snap_block

    for bq, bk in [(640, 384), (128, 128), (512, 256), (896, 768)]:
        sq, sk = _snap_block(bq), _snap_block(bk)
        assert sq <= bq and sk <= bk
        assert sq >= 128 and sk >= 128
        assert STREAM_MIN_SEQ % math.lcm(sq, sk) == 0


def test_exotic_blocks_numerics_match_reference(monkeypatch):
    """End-to-end through flash_attention with a shrunken VMEM budget so
    the snap path actually fires: sq=769 keeps blocks 640/384 past the
    cap clamp (cap=768), their lcm pads to 1920 > budget 1024, snap
    rewrites them to 512/256 and the padded length lands exactly at the
    budget. Numerics must still match the reference."""
    import jax
    import jax.numpy as jnp

    from kubedl_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "STREAM_MIN_SEQ", 1024)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    shape = (1, 1, 769, 64)
    q = jax.random.normal(ks[0], shape, jnp.float32)
    k = jax.random.normal(ks[1], shape, jnp.float32)
    v = jax.random.normal(ks[2], shape, jnp.float32)
    o = fa.flash_attention(q, k, v, causal=True, block_q=640, block_k=384, min_seq=0)
    r = fa.attention_reference(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(o - r))) < 2e-5


def test_in_budget_exotic_blocks_preserved(monkeypatch):
    """Caller block choices whose lcm padding fits the budget are NOT
    rewritten (a silent substitution would invalidate block sweeps)."""
    from kubedl_tpu.ops import flash_attention as fa

    seen = []
    real_fwd = fa._fwd

    def spy(q, k, v, sm_scale, causal, window, block_q, block_k, true_len,
            softcap=None):
        seen.append((block_q, block_k))
        return real_fwd(q, k, v, sm_scale, causal, window, block_q, block_k,
                        true_len, softcap=softcap)

    monkeypatch.setattr(fa, "_fwd", spy)
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    shape = (1, 1, 2048, 64)
    q = jax.random.normal(ks[0], shape, jnp.float32)
    k = jax.random.normal(ks[1], shape, jnp.float32)
    v = jax.random.normal(ks[2], shape, jnp.float32)
    fa.flash_attention(q, k, v, causal=True, block_q=640, block_k=384, min_seq=0)
    # lcm(640,384)=1920, target 3840 <= 8192: requested blocks survive
    assert seen == [(640, 384)]


# ---------------------------------------------------------------------------
# Sliding window (Mistral-style): query i attends keys in (i-window, i]
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [1, 7, 64, 300])
def test_window_fwd_matches_masked_reference(window):
    b, h, t, d = 2, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, t, d))
    k = jax.random.normal(ks[1], (b, h, t, d))
    v = jax.random.normal(ks[2], (b, h, t, d))
    out = flash_attention(q, k, v, causal=True, window=window)
    ref = attention_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)
    # and the window actually changed the result vs full causal
    if window < t:
        full = attention_reference(q, k, v, causal=True)
        assert float(jnp.max(jnp.abs(ref - full))) > 1e-3


def test_window_gradients_match_reference():
    b, h, t, d = 1, 2, 192, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, h, t, d))
    k = jax.random.normal(ks[1], (b, h, t, d))
    v = jax.random.normal(ks[2], (b, h, t, d))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=50) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True, window=50) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-3, rtol=5e-3, err_msg=f"d{name}")


def test_window_requires_causal():
    x = jnp.zeros((1, 1, 8, 16))
    with pytest.raises(ValueError):
        flash_attention(x, x, x, causal=False, window=4)
    with pytest.raises(ValueError):
        attention_reference(x, x, x, causal=False, window=4)


def test_window_streamed_kernel_matches_reference(monkeypatch):
    """The K-streaming kernel's window block-skip only runs past
    STREAM_MIN_SEQ; drop the threshold so its boundary math is exercised
    at test sizes."""
    from kubedl_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "STREAM_MIN_SEQ", 128)
    b, h, t, d = 1, 2, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, h, t, d))
    k = jax.random.normal(ks[1], (b, h, t, d))
    v = jax.random.normal(ks[2], (b, h, t, d))
    for window in (1, 100, 128, 129, 400):
        out = fa.flash_attention(q, k, v, causal=True, window=window,
                                 block_q=128, block_k=128)
        ref = fa.attention_reference(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=2e-3,
                                   err_msg=f"window={window}")


def test_config_rejects_zero_window():
    from kubedl_tpu.models.llama import LlamaConfig

    with pytest.raises(ValueError):
        LlamaConfig.tiny(sliding_window=0)


@pytest.mark.slow
def test_softcap_forward_and_gradients_match_reference():
    """Gemma-2 logit softcapping inside the kernel: forward and all
    three gradients match the reference exactly, with and without a
    sliding window, and the cap genuinely changes the output."""
    q, k, v = rand_qkv(b=1, hq=2, hkv=2, s=256, d=64)
    for window in (None, 64):
        out = flash_attention(q, k, v, causal=True, softcap=20.0,
                              window=window)
        ref = attention_reference(q, k, v, causal=True, softcap=20.0,
                                  window=window)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

        def loss_f(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, softcap=20.0, window=window) ** 2)

        def loss_r(q, k, v):
            return jnp.sum(attention_reference(
                q, k, v, causal=True, softcap=20.0, window=window) ** 2)

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(a, b, atol=5e-3, rtol=5e-3,
                                       err_msg=f"d{name} window={window}")
    uncapped = flash_attention(q, k, v, causal=True)
    capped = flash_attention(q, k, v, causal=True, softcap=1.0)
    assert float(jnp.abs(uncapped - capped).max()) > 1e-3

    with pytest.raises(ValueError, match="softcap"):
        flash_attention(q, k, v, causal=True, softcap=0.0)


def test_softcap_streamed_path():
    """The streamed (long-prefill) forward applies the cap too."""
    import kubedl_tpu.ops.flash_attention as fa

    q, k, v = rand_qkv(b=1, hq=1, hkv=1, s=512, d=64)
    orig = fa.STREAM_MIN_SEQ
    fa.STREAM_MIN_SEQ = 256  # force the streamed kernel at s=512
    try:
        out = flash_attention(q, k, v, causal=True, softcap=15.0)
    finally:
        fa.STREAM_MIN_SEQ = orig
    ref = attention_reference(q, k, v, causal=True, softcap=15.0)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# Blocks by position: dead ones skipped in every kernel, interior ones
# mask-free in dq (ops/flash_attention.py:_segments)
# ---------------------------------------------------------------------------


def _loss(attention, **kw):
    return lambda q, k, v: jnp.sum(attention(q, k, v, **kw) ** 2)


# (seq, window, block_q, block_k, causal, softcap)
BLOCK_KINDS = {
    "window_of_whole_blocks": (512, 256, 128, 128, True, None),
    "window_inside_a_block": (512, 300, 128, 128, True, None),
    "window_under_one_block": (512, 50, 128, 128, True, None),
    "wide_q_blocks": (512, 256, 256, 128, True, None),
    "wide_k_blocks": (512, 256, 128, 256, True, None),
    "ragged_window": (450, 192, 128, 128, True, None),
    "ragged_wide_k_blocks": (600, None, 128, 256, True, None),
    "full_causal": (512, None, 128, 128, True, None),
    "not_causal": (512, None, 128, 128, False, None),
    # the tail's edge lies in the last column of blocks, interior otherwise
    "not_causal_ragged": (450, None, 128, 128, False, None),
    "softcap": (512, 300, 128, 128, True, 20.0),
}


@pytest.mark.parametrize("shape", BLOCK_KINDS.values(), ids=BLOCK_KINDS.keys())
def test_every_kind_of_block_matches_reference(shape):
    seq, window, block_q, block_k, causal, softcap = shape
    # the shape holds what its name says: edge blocks, and interior ones
    # wherever the window is wide enough to hold a whole block
    for side in fa.SIDES:
        visited, interior = fa.block_plan(
            seq, window, block_q, block_k, causal, side)
        assert interior <= visited, (side, visited, interior)
        assert (interior > 0) == (
            window is None or window >= block_q + block_k - 1), side
    q, k, v = rand_qkv(b=1, hq=2, hkv=2, s=seq, d=64)
    kw = dict(causal=causal, window=window, softcap=softcap)
    blocks = dict(block_q=block_q, block_k=block_k)
    out = flash_attention(q, k, v, **kw, **blocks)
    ref = attention_reference(q, k, v, **kw)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)
    gf = jax.grad(_loss(flash_attention, **kw, **blocks), (0, 1, 2))(q, k, v)
    gr = jax.grad(_loss(attention_reference, **kw), (0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-3, rtol=5e-3,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("shape", [
    (512, 300, 128, 128, True, None),
    (450, None, 128, 256, True, None),
    (450, None, 256, 128, False, None),
    (512, 300, 128, 128, True, 20.0),
], ids=["window", "ragged_wide_k_blocks", "not_causal_ragged", "softcap"])
def test_the_mask_free_body_changes_no_bit(shape, monkeypatch):
    """Every block under the masked body (an empty interior range) against
    the kernels as they are: dq's `where(mask, p, 0)` with the mask all
    true is `p`, so `out` and the three gradients are equal bit for bit."""
    seq, window, block_q, block_k, causal, softcap = shape
    q, k, v = rand_qkv(b=1, hq=2, hkv=2, s=seq, d=64)
    kw = dict(causal=causal, window=window, softcap=softcap,
              block_q=block_q, block_k=block_k)

    def run():
        out = flash_attention(q, k, v, **kw)
        return (out,) + jax.grad(_loss(flash_attention, **kw), (0, 1, 2))(q, k, v)

    real = run()
    segments = fa._segments
    interior = []

    def all_masked(*a, **kwargs):
        start, lo, hi, stop = segments(*a, **kwargs)
        interior.append((lo, hi))
        return start, stop, stop, stop

    monkeypatch.setattr(fa, "_segments", all_masked)
    masked = run()
    assert len(interior) >= 3  # forward, dq and dk/dv all asked
    for a, b, name in zip(real, masked, ("out", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


def _live_pairs(seq, window, block_q, block_k, causal):
    """The mask itself, pair by pair over the padded square, cut into
    blocks: [q blocks, k blocks, block_q, block_k]."""
    import math

    lcm = math.lcm(block_q, block_k)
    padded = -(-seq // lcm) * lcm
    q = np.arange(padded)[:, None]
    k = np.arange(padded)[None, :]
    live = (q < seq) & (k < seq)
    if causal:
        live &= k <= q
    if window is not None:
        live &= k > q - window
    return live.reshape(
        padded // block_q, block_q, padded // block_k, block_k
    ).transpose(0, 2, 1, 3)


# (seq, window, block_q, block_k, causal): blocks of a few pairs, so that
# every edge falls on, beside and inside a block boundary
PLANS = [
    (64, None, 8, 8, True), (64, 16, 8, 8, True), (64, 13, 8, 8, True),
    (64, 3, 8, 8, True), (64, 1, 8, 8, True), (61, 24, 8, 8, True),
    (64, 24, 16, 8, True), (64, 24, 8, 16, True), (50, 20, 4, 16, True),
    (50, 7, 16, 4, True), (37, None, 4, 8, True), (64, 200, 8, 8, True),
    (64, None, 8, 8, False), (53, None, 8, 16, False), (53, None, 16, 8, False),
]


@pytest.mark.parametrize("side", ["fwd_dq", "dkv"])
@pytest.mark.parametrize("seq,window,block_q,block_k,causal", PLANS)
def test_block_plan_against_brute_force(seq, window, block_q, block_k, causal,
                                        side):
    live = _live_pairs(seq, window, block_q, block_k, causal)
    if side == "dkv":  # outer K blocks, inner Q blocks
        live = live.transpose(1, 0, 2, 3)
    n_outer, n_inner = live.shape[:2]
    start, lo, hi, stop = fa._segments(
        np.arange(n_outer), side, seq_len=seq, window=window,
        block_q=block_q, block_k=block_k, causal=causal, xp=np)
    assert np.all((0 <= start) & (start <= lo) & (lo <= hi) & (hi <= stop)
                  & (stop <= n_inner))
    inner = np.arange(n_inner)[None, :]
    visited = (start[:, None] <= inner) & (inner < stop[:, None])
    interior = (lo[:, None] <= inner) & (inner < hi[:, None])
    # visited = live; interior = every pair live
    np.testing.assert_array_equal(visited, live.any(axis=(2, 3)))
    np.testing.assert_array_equal(interior, live.all(axis=(2, 3)))
    assert fa.block_plan(seq, window, block_q, block_k, causal, side) == (
        int(live.any(axis=(2, 3)).sum()), int(live.all(axis=(2, 3)).sum()))


@pytest.mark.parametrize("side", ["fwd_dq", "dkv"])
@pytest.mark.parametrize("window,plan", [(4096, (108, 84)), (None, (136, 120))])
def test_block_plan_of_the_benchmark_shapes(window, plan, side):
    """Sequence 8,192 in blocks of 512: under the old bound the forward
    and dq visited 123 (window 4,096) and 151 (full causal) pairs a head."""
    assert fa.block_plan(8192, window, 512, 512, True, side) == plan


def test_block_plan_is_counted_on_the_host(monkeypatch):
    """numpy alone: a tool on a machine with no chip starts no backend."""
    monkeypatch.setattr(fa, "jnp", None)
    assert fa.block_plan(8192, 4096, 512, 512, True, "fwd_dq") == (108, 84)


def test_block_plan_refuses_an_unknown_side():
    with pytest.raises(ValueError, match="side"):
        fa.block_plan(64, None, 8, 8, True, "fwd")


# ---------------------------------------------------------------------------
# A block's mask: the column index less one scalar of the block's origin
# against the row index (ops/flash_attention.py:_block_mask)
# ---------------------------------------------------------------------------


MASKED_SHAPES = {
    **{"-".join(map(str, plan)): plan for plan in PLANS},
    **{name: shape[:5] for name, shape in BLOCK_KINDS.items()},
}


@pytest.mark.parametrize("seq,window,block_q,block_k,causal",
                         MASKED_SHAPES.values(), ids=MASKED_SHAPES.keys())
def test_block_mask_against_brute_force(seq, window, block_q, block_k, causal):
    """Every block a kernel visits, pair by pair: the helper's mask is
    `k < n and q < n and k <= q and k > q - window`; where it gives no
    mask at all, every pair of the block is live."""
    live = _live_pairs(seq, window, block_q, block_k, causal)
    visited = np.argwhere(live.any(axis=(2, 3)))
    assert len(visited) == fa.block_plan(
        seq, window, block_q, block_k, causal, "fwd_dq")[0]
    for qb, kb in visited:
        mask = fa._block_mask(
            int(qb) * block_q, int(kb) * block_k, block_q=block_q,
            block_k=block_k, seq_len=seq, causal=causal, window=window)
        if mask is None:
            assert live[qb, kb].all(), (qb, kb)
            continue
        np.testing.assert_array_equal(
            np.broadcast_to(np.asarray(mask), (block_q, block_k)),
            live[qb, kb], err_msg=f"block ({qb}, {kb})")


def test_block_mask_compares_the_length_only_in_a_ragged_tail():
    """A sequence of whole blocks costs a block no compare with the
    length, and full attention over one no mask at all; a ragged one
    pays a row's and a column's compare, not a block's. No operand of
    any compare is a `[block_q, block_k]` integer."""
    blocks = dict(block_q=8, block_k=8)

    def compares(**kw):
        jaxpr = jax.make_jaxpr(
            lambda q0, k0: fa._block_mask(q0, k0, **blocks, **kw))(0, 0)
        for eqn in jaxpr.eqns:
            for var in eqn.invars:
                assert (getattr(var.aval, "shape", ()) != (8, 8)
                        or var.aval.dtype == bool), eqn
        return sorted(
            (eqn.primitive.name, eqn.outvars[0].aval.shape)
            for eqn in jaxpr.eqns if eqn.primitive.name in ("lt", "le", "gt"))

    assert fa._block_mask(0, 0, **blocks, seq_len=64, causal=False,
                          window=None) is None
    assert compares(seq_len=64, causal=True, window=None) == [("le", (8, 8))]
    assert compares(seq_len=64, causal=True, window=16) == [
        ("gt", (8, 8)), ("le", (8, 8))]
    assert compares(seq_len=61, causal=True, window=None) == [
        ("le", (8, 8)), ("lt", (1, 8)), ("lt", (8, 1))]


def _pair_mask(q0, k0, *, block_q, block_k, seq_len, causal, window):
    """The mask as the kernels made it until PR 31: two `[block_q,
    block_k]` position tensors and five compares."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = (k_pos < seq_len) & (q_pos < seq_len)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    return mask


@pytest.mark.parametrize("streamed", [False, True], ids=["whole_kv", "streamed"])
@pytest.mark.parametrize("shape", [
    (512, 300, 128, 128, True, None),
    (512, 50, 128, 128, True, None),
    (512, 700, 128, 128, True, None),
    (450, 192, 128, 256, True, None),
    (450, None, 256, 128, True, None),
    (450, None, 256, 128, False, None),
    (512, None, 128, 128, False, None),
    (512, 300, 128, 128, True, 20.0),
], ids=["window", "window_under_a_block", "window_over_the_sequence",
        "ragged_window_wide_k_blocks", "ragged_wide_q_blocks",
        "not_causal_ragged", "not_causal", "softcap"])
def test_the_block_mask_changes_no_bit(shape, streamed, monkeypatch):
    """The same booleans, so the same bits: `out`, `lse` and the three
    gradients against the kernels run with the parent's mask in the
    helper's place, through the forward that holds K and V whole and
    through the streamed one."""
    seq, window, block_q, block_k, causal, softcap = shape
    q, k, v = rand_qkv(b=1, hq=2, hkv=2, s=seq, d=64)
    kw = dict(causal=causal, window=window, softcap=softcap,
              block_q=block_q, block_k=block_k)
    if streamed:
        monkeypatch.setattr(fa, "STREAM_MIN_SEQ", 128)
    forward = "_fwd_streamed" if streamed else "_fwd"

    def run():
        lse = []
        real = getattr(fa, forward)

        def spy(*a, **kwargs):
            got = real(*a, **kwargs)
            lse.append(got[1])
            return got

        with monkeypatch.context() as m:
            m.setattr(fa, forward, spy)
            out = flash_attention(q, k, v, **kw)
        grads = jax.grad(_loss(flash_attention, **kw), (0, 1, 2))(q, k, v)
        assert len(lse) == 1
        return (out, lse[0]) + grads

    new = run()
    asked = []

    def pair_mask(*a, **kwargs):
        asked.append(a)
        return _pair_mask(*a, **kwargs)

    monkeypatch.setattr(fa, "_block_mask", pair_mask)
    old = run()
    assert len(asked) >= 3  # forward, dq and dk/dv all asked
    for a, b, name in zip(new, old, ("out", "lse", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)



# -- a value head size of its own (latent attention: keys 192, values 128) ------------


def rand_unlike(d_qk, d_v, b=1, h=2, s=256, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, h, s, d_qk), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d_qk), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d_v), jnp.float32)
    do = jax.random.normal(ks[3], (b, h, s, d_v), jnp.float32)
    return q, k, v, do


UNLIKE = [(192, 128), (24, 16)]


@pytest.mark.parametrize("d_qk,d_v", UNLIKE)
def test_value_width_unlike_the_keys_forward(d_qk, d_v):
    q, k, v, _ = rand_unlike(d_qk, d_v)
    scale = 0.14468 if d_qk == 192 else None
    out = flash_attention(q, k, v, causal=True, sm_scale=scale)
    assert out.shape == v.shape
    ref = attention_reference(q, k, v, causal=True, sm_scale=scale)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("wrt", [0, 1, 2], ids=["dq", "dk", "dv"])
@pytest.mark.parametrize("d_qk,d_v", UNLIKE)
def test_value_width_unlike_the_keys_gradients(d_qk, d_v, wrt):
    q, k, v, do = rand_unlike(d_qk, d_v)
    loss = lambda fn: lambda *a: jnp.sum(fn(*a, causal=True) * do)
    got = jax.grad(loss(flash_attention), argnums=wrt)(q, k, v)
    want = jax.grad(loss(attention_reference), argnums=wrt)(q, k, v)
    assert got.shape == (q, k, v)[wrt].shape
    np.testing.assert_allclose(got, want, atol=5e-3, rtol=5e-3)


def test_each_width_is_padded_to_its_own_lanes(monkeypatch):
    """q, k, dq, dk ride at 256 lanes and v, o, do, dv at 128; the
    residuals keep the true 192 and 128."""
    q, k, v, do = rand_unlike(192, 128, s=128)
    seen = {}
    real_fwd, real_bwd = fa._fwd, fa._bwd

    def fwd(q, k, v, *a, **kw):
        seen["fwd"] = (q.shape[-1], k.shape[-1], v.shape[-1])
        out = real_fwd(q, k, v, *a, **kw)
        seen["out"] = out[0].shape[-1]
        return out

    def bwd(*a, **kw):
        res, dout = a[6], a[7]
        seen["bwd"] = tuple(x.shape[-1] for x in res[:4]) + (dout.shape[-1],)
        grads = real_bwd(*a, **kw)
        seen["grads"] = tuple(g.shape[-1] for g in grads)
        return grads

    monkeypatch.setattr(fa, "_fwd", fwd)
    monkeypatch.setattr(fa, "_bwd", bwd)
    jax.grad(lambda *a: jnp.sum(flash_attention(*a, causal=True) * do), (0, 1, 2))(q, k, v)
    assert seen == {"fwd": (256, 256, 128), "out": 128,
                    "bwd": (256, 256, 128, 128, 128), "grads": (256, 256, 128)}


@pytest.mark.parametrize("d", [64, 128])
def test_equal_widths_are_the_one_width_kernels_bit_for_bit(d):
    """Where v is as wide as q and k the kernels are the ones they were:
    no compiler parameter, the one padded width for every operand, and,
    bit for bit, what the two-width path gives for the same v handed over
    with zero columns up to the next lane tile's width cut off again."""
    q, k, v, do = rand_unlike(d, d, s=256)
    loss = lambda *a: jnp.sum(flash_attention(*a, causal=True) * do)
    out = flash_attention(q, k, v, causal=True)
    grads = jax.grad(loss, (0, 1, 2))(q, k, v)
    assert fa._whole_seq_params(8192, 128, 128) is None
    assert fa._whole_seq_params(8192, 256, 128).vmem_limit_bytes == 20 * 2**20
    # the same numbers through the two-width path: q and k carry 128 zero
    # columns more (another lane tile), which add nothing to any score;
    # the scale is said outright, since it follows q's width
    wide = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, 128)))
    scale = 1.0 / d ** 0.5
    out2 = flash_attention(wide(q), wide(k), v, causal=True, sm_scale=scale)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    g2 = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        wide(q), wide(k), v, causal=True, sm_scale=scale) * do), (0, 1, 2))(q, k, v)
    for a, b, name in zip(grads, g2, ("dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


def test_unlike_q_and_k_widths_are_refused():
    q, k, v, _ = rand_unlike(64, 64)
    with pytest.raises(ValueError, match="q heads are 64 wide and k heads 32"):
        flash_attention(q, k[..., :32], v)
