"""ops/row_gather.py in interpret mode: the kernel against XLA's gather
(`models/moe.py:_take`), which defines what it computes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubedl_tpu.models import moe
from kubedl_tpu.ops import gmm as G
from kubedl_tpu.ops import row_gather as RG
from kubedl_tpu.ops.row_gather import gather_rows


def f32(a):
    return np.asarray(a.astype(jnp.float32))


def case(seed, n, r, c, d, dtype, live_share=0.5):
    """x [n, d] and idx [c, r] of which about `live_share` name a row."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(ks[0], (n, d)).astype(dtype)
    idx = jax.random.randint(ks[1], (c, r), 0, int(n / live_share))
    return x, jnp.where(idx < n, idx, n).astype(jnp.int32)


# sum_j _take(x, idx[j]) in the order of j: float32 adds, rounded to x's
# dtype once; for float32 rows the plain ordered sum
ordered_sum = moe._take_sum


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("d", [128, 2048])
@pytest.mark.parametrize("live_share", [0.25, 1.0], ids=["quarter_live", "all_live"])
@pytest.mark.parametrize("c", [1, 4])
def test_kernel_equals_xlas_gather_element_for_element(c, live_share, d, dtype):
    # 300 source rows (not a multiple of the copied group of 8), 700
    # output rows (two whole tiles and a ragged third)
    x, idx = case(c, 300, 700, c, d, dtype, live_share)
    want = ordered_sum(x, idx)
    if c == 1:
        np.testing.assert_array_equal(f32(want), f32(moe._take(x, idx[0])))
    got = jax.jit(gather_rows)(x, idx)
    np.testing.assert_array_equal(f32(got), f32(want))


def test_kernel_with_nothing_to_copy_gives_zeros():
    x, idx = case(3, 64, 3 * RG.TILE_ROWS, 2, 128, jnp.bfloat16)
    got = gather_rows(x, jnp.full_like(idx, x.shape[0]))
    assert got.shape == (3 * RG.TILE_ROWS, 128) and not np.any(f32(got))


def test_index_beyond_the_sentinel_reads_zero_too():
    x, _ = case(4, 40, 16, 1, 128, jnp.float32)
    idx = jnp.array([[0, 39, 40, 41, 10_000, 7]], jnp.int32)
    got = gather_rows(x, idx)
    np.testing.assert_array_equal(f32(got), f32(moe._take(x, idx[0])))
    assert not np.any(f32(got)[2:5])


def test_next_live_walks_each_tile_on_its_own():
    idx = jnp.array([[9, 9, 3, 9, 9, 9, 9, 0, 9, 9, 9, 9]], jnp.int32)
    got = RG._next_live(idx, 9, 4)  # three tiles of 4; 9 is the sentinel
    np.testing.assert_array_equal(
        np.asarray(got), [[2, 2, 4, 4, 3, 3, 3, 4, 4, 4, 4, 4]])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("path", ["kernel", "all_rows"])
@pytest.mark.parametrize("c", [1, 4])
def test_a_move_without_a_bound_chooses_by_the_rows_its_indices_name(
        c, path, dtype, monkeypatch):
    """Both paths give `_take`'s rows; which one ran shows when the
    kernel is made to answer NaN."""
    x, idx = case(5, 300, 700, c, 128, dtype,
                  live_share=0.25 if path == "kernel" else 0.9)
    got = moe._move_rows(x, idx)
    np.testing.assert_array_equal(f32(got), f32(ordered_sum(x, idx)))
    monkeypatch.setattr(
        RG, "gather_rows",
        lambda x, idx: jnp.full((idx.shape[1], x.shape[1]), jnp.nan, x.dtype))
    # traced anew: `_move_rows`' own jit holds the trace with the real kernel
    unjitted = moe._move_rows.__wrapped__
    poisoned = f32(jax.jit(lambda x, idx: unjitted(x, idx))(x, idx))
    assert np.all(np.isnan(poisoned)) if path == "kernel" else np.all(
        np.isfinite(poisoned))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("live", [0, 300, 1536, 2048])
def test_a_bounded_move_gathers_the_live_rows_alone(live, dtype):
    # 2,048 rows: two steps of the loop's 1,024
    x, idx = case(6, 300, 2048, 1, 128, dtype, live_share=0.9)
    idx = jnp.where(jnp.arange(2048) < live, idx, 300)  # past it: none
    got = moe._move_rows(x, idx, jnp.int32(live))
    np.testing.assert_array_equal(f32(got), f32(moe._take(x, idx[0])))
    # an index past the bound is not looked at (the rows of its step are)
    astray = idx.at[0, 2047].set(0)
    got = moe._move_rows(x, astray, jnp.int32(min(live, 1024)))
    assert not np.any(f32(got)[1024:])


def test_rows_past_the_bound_are_never_read_by_the_dispatch():
    """The padded layout past the last live tile holds NaN after the
    permute; the grouped matmuls and the combine give the rows XLA's
    gathers give, and finite gradients."""
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    e, d, ff, s = 2, 128, 256, 512
    src = jax.random.normal(ks[0], (s, d))
    eid = jax.random.randint(ks[1], (4 * s,), 0, 4 * e)
    eid = jnp.where(eid < e, eid, e)
    params = {"w1": jax.random.normal(ks[2], (e, d, ff)) * 0.1,
              "w3": jax.random.normal(ks[3], (e, d, ff)) * 0.1,
              "w2": jax.random.normal(ks[4], (e, ff, d)) * 0.1}
    order, dest, pos_of_entry, tile_expert, m_pad = moe._dispatch_plan(eid, e)
    live = moe._live_rows(tile_expert, e, m_pad)
    assert int(live) < m_pad // 2

    def ffn(src, params, poison):
        x, entry_of_row = moe._permute(
            src, order, dest, pos_of_entry, m_pad, live)
        dead = (jnp.arange(m_pad) >= live)[:, None]
        x = jnp.where(dead & poison, jnp.nan, x)
        rows = moe._ffn_rows(x, tile_expert, params)
        rows = jnp.where(dead & poison, jnp.nan, rows)
        return moe._take_rows(rows, pos_of_entry, entry_of_row[None], None, live)

    y = ffn(src, params, True)
    assert np.all(np.isfinite(np.asarray(y)))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ffn(src, params, False)))
    loss = lambda src, params: jnp.sum(ffn(src, params, True) ** 2)
    for g in jax.tree_util.tree_leaves(jax.grad(loss, argnums=(0, 1))(src, params)):
        assert np.all(np.isfinite(np.asarray(g)))


def test_the_bound_is_the_grouped_matmuls_own():
    """`_gmm_ffn` bounds its row moves by the live tiles the `gmm*` grids
    visit, and the step's counter counts the same rows."""
    eid = jnp.where(jnp.arange(2048) % 4 == 0, jnp.arange(2048) % 3, 3)
    _, _, _, tile_expert, m_pad = moe._dispatch_plan(eid, 3)
    tile = m_pad // tile_expert.shape[0]
    live = moe._live_rows(tile_expert, 3, m_pad)
    assert int(live) == int(G._live_tiles(tile_expert, 3)) * tile
    stats = moe._dispatch_stats(eid, 3)
    assert float(stats["moe_rows_moved"]) == int(live) + 512
    assert float(stats["moe_rows_spanned"]) == m_pad + 2048
