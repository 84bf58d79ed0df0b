"""Row gather that copies only the rows its indices name — the dropless
dispatch's row moves out of the padded layout (models/moe.py
`_move_rows`), where most indices are the sentinel.

`gather_rows(x, idx)` computes `y[i] = sum_j x[idx[j, i]]`, an index of
`len(x)` or beyond adding a zero row. XLA's gather is paced by the rows
it is asked for, live or not, and a layer that holds 8 of its router's
32 experts asks for four rows to get one. Here a sentinel index costs
nothing but its output row's share of the tile's zero fill and
write-back: the kernel walks the live indices alone (`_next_live`, a
reverse running minimum XLA computes in microseconds) and issues one
copy for each.

Mosaic slices an HBM array along rows only at the tiling's 8: one row
cannot be copied alone. So a live index copies the aligned group of 8
rows that holds its row (one contiguous DMA) into a landing buffer in
VMEM, and the vector unit picks the row out of the group into a float32
tile: by a sublane index for 32-bit rows; for 16-bit rows, which sit two
to a 32-bit word (rows 2q and 2q+1 of a group in the low and high half
of word-row q), by a shift on the words, since a bfloat16's bits are a
float32's upper half. A batch of copies is in flight while the batch
before it is picked (two landing slots, a DMA semaphore each); Pallas
writes the tile back while the next one fills. The tile's indices reach
SMEM a tile at a time.

What that costs a live row on a v5e (PERF.md section 6, PR 29): 37 ns to
issue the copy, 44 ns to pick the row, little of it overlapped: 1.6
times XLA's gather, a row. So this is the faster of the two only where
at most about half of the indices are live; the caller chooses by what
it counts (`_move_rows`).

The `pallas_call` is named `moe_gather` (the device trace's event name)
and `gather_rows` opens a `row_gather` scope, so that the innermost name
on the stack is the kernel's own also under a bare `jax.grad`
(flash_attention.py). Interpret mode on the CPU backend only, like every
kernel in ops/.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubedl_tpu.ops import interpret

GROUP = 8  # rows of one HBM tile: the least a DMA can slice
TILE_ROWS = 256  # output rows a grid step
# bytes of the groups one batch of copies lands, two batches in flight
_LAND_BYTES = 1 << 20


def _next_live(idx, n: int, tile: int):
    """[c, r] (r whole tiles) -> for each position the first position
    after it, in its own tile, whose index names a row; `tile` where
    there is none. Positions count from the tile's start."""
    c, r = idx.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (c, r // tile, tile), 2)
    live = idx.reshape(c, r // tile, tile) < n
    at_or_after = jax.lax.cummin(
        jnp.where(live, pos, tile), axis=2, reverse=True)
    after = jnp.concatenate(
        [at_or_after[..., 1:], jnp.full((c, r // tile, 1), tile, jnp.int32)],
        axis=2)
    return after.reshape(c, r)


def _gather_kernel(idx_ref, nxt_ref, x_ref, out_ref, land, sems, acc,
                   dst, sub, *, n, c, tile, batch):
    """One output tile. idx_ref, nxt_ref [c, tile] SMEM; x_ref [n8, d] in
    HBM; out_ref [tile, d] VMEM; land [2, batch, GROUP, d] VMEM in x's
    dtype; acc [tile, d] float32 VMEM; dst, sub [2, batch] SMEM: the
    output row and the row in its group of each copy in flight."""
    packed = x_ref.dtype.itemsize == 2
    words = land.bitcast(jnp.uint32) if packed else land

    def copy(slot, q, row):
        group = pl.multiple_of((row // GROUP) * GROUP, GROUP)
        return pltpu.make_async_copy(
            x_ref.at[pl.ds(group, GROUP)], land.at[slot, q], sems.at[slot])

    def plane(j):
        def issue(slot, p):
            """Start up to a batch of copies from position p on; how many,
            and the position that follows them."""
            def more(state):
                return (state[0] < batch) & (state[1] < tile)

            def one(state):
                q, p = state
                row = idx_ref[j, p]
                copy(slot, q, row).start()
                dst[slot, q] = p
                sub[slot, q] = row % GROUP
                return q + 1, nxt_ref[j, p]
            return jax.lax.while_loop(more, one, (jnp.int32(0), p))

        def land_and_pick(slot, count):
            def wait(_, carry):
                # every copy moves one group: a wait takes one of them
                # off the slot's semaphore, whichever the descriptor names
                copy(slot, 0, 0).wait()
                return carry
            jax.lax.fori_loop(0, count, wait, 0)

            def pick(q, carry):
                i, s = dst[slot, q], sub[slot, q]
                if packed:
                    w = words[slot, q, pl.ds(s // 2, 1), :]
                    half = (16 * (s % 2)).astype(jnp.uint32)
                    v = jax.lax.bitcast_convert_type(
                        (w >> half) << 16, jnp.float32)
                else:
                    v = words[slot, q, pl.ds(s, 1), :]
                if j == 0:
                    acc[pl.ds(i, 1), :] = v
                else:
                    acc[pl.ds(i, 1), :] += v
                return carry
            jax.lax.fori_loop(0, count, pick, 0)

        def two_batches(state):
            count0, p = state
            count1, p = issue(1, p)
            land_and_pick(0, count0)
            count0, p = issue(0, p)
            land_and_pick(1, count1)
            return count0, p

        first = jnp.where(idx_ref[j, 0] < n, 0, nxt_ref[j, 0])
        jax.lax.while_loop(lambda state: state[0] > 0, two_batches,
                           issue(0, first))

    acc[...] = jnp.zeros_like(acc)
    for j in range(c):  # a row's c sources are added in the order of j
        plane(j)
    out_ref[...] = acc[...].astype(out_ref.dtype)


@jax.jit  # one trace and one lowering for all of a step's calls of a shape
@jax.named_scope("row_gather")
def gather_rows(x: jax.Array, idx: jax.Array) -> jax.Array:
    """[n, d], [c, r] int32 -> [r, d]: `y[i] = x[idx[0, i]] + ... +
    x[idx[c-1, i]]`, added in that order in float32 and rounded to x's
    dtype once (for 16-bit rows what XLA's fused sum of c gathers gives
    on the chip); an index >= n adds a zero row and is not copied."""
    if x.ndim != 2 or idx.ndim != 2:
        raise ValueError(f"gather_rows wants x [n, d] and idx [c, r], "
                         f"got {x.shape} and {idx.shape}")
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        raise NotImplementedError(
            f"gather_rows moves float32 and bfloat16 rows, got {x.dtype}")
    n, d = x.shape
    c, r = idx.shape
    if n % GROUP:
        # the last group must be whole to be copied; rows of zeros that
        # no index names (the sentinel is any index >= n: it is not read)
        x = jnp.pad(x, ((0, GROUP - n % GROUP), (0, 0)))
    tile = min(TILE_ROWS, max(16, 1 << (r - 1).bit_length()))
    batch = max(1, min(tile, _LAND_BYTES // (GROUP * d * x.dtype.itemsize)))
    n_tiles = -(-r // tile)
    idx = idx.astype(jnp.int32)
    if n_tiles * tile != r:
        # the last tile's indices past r: the sentinel, no copy
        idx = jnp.pad(idx, ((0, 0), (0, n_tiles * tile - r)),
                      constant_values=n)
    in_smem = pl.BlockSpec((c, tile), lambda i: (0, i),
                           memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_gather_kernel, n=n, c=c, tile=tile, batch=batch),
        grid=(n_tiles,),
        in_specs=[in_smem, in_smem, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile, d), lambda i: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, batch, GROUP, d), x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((tile, d), jnp.float32),
            pltpu.SMEM((2, batch), jnp.int32),
            pltpu.SMEM((2, batch), jnp.int32),
        ],
        out_shape=jax.ShapeDtypeStruct((r, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret(),
        name="moe_gather",
    )(idx, _next_live(idx, n, tile), x)
