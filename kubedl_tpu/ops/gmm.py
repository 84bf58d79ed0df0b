"""Grouped matrix multiply — the dropless-MoE expert FFN kernel.

`gmm(lhs, rhs, tile_expert)` computes, for every row-tile i of `lhs`,
`lhs[i] @ rhs[tile_expert[i]]` — i.e. a matmul whose weight matrix
changes per row-group. This is the TPU-native alternative to both of
the classic MoE dispatch shapes:

  * GShard's dense one-hot einsums burn S*E*C*d FLOPs per dispatch —
    measured equal to the expert FFN compute itself (models/moe.py);
  * capacity-slot gather/scatter (models/moe.py today) is
    bandwidth-cheap but still RUNS the expert matmuls over every
    capacity slot: at capacity_factor 1.25 that is a hard 1/1.25
    ceiling on MFU (the committed 0.474 at dense 0.60 is exactly that
    ceiling).

Here tokens are sorted by expert and padded per group to the row-tile
size, so the expert matmuls touch `top_k*S + E*tile_m` rows — a few
percent of tile rounding instead of 25% capacity padding, and NO
dropped tokens.

Three kernel families share the mechanics (ref: the megablox `gmm`
pattern from public JAX — SNIPPETS.md has no counterpart; built from
the pallas guide):
  * `gmm` — the plain grouped matmul;
  * `gmm_scaled` — same, with a per-expert [E, N] output scale folded
    into the accumulator flush (int8 per-output-channel dequant without
    materializing [M, N] row-scale arrays host-side);
  * `gmm_swiglu` — the fused MoE FFN front half: TWO weight stacks per
    tile, `silu(x @ w1_e * s1_e) * (x @ w3_e * s3_e)` computed in the
    f32 accumulators before a single write-back. Collapses the three
    unfused launches' first two and removes two [M, ffn] HBM
    round-trips (gate and up never hit HBM separately).

Shared mechanics:
  * caller guarantees every row-tile belongs to exactly ONE group and
    passes `tile_expert[num_m_tiles]`; the scalar-prefetch grid spec
    lets the rhs/scale BlockSpec index_maps select the expert's blocks
    per tile before the kernel body runs;
  * grid (m_tiles, n_tiles, k_tiles), k innermost sequential; f32
    accumulator scratch, epilogue (scale / SwiGLU) on the last k step;
  * tile sizes are dtype-aware (`_pick_tiles`): narrower element types
    take wider k/n tiles — the VMEM block budget stays ~constant while
    each block amortizes more MXU work per HBM fetch;
  * backward: dlhs is the same gmm against rhs^T (per expert);
    drhs is `tgmm` — grid (k, n, m) with m innermost sequential,
    accumulating row-tiles into the owning expert's [K, N] block
    (zeroed on the group's first tile). `gmm_swiglu` recomputes the
    two pre-activation products in backward (flash-attention-style
    rematerialization) rather than saving them;
  * each `pallas_call` carries a fixed `name=` that the device trace
    shows as the event's name: `gmm`, `gmm_scaled`, `gmm_swiglu` and
    `gmm_drhs` (the `tgmm` weight gradient). dlhs is the `gmm` kernel
    again; its `op_name` holds `transpose(jvp(` and the forward's does
    not. The public entries open a `grouped_matmul` scope, so that the
    innermost name on the stack, which the HLO instruction takes, is the
    kernel's own also under a bare `jax.grad` (flash_attention.py).

Live row tiles: a row-tile whose `tile_expert` entry is E (one past the
last expert of `rhs`) holds no routed row. Such tiles are a suffix of
the layout (moe.py `_dispatch_plan` sorts the rows it holds first), and
every kernel's grid stops before them: the row-tile grid dimension is
the traced count of live tiles, so device time follows the rows routed
here and not the static worst case `k*S + E*tile` in which every entry
lands here. Rows of dead tiles are never written and never read back
(the dispatch gathers only routed rows); the reductions over tiles
(`_owned_mask`, `_tile_segsum`) drop them by their out-of-range id.

Like ops/flash_attention.py, kernels run in interpret mode on the CPU
backend only, so CPU tests exercise the real kernel logic and any other
backend compiles them or raises.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubedl_tpu.ops import interpret

TILE_M = 128
_TILE_N = 256
_TILE_K = 256


def _scale_spec(tn: int) -> pl.BlockSpec:
    """Block of a per-expert scale carried as [E, 1, N]. Mosaic wants a
    block's second-to-last dimension divisible by 8 or equal to the
    array's, which a (1, tn) block of [E, N] is not; scale_ref[0] is
    then [1, tn] and broadcasts over the tile's rows as [tn] did."""
    return pl.BlockSpec((1, 1, tn), lambda i, j, kk, te: (te[i], 0, j))


def _row_tile_of(m: int, tile_expert, name: str) -> int:
    """The row-tile size IS m / len(tile_expert): the caller's per-tile
    expert map fixes the granularity, so bigger row tiles need no extra
    argument — the dispatch layout (moe.py `_row_tile`) simply hands in
    fewer, wider tiles. Bigger tiles matter: the kernel streams each
    row-tile's full [K, N] weight block from HBM, so rhs traffic is
    (m / tile) * K * N bytes — at tile 128 that is ~128 flops per rhs
    byte, BELOW a v5e's ~240 flops/byte balance point (the measured
    ~0.5x MoE-vs-dense efficiency gap); tile 512 clears it with margin.
    Must stay a multiple of TILE_M (layout padding + MXU sublanes)."""
    n_tiles = int(tile_expert.shape[0])
    if n_tiles <= 0 or m % n_tiles:
        raise ValueError(
            f"{name} tile_expert has {n_tiles} entries which do not evenly "
            f"tile {m} lhs rows; a ragged tail would silently never be "
            "computed")
    tm = m // n_tiles
    if tm % TILE_M:
        raise ValueError(
            f"{name} row-tile {tm} ({m} rows / {n_tiles} tile entries) "
            f"must be a multiple of TILE_M ({TILE_M}); the grid covers "
            "whole tiles and a ragged tail would silently never be "
            "computed")
    return tm


def _live_tiles(tile_expert, n_experts: int):
    """Row-tiles the grid visits: those owned by a real expert (dead ones
    carry the id `n_experts` and come last). At least one, so that a
    layout with no routed row still launches a defined grid; that tile
    multiplies zeros, as every padding row does."""
    return jnp.maximum(jnp.sum(tile_expert < n_experts, dtype=jnp.int32), 1)


def _visited_ids(tile_expert, n_experts: int):
    """The per-tile ids the kernels' index maps read. Only live tiles are
    visited, but for the one tile `_live_tiles` launches where nothing is
    live: its id is the dead mark, one past the last expert, and must not
    reach a BlockSpec (the chip halts on the out-of-range block)."""
    return jnp.minimum(tile_expert, n_experts - 1)


def _pick(dim: int, pref: int) -> int:
    """Largest tile <= pref that divides dim (dims here are model sizes —
    multiples of 128 in practice; fall back to the dim itself)."""
    for t in (pref, 512, 256, 128):
        if t <= pref and dim % t == 0:
            return t
    return dim


def _pick_tiles(k: int, n: int, dtype) -> "tuple[int, int]":
    """Dtype-aware (tk, tn): per-block VMEM bytes stay ~flat as elements
    narrow, so bf16/int8 take wider tiles — each weight block fetched
    from HBM feeds proportionally more MXU work. f32 keeps the classic
    256x256; 2-byte types go 512 on both contraction and output dims
    (block set ~1 MB + f32 accumulators, comfortably inside 16 MB VMEM
    with double buffering); 1-byte types the same (the MXU computes in
    bf16 after the operand-read convert, so wider than 512 buys nothing
    once accumulators dominate)."""
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize >= 4:
        pk, pn = _TILE_K, _TILE_N
    else:
        pk, pn = 512, 512
    return _pick(k, pk), _pick(n, pn)


# -- forward -----------------------------------------------------------------


def _gmm_kernel(te_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *, nk):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        lhs_ref[...], rhs_ref[0],
        preferred_element_type=jnp.float32,
    )

    @pl.when(kk == nk - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _gmm_scaled_kernel(te_ref, lhs_ref, rhs_ref, scale_ref, out_ref, acc_ref,
                       *, nk):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        lhs_ref[...], rhs_ref[0],
        preferred_element_type=jnp.float32,
    )

    @pl.when(kk == nk - 1)
    def _flush():
        # per-expert per-output-channel scale folded in the epilogue —
        # the [tn] vector broadcasts over the tile's rows, so no [M, N]
        # scale array ever exists in HBM
        out_ref[...] = (
            acc_ref[...] * scale_ref[0].astype(jnp.float32)
        ).astype(out_ref.dtype)


def _gmm_swiglu_kernel(te_ref, lhs_ref, w1_ref, w3_ref, s1_ref, s3_ref,
                       out_ref, acc1_ref, acc3_ref, *, nk):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc1_ref[...] = jnp.zeros_like(acc1_ref)
        acc3_ref[...] = jnp.zeros_like(acc3_ref)

    acc1_ref[...] += jnp.dot(
        lhs_ref[...], w1_ref[0], preferred_element_type=jnp.float32)
    acc3_ref[...] += jnp.dot(
        lhs_ref[...], w3_ref[0], preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _flush():
        # SwiGLU in the f32 accumulators: silu(gate) * up computed
        # before the single write-back — gate and up never round-trip
        # HBM as separate [M, ffn] tensors
        gate = acc1_ref[...] * s1_ref[0].astype(jnp.float32)
        up = acc3_ref[...] * s3_ref[0].astype(jnp.float32)
        out_ref[...] = (jax.nn.silu(gate) * up).astype(out_ref.dtype)


def _gmm_raw(lhs, rhs, tile_expert, out_scale=None):
    m, k = lhs.shape
    _, _, n = rhs.shape
    tm = _row_tile_of(m, tile_expert, "gmm")
    tk, tn = _pick_tiles(k, n, lhs.dtype)
    nk = k // tk
    grid = (_live_tiles(tile_expert, rhs.shape[0]), n // tn, nk)
    if out_scale is None:
        kernel = functools.partial(_gmm_kernel, nk=nk)
        in_specs = [
            pl.BlockSpec((tm, tk), lambda i, j, kk, te: (i, kk)),
            pl.BlockSpec((1, tk, tn), lambda i, j, kk, te: (te[i], kk, j)),
        ]
        operands = (_visited_ids(tile_expert, rhs.shape[0]), lhs, rhs)
    else:
        kernel = functools.partial(_gmm_scaled_kernel, nk=nk)
        in_specs = [
            pl.BlockSpec((tm, tk), lambda i, j, kk, te: (i, kk)),
            pl.BlockSpec((1, tk, tn), lambda i, j, kk, te: (te[i], kk, j)),
            _scale_spec(tn),
        ]
        operands = (_visited_ids(tile_expert, rhs.shape[0]), lhs, rhs,
                    out_scale[:, None, :])
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk, te: (i, j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, bytes_accessed=0, transcendentals=0),
        interpret=interpret(),
        name="gmm" if out_scale is None else "gmm_scaled",
    )(*operands)


def _gmm_swiglu_raw(lhs, w1, w3, tile_expert, scale1, scale3):
    m, k = lhs.shape
    _, _, n = w1.shape

    if w3.shape != w1.shape:
        raise ValueError(f"w1 {w1.shape} vs w3 {w3.shape} shape mismatch")
    tm = _row_tile_of(m, tile_expert, "gmm_swiglu")
    tk, tn = _pick_tiles(k, n, lhs.dtype)
    nk = k // tk
    grid = (_live_tiles(tile_expert, w1.shape[0]), n // tn, nk)
    return pl.pallas_call(
        functools.partial(_gmm_swiglu_kernel, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, kk, te: (i, kk)),
                pl.BlockSpec((1, tk, tn), lambda i, j, kk, te: (te[i], kk, j)),
                pl.BlockSpec((1, tk, tn), lambda i, j, kk, te: (te[i], kk, j)),
                _scale_spec(tn),
                _scale_spec(tn),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk, te: (i, j)),
            scratch_shapes=[
                pltpu.VMEM((tm, tn), jnp.float32),
                pltpu.VMEM((tm, tn), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * m * k * n, bytes_accessed=0, transcendentals=m * n),
        interpret=interpret(),
        name="gmm_swiglu",
    )(_visited_ids(tile_expert, w1.shape[0]), lhs, w1, w3,
      scale1[:, None, :], scale3[:, None, :])


# -- transposed (weight-gradient) --------------------------------------------


def _tgmm_kernel(te_ref, first_ref, lhs_ref, dout_ref, out_ref):
    mm = pl.program_id(2)

    @pl.when(first_ref[mm] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(
        lhs_ref[...].T, dout_ref[...],
        preferred_element_type=jnp.float32,
    )[None]


def _tgmm_raw(lhs, dout, tile_expert, first_tile, n_experts):
    """drhs[e] = sum over e's row-tiles of lhs_tile^T @ dout_tile.
    Experts with no live tile keep whatever was in their block — callers
    mask them to zero (cheap jnp.where on group counts)."""
    m, k = lhs.shape
    _, n = dout.shape
    tm = _row_tile_of(m, tile_expert, "tgmm")
    tk, tn = _pick_tiles(k, n, lhs.dtype)
    grid = (k // tk, n // tn, _live_tiles(tile_expert, n_experts))
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda kk, j, i, te, fi: (i, kk)),
                pl.BlockSpec((tm, tn), lambda kk, j, i, te, fi: (i, j)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda kk, j, i, te, fi: (te[i], kk, j)),
            scratch_shapes=[],
        ),
        out_shape=jax.ShapeDtypeStruct((n_experts, k, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, bytes_accessed=0, transcendentals=0),
        interpret=interpret(),
        name="gmm_drhs",
    )(_visited_ids(tile_expert, n_experts), first_tile, lhs, dout)


# -- shared backward helpers -------------------------------------------------


def _owned_mask(tile_expert, n_experts):
    """[E] int32 count of row-tiles each expert owns (0 = never written
    by tgmm — its block is garbage and must be masked). Dead tiles carry
    the id E and are dropped."""
    return jnp.zeros((n_experts,), jnp.int32).at[tile_expert].add(
        1, mode="drop")


def _bcast_tile_scale(x, scale, tile_expert):
    """x[m, n] * scale[tile_expert][...] without materializing a [m, n]
    repeat array: the per-tile [n] vectors broadcast over a reshaped
    [tiles, row_tile, n] view (XLA fuses the whole thing). A dead tile's
    out-of-range id clamps to the last expert; its rows are never read."""
    m, n = x.shape
    nt = tile_expert.shape[0]
    return (
        x.reshape(nt, m // nt, n)
        * scale[tile_expert][:, None, :].astype(x.dtype)
    ).reshape(m, n)


def _tile_segsum(x, tile_expert, n_experts):
    """[E, N] per-expert sum of x's rows (x [m, n]) — the dscale
    reduction: each tile's rows collapse, then tiles scatter-add into
    their owning expert's row. Dead tiles (id E) hold unwritten rows:
    zeroed before the sum, since the scatter's drop comes after it."""
    m, n = x.shape
    nt = tile_expert.shape[0]
    live = (tile_expert < n_experts)[:, None, None]
    per_tile = jnp.where(live, x.reshape(nt, m // nt, n), 0).sum(axis=1)
    return jnp.zeros((n_experts, n), x.dtype).at[tile_expert].add(
        per_tile, mode="drop")


def _first_tile_flags(tile_expert):
    """1 where a tile starts a new expert run (m-order), else 0."""
    prev = jnp.concatenate(
        [jnp.full((1,), -1, tile_expert.dtype), tile_expert[:-1]])
    return (tile_expert != prev).astype(jnp.int32)


def _drhs(lhs, dout, tile_expert, n_experts):
    first = _first_tile_flags(tile_expert)
    drhs = _tgmm_raw(lhs, dout, tile_expert, first, n_experts)
    owned = _owned_mask(tile_expert, n_experts)
    return jnp.where((owned > 0)[:, None, None], drhs, 0.0)


# -- public ops with VJPs ----------------------------------------------------


@jax.custom_vjp
def _gmm_vjp(lhs, rhs, tile_expert):
    return _gmm_raw(lhs, rhs, tile_expert)


def _gmm_fwd(lhs, rhs, tile_expert):
    return _gmm_raw(lhs, rhs, tile_expert), (lhs, rhs, tile_expert)


def _gmm_bwd(res, dout):
    lhs, rhs, tile_expert = res
    dlhs = _gmm_raw(dout, jnp.swapaxes(rhs, 1, 2), tile_expert)
    drhs = _drhs(lhs, dout, tile_expert, rhs.shape[0])
    dte = np.zeros(tile_expert.shape, jax.dtypes.float0)
    return dlhs.astype(lhs.dtype), drhs.astype(rhs.dtype), dte


_gmm_vjp.defvjp(_gmm_fwd, _gmm_bwd)


def _check_row_tile(m: int, tile_expert, row_tile: int, name: str) -> None:
    """Public-entry validation: the caller states the row-tile size it
    laid the rows out with, and len(tile_expert) must agree — otherwise
    a truncated tile_expert whose length happens to divide m would be
    silently reinterpreted as a wider tile and apply one expert's
    weights to another's rows."""
    if row_tile % TILE_M:
        raise ValueError(
            f"{name} row_tile {row_tile} must be a multiple of TILE_M "
            f"({TILE_M}) — MXU sublane alignment")
    if m % row_tile:
        raise ValueError(
            f"{name} lhs rows ({m}) must be a multiple of TILE_M-aligned "
            f"row_tile {row_tile}; the grid covers m // row_tile tiles and "
            "a ragged tail would silently never be computed")
    if tile_expert.shape[0] != m // row_tile:
        raise ValueError(
            f"{name} tile_expert has {tile_expert.shape[0]} entries for "
            f"{m // row_tile} row-tiles of {row_tile} rows; an out-of-range "
            "te[i] gather clamps and would silently reuse the last "
            "expert's weights")


@jax.named_scope("grouped_matmul")
def gmm(lhs, rhs, tile_expert, *, row_tile: int = TILE_M):
    """[M, K] x [E, K, N] -> [M, N], weight chosen per row-tile.

    `tile_expert[i]` names the expert for row-tile i (rows sorted and
    per-group padded to `row_tile` by the caller — see moe.py's
    dropless dispatch, which uses wider tiles for large dispatches to
    amortize the per-tile weight stream). Padding rows are zeros; they
    multiply into zeros and are never gathered back. Tiles named E (a
    suffix) are dead: not computed, their output rows left unwritten."""
    _check_row_tile(lhs.shape[0], tile_expert, row_tile, "gmm")
    return _gmm_vjp(lhs, rhs, tile_expert)


@jax.custom_vjp
def _gmm_scaled_vjp(lhs, rhs, tile_expert, out_scale):
    return _gmm_raw(lhs, rhs, tile_expert, out_scale=out_scale)


def _gmm_scaled_fwd(lhs, rhs, tile_expert, out_scale):
    out = _gmm_raw(lhs, rhs, tile_expert, out_scale=out_scale)
    return out, (lhs, rhs, tile_expert, out_scale, out)


def _gmm_scaled_bwd(res, dout):
    lhs, rhs, tile_expert, out_scale, out = res
    e = rhs.shape[0]
    # y = raw * s  =>  dL/draw = dout * s (tile-broadcast, no repeat)
    dpre = _bcast_tile_scale(dout, out_scale, tile_expert)
    dlhs = _gmm_raw(dpre, jnp.swapaxes(rhs, 1, 2), tile_expert)
    drhs = _drhs(lhs, dpre, tile_expert, e)
    # dL/ds[e, n] = sum over e's rows of raw * dout. raw = out / s (s is
    # strictly positive by construction, quant.py) and s is constant per
    # (e, n) within a segment, so the division moves OUTSIDE the segsum
    # — no forward-sized rematerialization launch needed
    dscale = _tile_segsum(
        out.astype(jnp.float32) * dout.astype(jnp.float32), tile_expert, e
    ) / out_scale.astype(jnp.float32)
    dte = np.zeros(tile_expert.shape, jax.dtypes.float0)
    return (dlhs.astype(lhs.dtype), drhs.astype(rhs.dtype), dte,
            dscale.astype(out_scale.dtype))


_gmm_scaled_vjp.defvjp(_gmm_scaled_fwd, _gmm_scaled_bwd)


@jax.named_scope("grouped_matmul")
def gmm_scaled(lhs, rhs, tile_expert, out_scale, *, row_tile: int = TILE_M):
    """gmm with a per-expert output scale: out[i] = (lhs[i] @
    rhs[te[i]]) * out_scale[te[i]], the scale ([E, N], per output
    channel) folded into the kernel epilogue. This is the int8 dequant
    path: the alternative — gathering scale rows host-side — builds a
    [M, N] f32 array whose size scales with the per-expert tile padding
    (e * row_tile extra rows), a pure memory/bandwidth tax."""
    _check_row_tile(lhs.shape[0], tile_expert, row_tile, "gmm_scaled")
    return _gmm_scaled_vjp(lhs, rhs, tile_expert, out_scale)


@jax.custom_vjp
def _gmm_swiglu_vjp(lhs, w1, w3, tile_expert, scale1, scale3):
    return _gmm_swiglu_raw(lhs, w1, w3, tile_expert, scale1, scale3)


def _gmm_swiglu_fwd(lhs, w1, w3, tile_expert, scale1, scale3):
    out = _gmm_swiglu_raw(lhs, w1, w3, tile_expert, scale1, scale3)
    return out, (lhs, w1, w3, tile_expert, scale1, scale3)


def _gmm_swiglu_bwd(res, dout):
    lhs, w1, w3, tile_expert, scale1, scale3 = res
    e = w1.shape[0]
    # rematerialize the pre-activation products (flash-style: cheaper
    # than holding two [M, ffn] tensors across the backward)
    g_raw = _gmm_raw(lhs, w1, tile_expert)
    u_raw = _gmm_raw(lhs, w3, tile_expert)
    g = _bcast_tile_scale(g_raw, scale1, tile_expert).astype(jnp.float32)
    u = _bcast_tile_scale(u_raw, scale3, tile_expert).astype(jnp.float32)
    df = dout.astype(jnp.float32)
    sig = jax.nn.sigmoid(g)
    silu_g = g * sig
    # d silu(g)/dg = sig * (1 + g * (1 - sig))
    dgate = df * u * (sig * (1.0 + g * (1.0 - sig)))
    dup = df * silu_g
    # fold the forward scales into the upstream grads (tile-broadcast)
    dgate_pre = _bcast_tile_scale(
        dgate.astype(lhs.dtype), scale1, tile_expert)
    dup_pre = _bcast_tile_scale(dup.astype(lhs.dtype), scale3, tile_expert)
    dlhs = (
        _gmm_raw(dgate_pre, jnp.swapaxes(w1, 1, 2), tile_expert)
        + _gmm_raw(dup_pre, jnp.swapaxes(w3, 1, 2), tile_expert)
    )
    dw1 = _drhs(lhs, dgate_pre, tile_expert, e)
    dw3 = _drhs(lhs, dup_pre, tile_expert, e)
    ds1 = _tile_segsum(g_raw.astype(jnp.float32) * dgate, tile_expert, e)
    ds3 = _tile_segsum(u_raw.astype(jnp.float32) * dup, tile_expert, e)
    dte = np.zeros(tile_expert.shape, jax.dtypes.float0)
    return (dlhs.astype(lhs.dtype), dw1.astype(w1.dtype),
            dw3.astype(w3.dtype), dte,
            ds1.astype(scale1.dtype), ds3.astype(scale3.dtype))


_gmm_swiglu_vjp.defvjp(_gmm_swiglu_fwd, _gmm_swiglu_bwd)


@jax.named_scope("grouped_matmul")
def gmm_swiglu(lhs, w1, w3, tile_expert, scale1, scale3, *,
               row_tile: int = TILE_M):
    """Fused grouped SwiGLU front half:

        out[i] = silu(lhs[i] @ w1[e] * s1[e]) * (lhs[i] @ w3[e] * s3[e])

    with e = tile_expert[i]. One kernel launch computes both grouped
    matmuls into f32 accumulators and applies scale + silu + multiply
    in the epilogue — vs the unfused path's two launches plus two
    [M, ffn] HBM round-trips for the separate gate/up tensors. scale1/
    scale3 are [E, N]; pass ones for unquantized weights (the f32
    multiply by 1.0 is exact). The caller's w2 projection stays a
    separate gmm/gmm_scaled (different contraction dim)."""
    _check_row_tile(lhs.shape[0], tile_expert, row_tile, "gmm_swiglu")
    return _gmm_swiglu_vjp(lhs, w1, w3, tile_expert, scale1, scale3)
