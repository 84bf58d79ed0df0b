"""A state-space layer's convolution (models/ssm.py ssm_mixer: K causal
depthwise taps, a bias and a SiLU over the xBC columns of the in
projection's output) as two Pallas TPU kernels, one call a layer and
pass: xBC is read once where it lies and x, B and C are written once.

For sequence i, token t and channel c of the convolution's d, with
u [b, t, width] the in projection's output, xBC its columns
offset .. offset + d, zero before the sequence, w [K, d] and bias [d]
float32:

    taps[t] = dtype(sum_j w[j] * xBC[t - (K-1) + j])      float32 sum, j = 0 first
    pre[t]  = f32(taps[t]) + bias
    out[t]  = dtype(pre[t] * sigmoid(pre[t]))             `ssm_conv_fwd`

the XLA form's operations at its rounding points. `out` leaves the kernel
as the arrays the scan takes, one a width of `widths` (x, B, C). Given
their cotangents dout, with pre recomputed from xBC:

    ds[t]    = f32(dout[t]) * sigmoid(pre[t]) * (1 + pre[t] * (1 - sigmoid(pre[t])))
    dxBC[t]  = dtype(sum_j w[j] * ds[t + (K-1) - j])      ds zero after the sequence
    dw[j]    = sum_{i, t} ds[t] * xBC[t - (K-1) + j]
    dbias    = sum_{i, t} ds[t]                           `ssm_conv_bwd`

The rounding between the taps and the bias is passed straight through,
as autodiff passes it; ds stays float32 where autodiff rounds it to the
model's dtype on its way to the taps. dw and dbias are summed in float32
over all tokens in the kernel's order, not XLA's.

A program holds a block of TOKEN_BLOCK tokens by 128 channels and walks
it in passes of ROWS tokens, whose values live in vector registers: xBC
is widened there, a tap's shifted operand is a sublane rotate of the
pass, and its first K - 1 rows come from the 8 rows before (the pass
before; at a block's start one more 16-row block of the same array, zero
at a sequence's start). The backward walks a sequence's blocks, and a
block's passes, from the last to the first, so that the K - 1 rows of ds
after a pass are the ones it has just made; its grid has the channel
block outermost, and dw and dbias leave the kernel once a channel block.
What reaches HBM: xBC read once (and 16 rows more a block), the outputs
written once, in the model's dtype; in the backward xBC and dout read
once and dxBC written once. No padded copy, no float32 tensor.

The columns of u beside xBC (z before, dt after) pass through
`split_conv` untouched, so that its backward can hand u's cotangent back
as one concatenation, which the in projection's backward needs anyway.

The gated form, a convolution layer's gates and taps
(models/short_conv.py): for u [b, t, 3d] = [B, C, z] by columns and taps
w [d, K], both in the model's dtype,

    g[t]  = dtype(B[t] * z[t])                            g zero before the sequence
    c[t]  = dtype(sum_j f32(w[j]) * g[t - (K-1) + j])     float32 sum, j = 0 first
    y[t]  = dtype(C[t] * c[t])                            `short_conv_fwd`

and given dy, with g and c recomputed:

    dC[t] = dtype(dy[t] * c[t]),    dc[t] = dtype(dy[t] * C[t])
    dg[t] = dtype(sum_j f32(w[j]) * f32(dc[t + (K-1) - j]))   j = K-1 first, dc zero after
    dB[t] = dtype(dg[t] * z[t]),    dz[t] = dtype(dg[t] * B[t])
    dw[j] = sum_{i, t} f32(dc[t]) * f32(g[t - (K-1) + j])     `short_conv_bwd`

the XLA form's operations at the rounding points where autodiff's jaxpr
puts them: y is that form's bit for bit, and du and dw are its autodiff's
taken one operation at a time, dw but for its float32 sum's order. XLA's
fusion of that autodiff drops some of those roundings (its excess
precision: on the CPU it keeps dc float32; on a v5e a sixth of du's
elements and two fifths of dw's land a bf16 rounding away from the
kernel's). The forward reads B, C and z where they lie in u and writes y
once; the backward walks as `ssm_conv_bwd` does and writes du whole, so
that the in projection's backward takes it with no concatenation: a
program's three channel blocks of du are made once and leave in three
steps of the grid's last axis, the second and third held in VMEM
meanwhile.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubedl_tpu.ops import interpret

# Tokens a program (a sequence that is no multiple takes the largest
# divisor that is a multiple of 128) and tokens a pass of its inner loop,
# which divide 128
TOKEN_BLOCK = 4096
ROWS = 128
MAX_TAPS = 8
_LANES = 128
_HALO = 16  # rows of the block before a block: one bf16 tile, the last 8 used
_F32 = jnp.float32


def supports(seq: int, offset: int, widths: Tuple[int, ...], taps: int) -> bool:
    """Whether the kernels take these shapes: xBC and each of its parts
    in whole 128-lane blocks of u, a sequence of whole 128-token blocks,
    and taps that reach back less than one 8-row tile."""
    return (seq > 0 and seq % _LANES == 0 and offset % _LANES == 0
            and all(w > 0 and w % _LANES == 0 for w in widths)
            and 2 <= taps <= MAX_TAPS)


def _token_block(seq: int) -> int:
    """Tokens a program: the largest multiple of 128 up to TOKEN_BLOCK
    that divides the sequence."""
    return next(q for q in range(min(TOKEN_BLOCK, seq), 0, -_LANES) if seq % q == 0)


def _rows8():
    return jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 0)


def _down(cur, before, s: int):
    """cur's rows moved s down: row r holds cur[r - s], the first s rows
    the last s of `before` [8, lanes]."""
    if s == 0:
        return cur
    moved = pltpu.roll(cur, s, 0)
    head = jnp.where(_rows8() < s, pltpu.roll(before, s, 0), moved[:8])
    return jnp.concatenate([head, moved[8:]], axis=0)


def _up(cur, after, s: int):
    """cur's rows moved s up: row r holds cur[r + s], the last s rows the
    first s of `after` [8, lanes]."""
    if s == 0:
        return cur
    n = cur.shape[0]
    moved = pltpu.roll(cur, n - s, 0)
    tail = jnp.where(_rows8() >= 8 - s, pltpu.roll(after, 8 - s, 0), moved[n - 8:])
    return jnp.concatenate([moved[:n - 8], tail], axis=0)


def _taps(cur, before, w):
    """The taps' sum over a pass [rows, lanes] float32, j = 0 first, and
    the pass moved down by each tap's reach (tap j weighs `moved[j]`)."""
    k = len(w)
    moved = [_down(cur, before, k - 1 - j) for j in range(k)]
    taps = moved[0] * w[0]
    for j in range(1, k):
        taps = taps + moved[j] * w[j]
    return taps, moved


def _pre(cur, before, w, bias, dtype):
    """The pre-activation of a pass [rows, lanes] float32, and the pass
    moved down by each tap's reach."""
    taps, moved = _taps(cur, before, w)
    return taps.astype(dtype).astype(_F32) + bias, moved


def _fold(v):
    """A sum over a pass's tokens, 8 rows apart: the rows are added up
    once a channel block."""
    return sum(v[r:r + 8] for r in range(0, v.shape[0], 8))


def _sigmoid(v):
    return 1.0 / (1.0 + jnp.exp(-v))


def _last8(tile):
    """The last 8 rows of a [16, lanes] tile, float32 (a bf16 tile packs
    two rows a sublane: widened whole, then cut)."""
    return tile.astype(_F32)[_HALO - 8:]


def _before_block(halo_ref, first):
    """The 8 rows before a block, float32; zero before a sequence."""
    return jnp.where(first, 0.0, _last8(halo_ref[0]))


def _taps_of(w_ref):
    return [w_ref[j:j + 1, :] for j in range(w_ref.shape[0])]


# A pass's arithmetic under a jit of its own: traced once for the three
# walks of a kernel's body that call it (one an output the program's
# channel block may lie in), where a plain helper is traced once a walk,
# 0.6 s of a step's trace
@functools.partial(jax.jit, static_argnames="dtype")
def _fwd_pass(cur, before, w, bias, *, dtype):
    pre, _ = _pre(cur, before, w, bias, dtype)
    return (pre * _sigmoid(pre)).astype(dtype)


@functools.partial(jax.jit, static_argnames="dtype")
def _bwd_pass(cur, before, dout, after, sums, w, bias, *, dtype):
    """A pass's dxBC, the first 8 rows of its ds (what the pass before
    it needs) and the running sums of dw's K taps and of dbias."""
    k = len(w)
    pre, moved = _pre(cur, before, w, bias, dtype)
    sig = _sigmoid(pre)
    ds = dout.astype(_F32) * (sig * (1.0 + pre * (1.0 - sig)))
    dx = _up(ds, after, k - 1) * w[0]
    for t in range(1, k):
        dx = dx + _up(ds, after, k - 1 - t) * w[t]
    sums = tuple(s + _fold(ds * m) for s, m in zip(sums, moved)) + (
        sums[k] + _fold(ds),)
    return dx.astype(dtype), ds[:8], sums


def _fwd_kernel(x_ref, halo_ref, w_ref, b_ref, *out_refs, bounds, rows):
    ti, j = pl.program_id(1), pl.program_id(2)
    w, bias = _taps_of(w_ref), b_ref[...]
    start = _before_block(halo_ref, ti == 0)

    def walk(o_ref):
        def body(c, before):
            at = pl.ds(pl.multiple_of(c * rows, rows), rows)
            cur = x_ref[0, at, :].astype(_F32)
            o_ref[0, at, :] = _fwd_pass(cur, before, w, bias, dtype=x_ref.dtype)
            return cur[rows - 8:]

        jax.lax.fori_loop(0, x_ref.shape[1] // rows, body, start)

    # a program's channel block lies in one output: the others' blocks
    # stay where they are (their index maps clamp) and are not written
    for (lo, hi), o_ref in zip(bounds, out_refs):
        pl.when((j >= lo) & (j < hi))(functools.partial(walk, o_ref))


def _bwd_kernel(x_ref, halo_ref, w_ref, b_ref, *refs, bounds, rows):
    n = len(bounds)
    dout_refs, (dx_ref, dw_ref, db_ref, after_ref, acc_ref) = refs[:n], refs[n:]
    j, i, ti = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nb, nt = pl.num_programs(1), pl.num_programs(2)
    w, bias = _taps_of(w_ref), b_ref[...]
    k = len(w)
    passes = x_ref.shape[1] // rows
    start = _before_block(halo_ref, ti == nt - 1)  # blocks from the last: ti counts back

    @pl.when((i == 0) & (ti == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ti == 0)  # the sequence's last block: nothing comes after
    def _():
        after_ref[...] = jnp.zeros_like(after_ref)

    def walk(d_ref):
        def body(step, carry):
            c = passes - 1 - step
            r0 = pl.multiple_of(c * rows, rows)
            at = pl.ds(r0, rows)
            cur = x_ref[0, at, :].astype(_F32)
            held = x_ref[0, pl.ds(pl.multiple_of(
                jnp.maximum(r0 - _HALO, 0), _HALO), _HALO), :]
            before = jnp.where(c == 0, start, _last8(held))
            dx, after, sums = _bwd_pass(
                cur, before, d_ref[0, at, :], *carry, w, bias, dtype=x_ref.dtype)
            dx_ref[0, at, :] = dx
            return after, sums

        after, sums = jax.lax.fori_loop(
            0, passes, body,
            (after_ref[...], tuple(acc_ref[m] for m in range(k + 1))))
        after_ref[...] = after
        for m in range(k + 1):
            acc_ref[m] = sums[m]

    for (lo, hi), d_ref in zip(bounds, dout_refs):
        pl.when((j >= lo) & (j < hi))(functools.partial(walk, d_ref))

    @pl.when((i == nb - 1) & (ti == nt - 1))
    def _():
        dw_ref[...] = jnp.sum(acc_ref[:k], axis=1)
        db_ref[...] = jnp.sum(acc_ref[k], axis=0, keepdims=True)


def _bounds(widths):
    """Each output's range of 128-lane channel blocks."""
    edges = [0]
    for width in widths:
        edges.append(edges[-1] + width // _LANES)
    return tuple(zip(edges[:-1], edges[1:]))


def _lane_dense(w, b):
    return w.astype(_F32).T, b.astype(_F32).reshape(1, -1)


_VMEM = 64 << 20


# one trace and one lowering for all of a step's calls (a bare pallas_call
# site is traced and lowered again before the compile cache is asked)
@functools.partial(jax.jit, static_argnames=("offset", "widths"))
def _fwd_call(u, w, b, *, offset, widths):
    bsz, seq, _ = u.shape
    tq, off = _token_block(seq), offset // _LANES
    bounds = _bounds(widths)
    tiles = tq // _HALO  # a block in units of the 16-row block before it
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bounds=bounds, rows=ROWS),
        grid=(bsz, seq // tq, bounds[-1][1]),
        in_specs=[
            pl.BlockSpec((1, tq, _LANES), lambda i, t, j: (i, t, off + j)),
            pl.BlockSpec((1, _HALO, _LANES),
                         lambda i, t, j: (i, jnp.maximum(t * tiles - 1, 0), off + j)),
            pl.BlockSpec((w.shape[1], _LANES), lambda i, t, j: (0, j)),
            pl.BlockSpec((1, _LANES), lambda i, t, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, tq, _LANES), functools.partial(
                lambda i, t, j, lo, hi: (i, t, jnp.clip(j - lo, 0, hi - lo - 1)),
                lo=lo, hi=hi))
            for lo, hi in bounds],
        out_shape=[jax.ShapeDtypeStruct((bsz, seq, width), u.dtype)
                   for width in widths],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret(),
        name="ssm_conv_fwd",
    )(u, u, *_lane_dense(w, b))


@functools.partial(jax.jit, static_argnames=("offset", "widths"))
def _bwd_call(u, w, b, douts, *, offset, widths):
    bsz, seq, _ = u.shape
    tq, off = _token_block(seq), offset // _LANES
    bounds = _bounds(widths)
    k, d, nt, tiles = w.shape[1], sum(widths), seq // tq, tq // _HALO
    back = lambda t: nt - 1 - t

    def cotangent_at(j, i, t, lo, hi):
        # outside its range a cotangent's block stays put: nothing is fetched
        inside = (j >= lo) & (j < hi)
        return tuple(jnp.where(inside, v, 0) for v in (i, back(t), j - lo))

    dxbc, dw, db = pl.pallas_call(
        functools.partial(_bwd_kernel, bounds=bounds, rows=ROWS),
        grid=(bounds[-1][1], bsz, nt),
        in_specs=[
            pl.BlockSpec((1, tq, _LANES), lambda j, i, t: (i, back(t), off + j)),
            pl.BlockSpec((1, _HALO, _LANES), lambda j, i, t: (
                i, jnp.maximum(back(t) * tiles - 1, 0), off + j)),
            pl.BlockSpec((k, _LANES), lambda j, i, t: (0, j)),
            pl.BlockSpec((1, _LANES), lambda j, i, t: (0, j)),
        ] + [pl.BlockSpec((1, tq, _LANES),
                          functools.partial(cotangent_at, lo=lo, hi=hi))
             for lo, hi in bounds],
        out_specs=[
            pl.BlockSpec((1, tq, _LANES), lambda j, i, t: (i, back(t), j)),
            pl.BlockSpec((k, _LANES), lambda j, i, t: (0, j)),
            pl.BlockSpec((1, _LANES), lambda j, i, t: (0, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bsz, seq, d), u.dtype),
                   jax.ShapeDtypeStruct((k, d), _F32),
                   jax.ShapeDtypeStruct((1, d), _F32)],
        scratch_shapes=[pltpu.VMEM((8, _LANES), _F32),
                        pltpu.VMEM((k + 1, 8, _LANES), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret(),
        name="ssm_conv_bwd",
    )(u, u, *_lane_dense(w, b), *douts)
    return dxbc, dw.T.astype(w.dtype), db.reshape(b.shape).astype(b.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _split_conv(u, w, b, offset, widths):
    outs = _fwd_call(u, w, b, offset=offset, widths=widths)
    return (u[..., :offset], *outs, u[..., offset + sum(widths):])


def _split_conv_fwd(u, w, b, offset, widths):
    return _split_conv(u, w, b, offset, widths), (u, w, b)


def _split_conv_bwd(offset, widths, res, cotangents):
    dleft, *douts, dright = cotangents
    dxbc, dw, db = _bwd_call(*res, tuple(douts), offset=offset, widths=widths)
    return jnp.concatenate([dleft, dxbc, dright], axis=-1), dw, db


_split_conv.defvjp(_split_conv_fwd, _split_conv_bwd)


def split_conv(u: jax.Array, w: jax.Array, b: jax.Array, offset: int,
               widths: Tuple[int, ...]) -> Tuple[jax.Array, ...]:
    """u [b, t, width] split along its columns, the middle part through
    the convolution: (u[..., :offset], one array a width of `widths` of
    silu(causal_taps(xBC, w) + b) in u's dtype, what is left of u), for
    xBC = u[..., offset : offset + sum(widths)], taps w [sum(widths), K]
    (tap K-1 weighs the token itself) and bias b [sum(widths)].
    `supports` holds of the shapes."""
    # A kernel's HLO instruction takes the innermost name on the stack:
    # under this scope that is its own name= (%ssm_conv_fwd.N), where a
    # bare jax.grad would wrap it (ops/ssm_scan.py has the same).
    with jax.named_scope("ssm_conv_kernel"):
        return _split_conv(u, w, b, offset, tuple(widths))


# -- the gated form: models/short_conv.py's gates and taps -----------------------


def _gate(b, z, dtype):
    """g = dtype(B * z) of two blocks of u, float32."""
    return (b.astype(_F32) * z.astype(_F32)).astype(dtype).astype(_F32)


def _gates_before(bh_ref, zh_ref, first, dtype):
    """g of the 8 tokens before a block, float32; zero before a sequence."""
    return jnp.where(first, 0.0, _gate(bh_ref[0], zh_ref[0], dtype)[_HALO - 8:])


def _gated_fwd_kernel(b_ref, c_ref, z_ref, bh_ref, zh_ref, w_ref, y_ref, *, rows):
    dtype, w = b_ref.dtype, _taps_of(w_ref)

    def body(p, before):
        at = pl.ds(pl.multiple_of(p * rows, rows), rows)
        g = _gate(b_ref[0, at, :], z_ref[0, at, :], dtype)
        c = _taps(g, before, w)[0].astype(dtype).astype(_F32)
        y_ref[0, at, :] = (c_ref[0, at, :].astype(_F32) * c).astype(dtype)
        return g[rows - 8:]

    jax.lax.fori_loop(0, b_ref.shape[1] // rows, body,
                      _gates_before(bh_ref, zh_ref, pl.program_id(1) == 0, dtype))


def _gated_bwd_pass(b, c_gate, z, before, dy, after, sums, w, dtype):
    """A pass's dB, dC and dz, the first 8 rows of its dc (what the pass
    before it needs) and the running sums of dw's K taps."""
    k = len(w)
    g = _gate(b, z, dtype)
    taps, moved = _taps(g, before, w)
    dy = dy.astype(_F32)
    dc_gate = (dy * taps.astype(dtype).astype(_F32)).astype(dtype)
    dc = (dy * c_gate.astype(_F32)).astype(dtype).astype(_F32)
    # autodiff's order: the tap on the token itself first
    dg = dc * w[k - 1]
    for j in range(k - 2, -1, -1):
        dg = dg + _up(dc, after, k - 1 - j) * w[j]
    dg = dg.astype(dtype).astype(_F32)
    sums = tuple(s + _fold(dc * m) for s, m in zip(sums, moved))
    return ((dg * z.astype(_F32)).astype(dtype), dc_gate,
            (dg * b.astype(_F32)).astype(dtype), dc[:8], sums)


def _gated_bwd_kernel(b_ref, c_ref, z_ref, bh_ref, zh_ref, w_ref, dy_ref,
                      du_ref, dw_ref, after_ref, acc_ref, held_ref, *, rows):
    i, ti, part = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    nb, nt = pl.num_programs(1), pl.num_programs(2)
    dtype, w = b_ref.dtype, _taps_of(w_ref)
    k, passes = len(w), b_ref.shape[1] // rows

    def at_pass(c):
        return pl.ds(pl.multiple_of(c * rows, rows), rows)

    # part 0 computes the block's dB, dC and dz and writes dB; parts 1 and
    # 2 write dC and dz, held in VMEM, into their own columns of du
    @pl.when(part == 0)
    def _():
        @pl.when((i == 0) & (ti == 0))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(ti == 0)  # the sequence's last block: nothing comes after
        def _():
            after_ref[...] = jnp.zeros_like(after_ref)

        # blocks from the last: ti counts back
        start = _gates_before(bh_ref, zh_ref, ti == nt - 1, dtype)

        def body(step, carry):
            c = passes - 1 - step
            r0 = pl.multiple_of(c * rows, rows)
            at = at_pass(c)
            halo = pl.ds(pl.multiple_of(jnp.maximum(r0 - _HALO, 0), _HALO), _HALO)
            before = jnp.where(c == 0, start, _gate(
                b_ref[0, halo, :], z_ref[0, halo, :], dtype)[_HALO - 8:])
            db, dc_gate, dz, after, sums = _gated_bwd_pass(
                b_ref[0, at, :], c_ref[0, at, :], z_ref[0, at, :], before,
                dy_ref[0, at, :], *carry, w, dtype)
            du_ref[0, at, :] = db
            held_ref[0, at, :] = dc_gate
            held_ref[1, at, :] = dz
            return after, sums

        after, sums = jax.lax.fori_loop(
            0, passes, body, (after_ref[...], tuple(acc_ref[m] for m in range(k))))
        after_ref[...] = after
        for m in range(k):
            acc_ref[m] = sums[m]

        @pl.when((i == nb - 1) & (ti == nt - 1))
        def _():
            dw_ref[...] = jnp.sum(acc_ref[...], axis=1)

    @pl.when(part > 0)
    def _():
        def body(c, _):
            du_ref[0, at_pass(c), :] = held_ref[part - 1, at_pass(c), :]
            return 0

        jax.lax.fori_loop(0, passes, body, 0)


# one trace and one lowering for all of a step's calls, as above
@jax.jit
def _gated_fwd_call(u, w):
    bsz, seq, width = u.shape
    tq, nd = _token_block(seq), width // (3 * _LANES)
    tiles = tq // _HALO
    block = lambda part: pl.BlockSpec(
        (1, tq, _LANES), lambda i, t, j: (i, t, part * nd + j))
    halo = lambda part: pl.BlockSpec(
        (1, _HALO, _LANES),
        lambda i, t, j: (i, jnp.maximum(t * tiles - 1, 0), part * nd + j))
    return pl.pallas_call(
        functools.partial(_gated_fwd_kernel, rows=ROWS),
        grid=(bsz, seq // tq, nd),
        in_specs=[block(0), block(1), block(2), halo(0), halo(2),
                  pl.BlockSpec((w.shape[1], _LANES), lambda i, t, j: (0, j))],
        out_specs=pl.BlockSpec((1, tq, _LANES), lambda i, t, j: (i, t, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, seq, width // 3), u.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret(),
        name="short_conv_fwd",
    )(u, u, u, u, u, w.astype(_F32).T)


@jax.jit
def _gated_bwd_call(u, w, dy):
    bsz, seq, width = u.shape
    tq, nd = _token_block(seq), width // (3 * _LANES)
    k, nt, tiles = w.shape[1], seq // tq, tq // _HALO
    back = lambda t: nt - 1 - t
    block = lambda part: pl.BlockSpec(
        (1, tq, _LANES), lambda j, i, t, p: (i, back(t), part * nd + j))
    halo = lambda part: pl.BlockSpec(
        (1, _HALO, _LANES),
        lambda j, i, t, p: (i, jnp.maximum(back(t) * tiles - 1, 0), part * nd + j))
    taps = pl.BlockSpec((k, _LANES), lambda j, i, t, p: (0, j))
    du, dw = pl.pallas_call(
        functools.partial(_gated_bwd_kernel, rows=ROWS),
        grid=(nd, bsz, nt, 3),
        in_specs=[block(0), block(1), block(2), halo(0), halo(2), taps,
                  pl.BlockSpec((1, tq, _LANES), lambda j, i, t, p: (i, back(t), j))],
        out_specs=[
            pl.BlockSpec((1, tq, _LANES), lambda j, i, t, p: (i, back(t), p * nd + j)),
            taps],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct((k, width // 3), _F32)],
        scratch_shapes=[pltpu.VMEM((8, _LANES), _F32),
                        pltpu.VMEM((k, 8, _LANES), _F32),
                        pltpu.VMEM((2, tq, _LANES), u.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret(),
        name="short_conv_bwd",
    )(u, u, u, u, u, w.astype(_F32).T, dy)
    return du, dw.T.astype(w.dtype)


@jax.custom_vjp
def _gated_conv(u, w):
    return _gated_fwd_call(u, w)


def _gated_conv_fwd(u, w):
    return _gated_conv(u, w), (u, w)


def _gated_conv_bwd(res, dy):
    return _gated_bwd_call(*res, dy)


_gated_conv.defvjp(_gated_conv_fwd, _gated_conv_bwd)


def gated_conv(u: jax.Array, w: jax.Array) -> jax.Array:
    """C * causal_taps(B * z, w) in u's dtype, for [B, C, z] = u [b, t, 3d]
    split in three along its columns and taps w [d, K] (tap K-1 weighs
    the token itself). `supports(t, 0, (d, d, d), K)` holds."""
    # under this scope the kernels' instructions keep their own names, as
    # `split_conv`'s do
    with jax.named_scope("short_conv_kernel"):
        return _gated_conv(u, w)
