"""The hyper-connections' passes over the streams (models/hyper.py) as four
Pallas TPU kernels: each reads the bfloat16 streams once and writes them
once, and keeps every float32 value in VMEM.

The streams of a sublayer are X [b, t, n*d], stream j at lanes j*d .. ,
in the model's dtype. For a token, with P = [P_pre | P_post | P_res]
[n*d, m], m = n (n + 2), float32:

    inv     = rsqrt(sum(X^2) / (n d) + eps)
    L       = (X P) inv                 the m logits       } `hc_pre_fwd`
    H_pre   = sigmoid(a_pre L[:n] + b_pre)                 }
    u       = dtype(sum_j H_pre[j] X[j])                   }
    X'[i]   = dtype(H_post[i] y + sum_j H_res[i, j] X[j])    `hc_post_fwd`

H_post and H_res come from L by the caller's `maps_of` (models/hyper.py:
two sigmoids and Sinkhorn-Knopp over [b, t, n (+ n)] values, XLA
operations inside `pre`) and enter `hc_post_fwd` as one [b, t, 128] array,
H_res row by row and H_post after it. Given dX':

    dy = sum_i H_post[i] dX'[i]
    dH_res[i, j] = <dX'[i], X[j]>           dH_post[i] = <dX'[i], y>       `hc_post_bwd`

and, given du, dL from H_res's and H_post's cotangents (the VJP of
`maps_of`) and dX':

    dz   = <du, X[j]> H_pre (1 - H_pre)       dL[:n] += a_pre dz
    c    = inv^2 / (n d) sum_k dL_k L_k
    dX[j] = sum_i H_res[i, j] dX'[i] + H_pre[j] du + ((dL inv) P^T)[j] - c X[j]   `hc_pre_bwd`
    dP   = X^T (dL inv)    d a_pre = sum dz L[:n]    d b_pre = sum dz

so dX is summed in float32 and rounded once, and no pass writes a share of
it. To that end `pre` hands X on as an output of its own, which `post`
alone reads, and `post`'s backward hands dX' on as that output's cotangent
unchanged: `pre`'s backward applies H_res's transpose.

Precision: X is exact in bfloat16, so the projection's products are exact
against P split into three bfloat16 parts (hi + mid + lo = P), in one MXU
pass of 3m <= 128 columns, summed in float32: the float32 product at
`HIGHEST`. dP likewise against dL inv split in three. The backward's
(dL inv) P^T is hi.hi + hi.mid + mid.hi of two-part splits, float32
to about 2^-16, under the bfloat16 rounding of dX. Everything else is
float32 and rounded to the model's dtype once.

A program holds TOKEN_BLOCK tokens of one sequence. Its body loops over
sub-tiles of ROWS tokens by LANES lanes (the products over LANES lanes and
all of the block's tokens, for the MXU), so a body's code is the size of
a sub-tile while the DMA block stays large; each pass's arithmetic
is a jitted helper, traced once for every loop that calls it. Each kernel
is called through one jitted wrapper: Mosaic lowers it once for every
mapping of a step.
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubedl_tpu.ops import interpret

# Tokens a program (a sequence that is no multiple takes the largest
# divisor that is a multiple of ROWS), token rows and lanes a pass of a
# body's loops (a product's pass takes all of the block's tokens)
TOKEN_BLOCK = 256
ROWS = 32
LANES = 512
_LANE = 128
_F32 = jnp.float32
_VMEM = 100 << 20


def supports(seq: int, n: int, d: int) -> bool:
    """Whether the kernels take these shapes: a stream of whole 128-lane
    blocks, a sequence of whole ROWS-token tiles, and the three parts of
    the n (n + 2) logits within one 128-lane block."""
    return (n >= 2 and seq > 0 and seq % ROWS == 0 and d > 0 and d % _LANE == 0
            and 3 * n * (n + 2) <= _LANE)


def _block(size: int, cap: int, unit: int) -> int:
    """The largest multiple of `unit` up to `cap` that divides `size`."""
    return next(q for q in range(min(cap, size) // unit * unit, 0, -unit)
                if size % q == 0)


def _up8(k: int) -> int:
    return -(-k // 8) * 8


def _fold(v):
    """[rows, lanes] -> [rows, 128]: the sum of its 128-lane pieces."""
    out = v[:, :_LANE]
    for at in range(_LANE, v.shape[1], _LANE):
        out = out + v[:, at:at + _LANE]
    return out


def _columns(sums):
    """[rows, 128] partial sums -> [rows, 128] whose lane k holds the sum
    of sums[k]'s lanes."""
    col = jax.lax.broadcasted_iota(jnp.int32, sums[0].shape, 1)
    out = jnp.zeros(sums[0].shape, _F32)
    for k, s in enumerate(sums):
        out = jnp.where(col == k, jnp.sum(s, axis=1, keepdims=True), out)
    return out


def _sigmoid(v):
    return 1.0 / (1.0 + jnp.exp(-v))


def _rows(i):
    return pl.ds(pl.multiple_of(i * ROWS, ROWS), ROWS)


def _at(start, size):
    return pl.ds(pl.multiple_of(start, size), size)


# --- the passes' arithmetic, each traced once -----------------------------

@functools.partial(jax.jit, static_argnames=("n", "nd", "eps"))
def _pre_maps(r, ss, ab, *, n, nd, eps):
    """The projection's three parts [tb, 128] and the lane partials of the
    squares [tb, 128] -> (lanes < m the logits, lane m inv; H_pre)."""
    m = n * (n + 2)
    raw = r + pltpu.roll(r, _LANE - m, 1) + pltpu.roll(r, _LANE - 2 * m, 1)
    inv = jax.lax.rsqrt(jnp.sum(ss, axis=1, keepdims=True) / nd + eps)
    col = jax.lax.broadcasted_iota(jnp.int32, r.shape, 1)
    logits = jnp.where(col < m, raw * inv, jnp.where(col == m, inv, 0.0))
    return logits, _sigmoid(ab[0:1] * logits + ab[1:2])


@jax.jit
def _squares_pass(v, s):
    v = v.astype(_F32)
    return s + _fold(v * v)


@functools.partial(jax.jit, static_argnames="dtype")
def _pre_pass(p, xs, *, dtype):
    acc = p[:, 0:1] * xs[0].astype(_F32)
    for j in range(1, len(xs)):
        acc = acc + p[:, j:j + 1] * xs[j].astype(_F32)
    return acc.astype(dtype)


@functools.partial(jax.jit, static_argnames="dtype")
def _post_pass(coef, xs, y, *, dtype):
    n = len(xs)
    xs, y = [v.astype(_F32) for v in xs], y.astype(_F32)
    out = []
    for i in range(n):
        acc = coef[:, n * n + i:n * n + i + 1] * y
        for j in range(n):
            acc = acc + coef[:, i * n + j:i * n + j + 1] * xs[j]
        out.append(acc.astype(dtype))
    return out


@functools.partial(jax.jit, static_argnames="dtype")
def _post_bwd_pass(coef, gs, xs, y, sums, *, dtype):
    """A pass's dy and the running lane sums of dH_res (i n + j) and
    dH_post (n n + i)."""
    n = len(xs)
    gs, xs, y = [v.astype(_F32) for v in gs], [v.astype(_F32) for v in xs], y.astype(_F32)
    dy = coef[:, n * n:n * n + 1] * gs[0]
    for i in range(1, n):
        dy = dy + coef[:, n * n + i:n * n + i + 1] * gs[i]
    new = [sums[i * n + j] + _fold(gs[i] * xs[j]) for i in range(n) for j in range(n)]
    new += [sums[n * n + i] + _fold(gs[i] * y) for i in range(n)]
    return dy.astype(dtype), new


@jax.jit
def _dots_pass(du, xs, sums):
    du = du.astype(_F32)
    return [s + _fold(du * x.astype(_F32)) for s, x in zip(sums, xs)]


@functools.partial(jax.jit, static_argnames=("n", "nd"))
def _pre_bwd_maps(red, lp, dl, ab, *, n, nd):
    """A block's per-token backward from <du, X_j> (lanes < n), the saved
    logits and inv, and dL: the two bf16 left operands of the products
    ([tb, 128] against P^T's parts, [3m, tb] against X), d a_pre's and
    d b_pre's sums over the block's tokens (two [1, 128] rows), H_pre
    [tb, 128] and -c [tb, 1]."""
    m = n * (n + 2)
    col = jax.lax.broadcasted_iota(jnp.int32, lp.shape, 1)
    logits = jnp.where(col < m, lp, 0.0)
    inv = jnp.sum(jnp.where(col == m, lp, 0.0), axis=1, keepdims=True)
    pre = _sigmoid(ab[0:1] * logits + ab[1:2])
    dz = jnp.where(col < n, red * pre * (1.0 - pre), 0.0)
    dlt = jnp.where(col < m, dl, 0.0) + ab[0:1] * dz
    c = inv * inv / nd * jnp.sum(dlt * logits, axis=1, keepdims=True)
    g = dlt * inv
    bf = lambda v: v.astype(jnp.bfloat16).astype(_F32)
    hi = bf(g)
    mid = bf(g - hi)
    lo = bf(g - hi - mid)

    def parts(a, b_, c_):  # a at lanes 0.., b_ at m.., c_ at 2m..
        return jnp.where(col < m, a, jnp.where(col < 2 * m, pltpu.roll(b_, m, 1), jnp.where(
            col < 3 * m, pltpu.roll(c_, 2 * m, 1), 0.0)))

    g3 = parts(hi, hi, mid).astype(jnp.bfloat16)
    gdt = parts(hi, mid, lo).T[:_up8(3 * m)].astype(jnp.bfloat16)
    return (g3, gdt, jnp.sum(dz * logits, axis=0, keepdims=True),
            jnp.sum(dz, axis=0, keepdims=True), pre, -c)


@functools.partial(jax.jit, static_argnames="dtype")
def _pre_bwd_pass(gs, du, ps, xs, res, pjs, negc, *, dtype):
    """A pass's dX[j] = sum_i H_res[i, j] dX'[i] + H_pre[j] du
    + (dL inv P^T)[j] - c X[j]; res[i][j] and the rest lane-broadcast."""
    n = len(xs)
    gs, du, negc = [g.astype(_F32) for g in gs], du.astype(_F32), negc[:, 0:1]
    out = []
    for j in range(n):
        acc = res[0][j][:, 0:1] * gs[0]
        for i in range(1, n):
            acc = acc + res[i][j][:, 0:1] * gs[i]
        acc = acc + pjs[j][:, 0:1] * du + (ps[j] + negc * xs[j].astype(_F32))
        out.append(acc.astype(dtype))
    return out


# --- the kernels ----------------------------------------------------------

def _pre_fwd_kernel(x_ref, w_ref, ab_ref, u_ref, l_ref, acc_ref, ss_ref, pre_ref,
                    *, n, eps):
    _, tb, nd = x_ref.shape
    d, dtype = nd // n, x_ref.dtype
    ln = _block(d, LANES, _LANE)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    ss_ref[...] = jnp.zeros_like(ss_ref)

    # a slice of every token's row, loaded once for its products and squares
    def product(c, carry):
        at = _at(c * ln, ln)
        xs = x_ref[0, :, at]
        acc_ref[...] += jnp.dot(xs, w_ref[at, :], preferred_element_type=_F32)
        ss_ref[...] = _squares_pass(xs, ss_ref[...])
        return carry

    jax.lax.fori_loop(0, nd // ln, product, 0)
    l_ref[0], pre_ref[...] = _pre_maps(acc_ref[...], ss_ref[...], ab_ref[...],
                                       n=n, nd=nd, eps=eps)

    def mix(i, carry):
        rows = _rows(i)
        p = pre_ref[rows, :]

        def lanes(c, carry):
            at = c * ln
            u_ref[0, rows, _at(at, ln)] = _pre_pass(
                p, [x_ref[0, rows, _at(j * d + at, ln)] for j in range(n)], dtype=dtype)
            return carry

        return jax.lax.fori_loop(0, d // ln, lanes, carry)

    jax.lax.fori_loop(0, tb // ROWS, mix, 0)


def _post_fwd_kernel(x_ref, y_ref, m_ref, o_ref, *, n):
    _, tb, nd = x_ref.shape
    d = nd // n
    ln = _block(d, LANES, _LANE)

    def mix(i, carry):
        rows = _rows(i)
        coef = m_ref[0, rows, :]

        def lanes(c, carry):
            at = c * ln
            outs = _post_pass(coef, [x_ref[0, rows, _at(j * d + at, ln)] for j in range(n)],
                              y_ref[0, rows, _at(at, ln)], dtype=o_ref.dtype)
            for i2, out in enumerate(outs):
                o_ref[0, rows, _at(i2 * d + at, ln)] = out
            return carry

        return jax.lax.fori_loop(0, d // ln, lanes, carry)

    jax.lax.fori_loop(0, tb // ROWS, mix, 0)


def _post_bwd_kernel(g_ref, x_ref, y_ref, m_ref, dy_ref, dm_ref, acc_ref, *, n):
    _, tb, nd = x_ref.shape
    d, k = nd // n, n * n + n
    ln = _block(d, LANES, _LANE)

    def mix(i, carry):
        rows = _rows(i)
        coef = m_ref[0, rows, :]
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def lanes(c, carry):
            at = c * ln
            dy, sums = _post_bwd_pass(
                coef, [g_ref[0, rows, _at(j * d + at, ln)] for j in range(n)],
                [x_ref[0, rows, _at(j * d + at, ln)] for j in range(n)],
                y_ref[0, rows, _at(at, ln)], [acc_ref[q] for q in range(k)],
                dtype=dy_ref.dtype)
            dy_ref[0, rows, _at(at, ln)] = dy
            for q, s in enumerate(sums):
                acc_ref[q] = s
            return carry

        jax.lax.fori_loop(0, d // ln, lanes, 0)
        dm_ref[0, rows, :] = _columns([acc_ref[q] for q in range(k)])
        return carry

    jax.lax.fori_loop(0, tb // ROWS, mix, 0)


def _pre_bwd_kernel(x_ref, du_ref, dl_ref, l_ref, m_ref, ab_ref, wt_ref, g_ref,
                    dx_ref, dwt_ref, dab_ref, red_ref, p_ref, cols_ref, *, n):
    _, tb, nd = x_ref.shape
    d, dtype = nd // n, dx_ref.dtype
    ln = _block(d, LANES, _LANE)

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dwt_ref[...] = jnp.zeros_like(dwt_ref)
        dab_ref[...] = jnp.zeros_like(dab_ref)

    def dots(i, carry):
        rows = _rows(i)
        sums = jax.lax.fori_loop(
            0, d // ln, lambda c, s: _dots_pass(
                du_ref[0, rows, _at(c * ln, ln)],
                [x_ref[0, rows, _at(j * d + c * ln, ln)] for j in range(n)], s),
            [jnp.zeros((ROWS, _LANE), _F32) for _ in range(n)])
        red_ref[rows, :] = _columns(sums)
        return carry

    jax.lax.fori_loop(0, tb // ROWS, dots, 0)
    g3, gdt, da, db, pre, negc = _pre_bwd_maps(red_ref[...], l_ref[0], dl_ref[0],
                                               ab_ref[...], n=n, nd=nd)
    dab_ref[0:1, :] += da
    dab_ref[1:2, :] += db
    # the per-token scalars of the last loop, broadcast over lanes once:
    # H_pre[j], -c, H_res[i, j]
    maps = m_ref[0]
    for j in range(n):
        cols_ref[j] = jnp.broadcast_to(pre[:, j:j + 1], pre.shape)
    cols_ref[n] = jnp.broadcast_to(negc, pre.shape)
    for k in range(n * n):
        cols_ref[n + 1 + k] = jnp.broadcast_to(maps[:, k:k + 1], pre.shape)

    def lanes(c, carry):
        at = c * ln
        streams = [_at(j * d + at, ln) for j in range(n)]
        for j, cs in enumerate(streams):
            p_ref[j] = jnp.dot(g3, wt_ref[:, cs], preferred_element_type=_F32)
            dwt_ref[:, cs] += jnp.dot(gdt, x_ref[0, :, cs], preferred_element_type=_F32)

        def rows_of(i, carry):
            rows = _rows(i)
            dxs = _pre_bwd_pass(
                [g_ref[0, rows, cs] for cs in streams], du_ref[0, rows, _at(at, ln)],
                [p_ref[j, rows, :] for j in range(n)], [x_ref[0, rows, cs] for cs in streams],
                [[cols_ref[n + 1 + i * n + j, rows, :] for j in range(n)] for i in range(n)],
                [cols_ref[j, rows, :] for j in range(n)], cols_ref[n, rows, :], dtype=dtype)
            for cs, dx in zip(streams, dxs):
                dx_ref[0, rows, cs] = dx
            return carry

        return jax.lax.fori_loop(0, tb // ROWS, rows_of, carry)

    jax.lax.fori_loop(0, d // ln, lanes, 0)


# --- one jitted wrapper a kernel: one trace and one lowering for all calls --

def _grid(x):
    b, t, _ = x.shape
    tb = _block(t, TOKEN_BLOCK, ROWS)
    return tb, (b, t // tb)


def _tokens(tb, width):
    return pl.BlockSpec((1, tb, width), lambda i, k: (i, k, 0))


def _whole(shape):
    return pl.BlockSpec(shape, lambda i, k: (0,) * len(shape))


def _params(semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=_VMEM)


@functools.partial(jax.jit, static_argnames=("n", "eps"))
def _pre_fwd_call(x, w3, ab, *, n, eps):
    b, t, nd = x.shape
    tb, grid = _grid(x)
    return pl.pallas_call(
        functools.partial(_pre_fwd_kernel, n=n, eps=eps),
        grid=grid,
        in_specs=[_tokens(tb, nd), _whole(w3.shape), _whole(ab.shape)],
        out_specs=[_tokens(tb, nd // n), _tokens(tb, _LANE)],
        out_shape=[jax.ShapeDtypeStruct((b, t, nd // n), x.dtype),
                   jax.ShapeDtypeStruct((b, t, _LANE), _F32)],
        scratch_shapes=[pltpu.VMEM((tb, _LANE), _F32)] * 3,
        compiler_params=_params(("parallel", "parallel")),
        interpret=interpret(),
        name="hc_pre_fwd",
    )(x, w3, ab)


@functools.partial(jax.jit, static_argnames="n")
def _post_fwd_call(x, y, maps, *, n):
    tb, grid = _grid(x)
    return pl.pallas_call(
        functools.partial(_post_fwd_kernel, n=n),
        grid=grid,
        in_specs=[_tokens(tb, x.shape[2]), _tokens(tb, y.shape[2]), _tokens(tb, _LANE)],
        out_specs=_tokens(tb, x.shape[2]),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_params(("parallel", "parallel")),
        interpret=interpret(),
        name="hc_post_fwd",
    )(x, y, maps)


@functools.partial(jax.jit, static_argnames="n")
def _post_bwd_call(g, x, y, maps, *, n):
    tb, grid = _grid(x)
    nd, d = x.shape[2], y.shape[2]
    return pl.pallas_call(
        functools.partial(_post_bwd_kernel, n=n),
        grid=grid,
        in_specs=[_tokens(tb, nd), _tokens(tb, nd), _tokens(tb, d), _tokens(tb, _LANE)],
        out_specs=[_tokens(tb, d), _tokens(tb, _LANE)],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(maps.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((n * n + n, ROWS, _LANE), _F32)],
        compiler_params=_params(("parallel", "parallel")),
        interpret=interpret(),
        name="hc_post_bwd",
    )(g, x, y, maps)


@functools.partial(jax.jit, static_argnames="n")
def _pre_bwd_call(x, du, dl, lp, maps, ab, wt3, g, *, n):
    tb, grid = _grid(x)
    nd, d = x.shape[2], du.shape[2]
    return pl.pallas_call(
        functools.partial(_pre_bwd_kernel, n=n),
        grid=grid,
        in_specs=[_tokens(tb, nd), _tokens(tb, d), _tokens(tb, _LANE), _tokens(tb, _LANE),
                  _tokens(tb, _LANE), _whole(ab.shape), _whole(wt3.shape), _tokens(tb, nd)],
        out_specs=[_tokens(tb, nd), _whole((_up8(3 * n * (n + 2)), nd)), _whole((8, _LANE))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((_up8(3 * n * (n + 2)), nd), _F32),
                   jax.ShapeDtypeStruct((8, _LANE), _F32)],
        scratch_shapes=[pltpu.VMEM((tb, _LANE), _F32),
                        pltpu.VMEM((n, tb, _block(d, LANES, _LANE)), _F32),
                        pltpu.VMEM((n + 1 + n * n, tb, _LANE), _F32)],
        input_output_aliases={7: 0},  # dX' becomes dX
        # dP and the two scalars' sums build up over every block
        compiler_params=_params(("arbitrary", "arbitrary")),
        interpret=interpret(),
        name="hc_pre_bwd",
    )(x, du, dl, lp, maps, ab, wt3, g)


# --- the differentiable passes --------------------------------------------

def _split(w):
    """w [nd, m] float32 -> three bfloat16 parts whose sum is w."""
    bf = lambda v: v.astype(jnp.bfloat16)
    hi = bf(w)
    mid = bf(w - hi.astype(_F32))
    lo = bf(w - hi.astype(_F32) - mid.astype(_F32))
    return hi, mid, lo


def _pad_lanes(v):
    return jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, _LANE - v.shape[-1])])


def _coefs(a, b):
    """a_pre and b_pre as the kernels read them: [2, 128], zero past n."""
    return _pad_lanes(jnp.stack([jnp.broadcast_to(a, b.shape), b]).astype(_F32))


def _packed(post, res):
    """H_res row by row, then H_post: [bsz, t, 128] float32, as the kernels
    read them."""
    bsz, t, n = post.shape
    return _pad_lanes(jnp.concatenate([res.reshape(bsz, t, n * n), post], axis=-1))


def _pre_kernel(x, w, a, b, eps):
    hi, mid, lo = _split(w)
    return _pre_fwd_call(x, _pad_lanes(jnp.concatenate([hi, mid, lo], axis=1)),
                         _coefs(a, b), n=b.shape[0], eps=eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _pre(x, w, a, b, params, maps_of, eps):
    u, lp = _pre_kernel(x, w, a, b, eps)
    post, res = maps_of(lp[..., :w.shape[1]], params)
    return u, lp, post, res, x


def _pre_fwd(x, w, a, b, params, maps_of, eps):
    u, lp = _pre_kernel(x, w, a, b, eps)
    # the mappings' VJP kept, so that the backward runs no Sinkhorn forward
    (post, res), maps_vjp = jax.vjp(maps_of, lp[..., :w.shape[1]], params)
    return (u, lp, post, res, x), (x, w, a, b, lp, post, res, maps_vjp)


def _pre_bwd(maps_of, eps, saved, cotangents):
    x, w, a, b, lp, post, res, maps_vjp = saved
    du, dl, dpost, dres, g = cotangents
    n, m = b.shape[0], w.shape[1]
    dl_maps, dparams = maps_vjp((dpost, dres))
    hi, mid, _ = _split(w)
    wt3 = jnp.pad(jnp.concatenate([hi, mid, hi], axis=1).T, ((0, _LANE - 3 * m), (0, 0)))
    dx, dwt, dab = _pre_bwd_call(x, du, dl + _pad_lanes(dl_maps), lp, _packed(post, res),
                                 _coefs(a, b), wt3, g, n=n)
    dw = (dwt[:m] + dwt[m:2 * m] + dwt[2 * m:3 * m]).T
    return (dx, dw, jnp.sum(dab[0, :n]).astype(a.dtype), dab[1, :n].astype(b.dtype),
            dparams)


_pre.defvjp(_pre_fwd, _pre_bwd)


@jax.custom_vjp
def _post(x, y, maps):
    return _post_fwd_call(x, y, maps, n=x.shape[2] // y.shape[2])


def _post_fwd(x, y, maps):
    return _post(x, y, maps), (x, y, maps)


def _post_bwd(saved, g):
    x, y, maps = saved
    dy, dmaps = _post_bwd_call(g, x, y, maps, n=x.shape[2] // y.shape[2])
    # dX' itself in the place of X's cotangent: `pre`'s backward, which
    # alone receives it, applies H_res's transpose there
    return g, dy, dmaps


_post.defvjp(_post_fwd, _post_bwd)


# A kernel's HLO instruction takes the innermost name on the stack: under
# this scope that is its own name= (%hc_pre_fwd.N), where a bare jax.grad
# would wrap it (ops/ssm_scan.py has the same).

def pre(x: jax.Array, w: jax.Array, a: jax.Array, b: jax.Array, params,
        maps_of: Callable, eps: float) -> Tuple[jax.Array, ...]:
    """For streams x [bsz, t, n*d] and P = w [n*d, m] float32, a_pre a
    scalar and b_pre = b [n]: (u [bsz, t, d] in x's dtype; [bsz, t, 128]
    float32 holding the m logits x~ P at lanes < m and inv at lane m;
    H_post [bsz, t, n] and H_res [bsz, t, n, n] = maps_of(logits, params);
    x itself, for `post` to read and for nothing else: its cotangent is to
    be `post`'s dX'). `maps_of` is a hashable function of the logits and
    the pytree `params`, of XLA operations. `supports` holds of the
    shapes."""
    with jax.named_scope("hc_kernel"):
        return _pre(x, w, a, b, params, maps_of, eps)


def post(x: jax.Array, y: jax.Array, post_: jax.Array, res: jax.Array) -> jax.Array:
    """X'[i] = H_post[i] y + sum_j H_res[i, j] X[j] for the streams x
    (`pre`'s last output), the sublayer's output y [bsz, t, d], H_post
    [bsz, t, n] and H_res [bsz, t, n, n] float32 (`pre`'s)."""
    maps = _packed(post_, res)
    with jax.named_scope("hc_kernel"):
        return _post(x, y, maps)
