"""The chunked scan (models/ssm.py chunked_scan) as two Pallas TPU
kernels, one call a layer and pass: a chunk's decay-weighted scores and
the state between chunks live in VMEM only.

For sequence i, chunk c and head j, with x_j [q, p], dt_j [q], cum_j [q]
the log of the decay from the chunk's start through each token (float32,
<= 0 and falling), last_j = cum_j[q - 1], S_j [p, n] the state entering
the chunk (float32, zero at the first), and G = tril(C B^T) [q, q]
float32, built once a chunk and shared by the heads:

    X_j  = dtype(x_j * dt_j)
    L_j[l, s] = exp(cum_j[l] - cum_j[s])            s <= l
    W_j  = dtype(G * L_j)
    Y_j  = W_j X_j + exp(cum_j)[:, None] * (C dtype(S_j)^T)
    R_j  = dtype(X_j * exp(last_j - cum_j)[:, None])   what each token adds by the chunk's end
    S_j' = exp(last_j) S_j + R_j^T B                `ssm_scan_fwd`

and, given dY_j and dS_j' from the chunks after (every sum float32, MXU
operands in the model's dtype), walking the chunks backwards:

    dW_j = dY_j X_j^T              dG = tril(sum_j dW_j * L_j)
    Z_j  = exp(cum_j)[:, None] * dY_j
    dR_j = B dS_j'^T               dX_j = W_j^T dY_j + dR_j * exp(last_j - cum_j)[:, None]
    dB   = dG^T C + sum_j R_j dS_j'          dC = dG B + sum_j Z_j dtype(S_j)
    dS_j = Z_j^T C + exp(last_j) dS_j'
    dlast_j[l] = rowsum(X_j * dR_j)[l] * exp(last_j - cum_j[l])      (+ exp(last_j) <dS_j', S_j>, once)
    dcum_j[l]  = rowsum(dY_j * Y_j)[l] - rowsum(X_j * dX_j)[l]
    dx_j = dX_j * dt_j             ddt_j = rowsum(dX_j * x_j)       `ssm_scan_bwd`

dcum: row l of L_j scales y_l and column l scales what token l gives the
later ones (with T_j = dW_j * G * L_j that is rowsum(T_j) - colsum(T_j),
which are rowsum(dY_j * W_j X_j) and rowsum(X_j * W_j^T dY_j): no [q, q]
product or reduction), and the same holds of the two decays outside L_j.

Above the diagonal G is zero and the decay's exponent is clamped at 0
(there it is positive and would overflow), which is the XLA form's mask
before the exp for every decay that does not grow: dt >= 0, a <= 0.

The grid is (sequence, chunk, block of HEAD_BLOCK heads): the chunks of a
sequence in order (the backward in reverse), the head axis innermost so
that G, dG, dB and dC stay in VMEM across a chunk's heads; all heads'
states (h x p x n float32, 2 MiB at 64 x 64 x 128) stay in VMEM across a
sequence's chunks. Arrays keep the token before the head ([b, t, h * p],
lane-dense), and so do the states, kept transposed ([n, h * p]) so that
no product but W_j^T dY_j takes a transposed operand (B^T and C^T are
made once a chunk). The three per-token, per-head scalars (cum, dt,
last) reach a program with tokens along lanes, one row a head (`_rows`);
a program takes its exps there, turns the block into columns with one
transpose, and the backward hands the gradients back the same way. What
reaches HBM: x, B, C, those rows, y, and each chunk's entering state
(float32, the backward's one residual beside the inputs); no
[b, c, h, q, q] tensor, no head-before-token copy of y, no x * dt, no
chunk's end state. Within a chunk the tokens are taken in bands of 128,
each against the columns it can see: the block above the diagonal is
never built.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubedl_tpu.ops import interpret

# Heads a program. 16 are 3% faster a call and cost 2 s more of set-up: a
# step's lowering grows with the kernels' unrolled bodies (PERF.md section 6)
HEAD_BLOCK = 8
_LANES = 128
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b
_F32 = jnp.float32


def supports(heads: int, head_dim: int, state: int, chunk: int, seq: int) -> bool:
    """Whether the kernels take these shapes: whole (8, 128) tiles in every
    block, and a sequence of at least one chunk."""
    return (chunk % _LANES == 0 and state % _LANES == 0 and seq >= chunk
            and heads % HEAD_BLOCK == 0 and (HEAD_BLOCK * head_dim) % _LANES == 0
            and head_dim <= chunk)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _tril(scores):
    q = scores.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1)
    return jnp.where(cols <= rows, scores, 0.0)


def _transposed(a):
    """[q, n] in the model's dtype -> [n, q] (a 32-bit transpose)."""
    return a.astype(_F32).T.astype(a.dtype)


def _bands(g_ref, cum_col, cum_row):
    """A chunk's tokens in bands of 128, each with the columns it can see:
    (its rows, how many columns, L and G * L over them, [128, seen]
    float32). The block above the diagonal is never built. On the
    diagonal block G is zero above the diagonal and the exponent is
    clamped at 0 (there it is positive and would overflow)."""
    for r in range(0, g_ref.shape[0], _LANES):
        rows, seen = slice(r, r + _LANES), r + _LANES
        lam = jnp.exp(jnp.minimum(cum_col[rows] - cum_row[:, :seen], 0.0))
        yield rows, seen, lam, g_ref[rows, :seen] * lam


def _scalars(rows, hb):
    """A block's [3 * hb, q] rows (cum, dt, last) -> for each head its
    columns [q, 1] of cum, dt, exp(cum) and exp(last - cum), and the row
    [1, q] of cum."""
    cum, dt, last = (rows[i * hb:(i + 1) * hb] for i in range(3))
    groups = [cum, dt, jnp.exp(cum), jnp.exp(last - cum)]
    pad = jnp.zeros((_LANES - len(groups) * hb, rows.shape[1]), _F32)
    cols = jnp.concatenate(groups + [pad], axis=0).T  # [q, 128]
    return [tuple(cols[:, i * hb + k:i * hb + k + 1] for i in range(len(groups)))
            + (cum[k:k + 1],) for k in range(hb)]


def _through(rows, hb, p):
    """exp(last) of each head over its p lanes, [1, hb * p]: the share of
    a state that leaves the chunk."""
    last = rows[2 * hb:3 * hb]
    return jnp.exp(jnp.concatenate(
        [last[k:k + 1, :p] for k in range(hb)], axis=1))


def _fwd_kernel(x_ref, b_ref, c_ref, rows_ref, y_ref, e_ref,
                g_ref, bt_ref, s_ref, *, hb, p):
    ci, jb = pl.program_id(1), pl.program_id(2)
    c = c_ref[0]
    dtype = c.dtype

    @pl.when(jb == 0)
    def _():
        g_ref[...] = _tril(_dot(c, b_ref[0], _NT))
        bt_ref[...] = _transposed(b_ref[0])

    @pl.when(ci == 0)
    def _():
        s_ref[jb] = jnp.zeros(s_ref.shape[1:], _F32)

    rows = rows_ref[0, 0, 0]
    x, st = x_ref[0], s_ref[jb]  # the states transposed, [n, hb * p]
    e_ref[0, 0] = st
    carried = _dot(c, st.astype(dtype))  # [q, hb * p]
    ys, rs = [], []
    for k, (cc, dt, ecc, to_end, cum_row) in enumerate(_scalars(rows, hb)):
        heads = slice(k * p, (k + 1) * p)
        xdt = (x[:, heads].astype(_F32) * dt).astype(dtype)
        inside = [_dot(wf.astype(dtype), xdt[:seen])
                  for _, seen, _, wf in _bands(g_ref, cc, cum_row)]
        ys.append(jnp.concatenate(inside, axis=0) + carried[:, heads] * ecc)
        rs.append((xdt.astype(_F32) * to_end).astype(dtype))
    y_ref[0] = jnp.concatenate(ys, axis=-1)
    ends = _dot(bt_ref[...], jnp.concatenate(rs, axis=-1))  # [n, hb * p]
    s_ref[jb] = st * _through(rows, hb, p) + ends


def _bwd_kernel(x_ref, b_ref, c_ref, rows_ref, e_ref, dy_ref,
                dx_ref, db_ref, dc_ref, drows_ref, dthrough_ref,
                g_ref, dg_ref, ct_ref, db_acc_ref, dc_acc_ref, ds_ref, *, hb, p):
    ci, jb = pl.program_id(1), pl.program_id(2)
    b, c = b_ref[0], c_ref[0]
    dtype, q = c.dtype, c.shape[0]

    @pl.when(jb == 0)
    def _():
        g_ref[...] = _tril(_dot(c, b, _NT))
        ct_ref[...] = _transposed(c)
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_acc_ref[...] = jnp.zeros_like(db_acc_ref)
        dc_acc_ref[...] = jnp.zeros_like(dc_acc_ref)

    @pl.when(ci == 0)  # the sequence's last chunk: nothing comes after
    def _():
        ds_ref[jb] = jnp.zeros(ds_ref.shape[1:], _F32)

    rows = rows_ref[0, 0, 0]
    x, dy = x_ref[0], dy_ref[0]
    st, dst = e_ref[0, 0], ds_ref[jb]  # S and dS' transposed, [n, hb * p]
    e, ds_r = st.astype(dtype), dst.astype(dtype)
    carried = _dot(c, e)  # [q, hb * p]
    drs = _dot(b, ds_r)   # [q, hb * p]
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, _LANES), 1)
    dcols = jnp.zeros((q, _LANES), _F32)
    dxs, zs, rs = [], [], []
    for k, (cc, dt, ecc, to_end, cum_row) in enumerate(_scalars(rows, hb)):
        heads = slice(k * p, (k + 1) * p)
        x_k = x[:, heads].astype(_F32)
        xdt = (x_k * dt).astype(dtype)
        xdt32 = xdt.astype(_F32)
        dy_k = dy[:, heads]
        dy_r = dy_k.astype(dtype)
        inside, back = [], []
        for band, seen, lam, wf in _bands(g_ref, cc, cum_row):
            w = wf.astype(dtype)
            inside.append(_dot(w, xdt[:seen]))
            back.append(_dot(w, dy_r[band], _TN))  # [seen, p]
            dg_ref[band, :seen] += _dot(dy_r[band], xdt[:seen], _NT) * lam
        z = dy_k * ecc
        zs.append(z.astype(dtype))
        rs.append((xdt32 * to_end).astype(dtype))
        dxs_k = drs[:, heads] * to_end  # through the chunk's end state
        dxdt = jnp.concatenate([
            sum(part[r:r + _LANES] for part in back[i:])
            for i, r in enumerate(range(0, q, _LANES))], axis=0)
        # A decay's gradient is what it scales, out less in: row l of L
        # scales y_l, column l what token l gives the later ones. Both
        # sums run over the same products dY W X (the cotangent as the MXU
        # took it), so that what cancels between them does.
        dlast = jnp.sum(xdt32 * dxs_k, axis=1, keepdims=True)
        dcum = jnp.sum(
            dy_r.astype(_F32) * jnp.concatenate(inside, axis=0) - xdt32 * dxdt
            + z * carried[:, heads], axis=1, keepdims=True) - dlast
        dxdt = dxdt + dxs_k
        dxs.append((dxdt * dt).astype(dx_ref.dtype))
        ddt = jnp.sum(dxdt * x_k, axis=1, keepdims=True)
        for i, col in enumerate((dcum, ddt, dlast)):
            dcols = jnp.where(lane == i * hb + k, col, dcols)
    dx_ref[0] = jnp.concatenate(dxs, axis=-1)
    drows_ref[0, 0, 0] = dcols.T[:3 * hb]
    # <dS', S> over the state's n, a head's p lanes left to be summed outside
    dthrough_ref[0, 0] = jnp.sum(dst * st, axis=0, keepdims=True)
    z = jnp.concatenate(zs, axis=-1)  # [q, hb * p]
    dc_acc_ref[...] += _dot(z, e, _NT)
    db_acc_ref[...] += _dot(jnp.concatenate(rs, axis=-1), ds_r, _NT)
    ds_ref[jb] = _dot(ct_ref[...], z) + dst * _through(rows, hb, p)

    @pl.when(jb == pl.num_programs(2) - 1)
    def _():
        dg = _tril(dg_ref[...]).astype(dtype)
        dc_ref[0] = (dc_acc_ref[...] + _dot(dg, b)).astype(dc_ref.dtype)
        db_ref[0] = (db_acc_ref[...] + _dot(dg, c, _TN)).astype(db_ref.dtype)


def _rows(cum, dt, last, nc, hb):
    """Three [b, t, h] -> [b, chunks, h / hb, 3 * hb, q]: a block of heads'
    cum, then dt, then last, each head a row with its chunk along lanes."""
    bsz, t, h = cum.shape
    v = jnp.stack([cum, dt, last], axis=1).reshape(bsz, 3, nc, t // nc, h // hb, hb)
    return v.transpose(0, 2, 4, 1, 5, 3).reshape(bsz, nc, h // hb, 3 * hb, t // nc)


def _unrows(rows, shape):
    """`_rows` backwards: the three [b, t, h] of a [b, chunks, h / hb, 3 * hb, q]."""
    bsz, nc, blocks, r, q = rows.shape
    v = rows.reshape(bsz, nc, blocks, 3, r // 3, q).transpose(0, 3, 1, 5, 2, 4)
    return tuple(v[:, i].reshape(shape) for i in range(3))


def _sizes(x, cum, b_, chunk):
    bsz, t, h = cum.shape
    assert t % chunk == 0 and x.shape[:2] == b_.shape[:2] == (bsz, t)
    return bsz, t // chunk, chunk, h, x.shape[-1] // h, b_.shape[-1]


def _specs(q, n, p, hb, chunk_of):
    """BlockSpecs of x, B, C and the rows, over (i, c, j)."""
    at = chunk_of
    return [
        pl.BlockSpec((1, q, hb * p), lambda i, c, j: (i, at(c), j)),
        pl.BlockSpec((1, q, n), lambda i, c, j: (i, at(c), 0)),
        pl.BlockSpec((1, q, n), lambda i, c, j: (i, at(c), 0)),
        pl.BlockSpec((1, 1, 1, 3 * hb, q), lambda i, c, j: (i, at(c), j, 0, 0)),
    ]


def _state_spec(rows, p, hb, chunk_of):
    """A block of heads' [rows, hb * p] of a [chunks, b, rows, h * p]."""
    return pl.BlockSpec((1, 1, rows, hb * p),
                        lambda i, c, j: (chunk_of(c), i, 0, j))


_PARAMS = dict(dimension_semantics=("parallel", "arbitrary", "arbitrary"),
               vmem_limit_bytes=64 << 20)


# one trace and one lowering for all of a step's calls (a bare pallas_call
# site is traced and lowered again before the compile cache is asked)
@functools.partial(jax.jit, static_argnames="chunk")
def _fwd_call(x, dt, cum, last, b_, c_, *, chunk):
    bsz, nc, q, h, p, n = _sizes(x, cum, b_, chunk)
    hb = HEAD_BLOCK
    in_order = lambda c: c
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, p=p),
        grid=(bsz, nc, h // hb),
        in_specs=_specs(q, n, p, hb, in_order),
        out_specs=[pl.BlockSpec((1, q, hb * p), lambda i, c, j: (i, c, j)),
                   _state_spec(n, p, hb, in_order)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct((nc, bsz, n, h * p), _F32)],
        scratch_shapes=[pltpu.VMEM((q, q), _F32), pltpu.VMEM((n, q), b_.dtype),
                        pltpu.VMEM((h // hb, n, hb * p), _F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret(),
        name="ssm_scan_fwd",
    )(x, b_, c_, _rows(cum, dt, last, nc, hb))


@functools.partial(jax.jit, static_argnames="chunk")
def _bwd_call(x, dt, cum, last, b_, c_, entering, dy, *, chunk):
    bsz, nc, q, h, p, n = _sizes(x, cum, b_, chunk)
    hb = HEAD_BLOCK
    backwards = lambda c: nc - 1 - c
    specs = _specs(q, n, p, hb, backwards)
    dx, db, dc, drows, dthrough = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, p=p),
        grid=(bsz, nc, h // hb),
        in_specs=specs + [_state_spec(n, p, hb, backwards), specs[0]],
        out_specs=specs + [_state_spec(1, p, hb, backwards)],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(b_.shape, b_.dtype),
            jax.ShapeDtypeStruct(c_.shape, c_.dtype),
            jax.ShapeDtypeStruct((bsz, nc, h // hb, 3 * hb, q), _F32),
            jax.ShapeDtypeStruct((nc, bsz, 1, h * p), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((q, q), _F32), pltpu.VMEM((q, q), _F32),
                        pltpu.VMEM((n, q), c_.dtype),
                        pltpu.VMEM((q, n), _F32), pltpu.VMEM((q, n), _F32),
                        pltpu.VMEM((h // hb, n, hb * p), _F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret(),
        name="ssm_scan_bwd",
    )(x, b_, c_, _rows(cum, dt, last, nc, hb), entering, dy)
    dcum, ddt, dlast = _unrows(drows, cum.shape)
    # `last` is one number a chunk and head, handed in along the chunk's
    # tokens: its gradient is summed over them outside, so what the
    # leaving state carries, exp(last) <dS', S>, rides the first
    carries = (dthrough.reshape(nc, bsz, h, p).sum(-1).transpose(1, 0, 2)
               * jnp.exp(last.reshape(bsz, nc, q, h)[:, :, 0]))
    dlast = dlast.reshape(bsz, nc, q, h).at[:, :, 0].add(carries).reshape(cum.shape)
    return dx, ddt, dcum, dlast, db, dc


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, cum, last, b_, c_, chunk):
    return _fwd_call(x, dt, cum, last, b_, c_, chunk=chunk)[0]


def _scan_fwd(x, dt, cum, last, b_, c_, chunk):
    y, entering = _fwd_call(x, dt, cum, last, b_, c_, chunk=chunk)
    return y, (x, dt, cum, last, b_, c_, entering)


def _scan_bwd(chunk, res, dy):
    return _bwd_call(*res, dy, chunk=chunk)


_scan.defvjp(_scan_fwd, _scan_bwd)


def scan(x: jax.Array, dt: jax.Array, cum: jax.Array, b_: jax.Array,
         c_: jax.Array, chunk: int) -> jax.Array:
    """y [b, t, h * p] float32 of the scan over chunks of `chunk` tokens.

    x [b, t, h * p] and b_, c_ [b, t, n] in the model's dtype; dt [b, t, h]
    float32; cum [b, t, h] float32, the cumulative sum of dt * a inside
    each chunk. t is a whole number of chunks, and `supports` holds of the
    shapes."""
    bsz, t, h = cum.shape
    ends = cum.reshape(bsz, t // chunk, chunk, h)[:, :, -1:]
    last = jnp.broadcast_to(ends, (bsz, t // chunk, chunk, h)).reshape(cum.shape)
    # A kernel's HLO instruction takes the innermost name on the stack:
    # under this scope that is its own name= (%ssm_scan_fwd.N), where a
    # bare jax.grad would wrap it (flash_attention has the same).
    with jax.named_scope("ssm_scan_kernel"):
        return _scan(x, dt, cum, last, b_, c_, chunk)
