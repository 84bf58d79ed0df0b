"""Flash attention — Pallas TPU kernels (forward + backward).

The hot op of the flagship model (SURVEY.md §7 step 9). Blocked online-softmax
attention: Q blocks stream against K/V blocks held in VMEM, accumulating in
f32 while inputs stay bf16 so the QK^T and PV matmuls hit the MXU; the
backward pass recomputes P from the saved log-sum-exp instead of
materializing [T, T] attention weights (memory O(T) per block, the property
ring attention builds on — ops/ring_attention.py).

Layout: [batch*heads, seq, head_dim]. The public entry handles GQA by
broadcasting KV heads, pads ragged sequence lengths to block multiples, and
installs a custom VJP wiring the two kernels together.

q and k share one head size and v may have another (latent attention:
keys of 192, values of 128): q, k, dq and dk ride at the q/k width, v, o,
do, dv and the forward's accumulator at the value width, each padded to
its own multiple of 128 lanes. Equal widths are the one-width kernels
they always were.

Each kernel reads a block's fate off its position (`_segments`): a block
with no pair inside the causal diagonal, the window and the true length
is never visited, and `block_plan` counts for a shape the blocks that
are and those whose every pair is live. The mask has one definition,
`_block_mask`: the block's column index less one scalar of its origin,
compared with its row index, so that a program holds no
`[block_q, block_k]` integer (three of them, kept in VMEM and read back
every block, cost the forward a tenth of its time under a window:
PERF.md section 6, PR 31). The forward and `flash_bwd_dkv` mask every
block they visit; `flash_bwd_dq` runs its interior blocks through a body
with no mask, the one kernel that gained by it (PR 27).

Each `pallas_call` carries a fixed `name=`, which the device trace shows as
the event's name: `flash_fwd`, `flash_fwd_streamed`, `flash_bwd_dq`,
`flash_bwd_dkv`. The benchmark's by-name metrics read them (PERF.md section 3).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubedl_tpu.ops import interpret

# What every ledger line of the 8k cells ran. Under Q blocks of 512, K
# blocks of 256 and 128 cost 1.6 and 2.7 times as much a pair on a v5e
# (PERF.md Open question 14 (f)); a grid step costs 0.9-1.2 us before its
# first block and a block 1.1-1.8 us (`hack/probe_flash_blocks.py`).
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
# Under this length the public entry takes plain XLA attention. No ledger
# line holds the crossover: the benchmark's 512 cell runs under it, its
# 8k cells far over it (ROADMAP Speed 10).
FLASH_MIN_SEQ = 1024
# Above this sequence length the default kernel's full-K/V-in-VMEM
# BlockSpecs crowd the 16 MB scoped VMEM; the forward streams K/V blocks
# through a 3D grid instead. The backward kernels keep whole-tensor loads,
# so TRAINING beyond this length belongs to ring attention / context
# parallelism — the streamed path serves long-context inference prefill.
STREAM_MIN_SEQ = 8192
# What the whole-sequence kernels were sized for: a head's two operands
# (K and V in the forward and in `flash_bwd_dq`, Q and dO in
# `flash_bwd_dkv`) of STREAM_MIN_SEQ tokens by 128 lanes each, held twice
# over (Mosaic double-buffers a BlockSpec), leave half of the default
# 16 MB of scoped VMEM to the blocks and the loops' temporaries.
WHOLE_SEQ_BYTES = 2 * 2 * STREAM_MIN_SEQ * (128 + 128)
NEG_INF = -1e30
# `jax.ad_checkpoint.checkpoint_name`s on the forward kernel's two outputs
# under differentiation. A `jax.checkpoint` whose policy saves these names
# (models/llama.py:_remat_policy) keeps them and its backward does not run
# the forward kernel again; anywhere else the tag is the identity.
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"


# ---------------------------------------------------------------------------
# which blocks a kernel visits, and which of them need a mask
# ---------------------------------------------------------------------------

# The two inner loops: forward and dq walk K blocks under one Q block,
# dk/dv walks Q blocks under one K block.
SIDES = ("fwd_dq", "dkv")


def _segments(outer, side, *, seq_len, window, block_q, block_k, causal,
              xp=jnp):
    """The inner loop's block ranges under outer block `outer`, as
    `(start, lo, hi, stop)`: `[start, stop)` holds exactly the blocks with
    at least one live pair, `[lo, hi)` those whose every pair is live
    (inside the diagonal, the window and `seq_len`), and `[start, lo)` /
    `[hi, stop)` the blocks an edge crosses. Traced inside the kernels
    (`outer` a program id) and evaluated by `block_plan` on the host
    (`outer` an index array, `xp` numpy): one arithmetic for both."""
    zero, top = xp.zeros_like(outer), seq_len - 1
    if side == "fwd_dq":
        # rows first..last of Q: the keys live for some row and for every
        # row. The window's edge is the low edge, the diagonal the high one.
        first, last, inner = outer * block_q, (outer + 1) * block_q - 1, block_k
        some = (zero, last if causal else top)
        every = (zero, first if causal else top)
        if window is not None:
            some = (xp.maximum(first - window + 1, 0), some[1])
            every = (xp.maximum(last - window + 1, 0), every[1])
    elif side == "dkv":
        # columns first..last of K: the queries live for some column and
        # for every column. The diagonal is the low edge, the window's the
        # high one.
        first, last, inner = outer * block_k, (outer + 1) * block_k - 1, block_q
        some = (first if causal else zero, top)
        every = (last if causal else zero, top)
        if window is not None:
            some = (some[0], xp.minimum(last, top) + window - 1)
            every = (every[0], first + window - 1)
    else:
        raise ValueError(f"side must be one of {SIDES}, got {side!r}")
    start = some[0] // inner
    stop = xp.minimum(some[1], top) // inner + 1
    lo = (every[0] + inner - 1) // inner
    hi = (xp.minimum(every[1], top) + 1) // inner
    # an outer block wholly in the padded tail has no live pair, one that
    # reaches into it no interior block
    stop = xp.where(first <= top, xp.maximum(stop, start), start)
    lo = xp.clip(lo, start, stop)
    hi = xp.where(last <= top, xp.clip(hi, lo, stop), lo)
    return start, lo, hi, stop


def block_plan(seq_len, window, block_q, block_k, causal, side):
    """`(visited, interior)`: the block pairs one head's call of a kernel
    visits, and how many of them hold live pairs alone. `side` is "fwd_dq"
    (`flash_fwd`, `flash_bwd_dq`) or "dkv" (`flash_bwd_dkv`). `flash_bwd_dq`
    runs its interior blocks with no mask; the other two mask every block
    they visit. A function of the shape alone, from the kernels' own
    `_segments` in numpy: no device is touched."""
    outer = block_q if side == "fwd_dq" else block_k
    start, lo, hi, stop = _segments(
        np.arange(pl.cdiv(seq_len, outer)), side, seq_len=seq_len,
        window=window, block_q=block_q, block_k=block_k, causal=causal, xp=np)
    return int(np.sum(stop - start)), int(np.sum(hi - lo))


def _block_mask(q0, k0, *, block_q, block_k, seq_len, causal, window):
    """Which pairs of the block at origin `(q0, k0)` are live: a mask that
    broadcasts to `[block_q, block_k]`, or None where no pair of any
    visited block can be dead (full attention over whole blocks).

    Row `i` and column `j` of the block are live iff `k0 + j <= q0 + i`
    (causal) and `k0 + j > q0 + i - window`. The block's origin enters as
    one scalar taken off the `[1, block_k]` column index, which is then
    compared with the `[block_q, 1]` row index: a compare an edge and no
    `[block_q, block_k]` integer, where position tensors of that size sat
    in VMEM and were read back every block. The length is compared only
    where `seq_len` is no multiple of the block: elsewhere `_segments`
    visits no block that reaches into the padded tail (the streamed
    forward may run a Q block that lies wholly in it; the public entry
    cuts those rows off)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    rel = col - (q0 - k0)
    edges = []
    if causal:
        edges.append(rel <= row)
    if window is not None:
        edges.append(rel + window > row)
    if seq_len % block_k:
        edges.append(col < seq_len - k0)
    if seq_len % block_q:
        edges.append(row < seq_len - q0)
    return functools.reduce(jnp.logical_and, edges) if edges else None


def _where_live(mask, x, fill):
    return x if mask is None else jnp.where(mask, x, fill)


def _whole_seq_params(seq: int, d_qk: int, d_v: int):
    """Compiler parameters of a whole-sequence kernel at these (padded)
    widths: none (the default scoped VMEM) where the two operands fit
    what the kernels were sized for, and beyond that (q and k of 256
    lanes at 8,192 tokens) a limit that grows by what they hold more."""
    held = 2 * 2 * seq * (d_qk + d_v)
    if held <= WHOLE_SEQ_BYTES:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=16 * 2**20 + held - WHOLE_SEQ_BYTES)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _softcap_scores(s, cap):
    """cap * tanh(s / cap) — Gemma-2 logit softcapping, the ONE place
    the transform lives. Backward sites derive its gradient from the
    CAPPED value: d/ds = 1 - tanh(s/cap)^2 = 1 - (capped/cap)^2."""
    return jnp.tanh(s / cap) * cap


def _online_softmax_step(q, k, v, m, l, acc, sm_scale, mask, softcap=None):
    """One K-block update of the online-softmax state (m, l, acc) — the
    shared numerics of the default and streamed forward kernels.
    softcap (Gemma-2): cap*tanh(s/cap) on the scaled scores, applied
    before masking, exactly as in attention_reference."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    if softcap is not None:
        s = _softcap_scores(s, softcap)
    s = _where_live(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l, acc


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal,
                window, block_q, block_k, seq_len, softcap):
    qb = pl.program_id(1)
    # Keep q/k/v in their storage dtype (bf16): the MXU runs bf16 x bf16 ->
    # f32 at full rate, while f32 inputs drop it several-fold. All
    # accumulation stays f32 via preferred_element_type.
    q = q_ref[0]  # [block_q, d]

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        mask = _block_mask(
            qb * block_q, kb * block_k, block_q=block_q, block_k=block_k,
            seq_len=seq_len, causal=causal, window=window)
        return _online_softmax_step(q, k, v, m, l, acc, sm_scale, mask,
                                    softcap)

    start_kb, _, _, num_kb = _segments(
        qb, "fwd_dq", seq_len=seq_len, window=window, block_q=block_q,
        block_k=block_k, causal=causal)
    m, l, acc = jax.lax.fori_loop(start_kb, num_kb, body, (m0, l0, acc0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # lse rides in a [bh, 1, seq] buffer: a (1, 1, block_q) block keeps the
    # trailing two dims TPU-tileable (second-to-last == array dim 1)
    lse_ref[0, 0] = (m + jnp.log(l))[:, 0]


def _fwd(q, k, v, sm_scale, causal, window, block_q, block_k, true_len,
         softcap=None):
    bh, seq, d = q.shape
    d_v = v.shape[-1]
    # dispatch on the TRUE length: lcm padding of mixed block sizes must
    # not shift the documented threshold
    if true_len > STREAM_MIN_SEQ:
        return _fwd_streamed(q, k, v, sm_scale, causal, window, block_q,
                             block_k, true_len, softcap=softcap)
    grid = (bh, pl.cdiv(seq, block_q))
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, seq_len=true_len,
            softcap=softcap,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq, d_v), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d_v), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=int(2 * bh * seq * seq * (d + d_v) * (0.5 if causal else 1.0)),
            bytes_accessed=q.size * 2 + k.size * 2 + v.size * 2,
            transcendentals=bh * seq * seq,
        ),
        compiler_params=_whole_seq_params(seq, d, d_v),
        interpret=interpret(),
        name="flash_fwd",
    )(q, k, v)
    return out, lse


def _fwd_streamed_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s,
                         *, sm_scale, causal, window, block_q, block_k,
                         seq_len, n_kb, softcap):
    """K-streaming variant: grid (bh, q_blocks, k_blocks); K/V arrive one
    block per grid step via BlockSpecs (double-buffered by Mosaic), and the
    online-softmax state lives in VMEM scratch across the kb dimension.
    VMEM use is O(block) regardless of sequence length."""
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # A 3D grid cannot skip iterations (the K/V DMA always runs), but the
    # compute CAN skip grid steps that contribute nothing: fully past the
    # diagonal (causal) or fully beyond the true sequence. On a causal
    # prefill that's ~half the MXU work.
    live = kb * block_k < seq_len
    if causal:
        live &= kb * block_k < (qb + 1) * block_q
    if window is not None:
        # the whole K block sits below every query's window
        live &= (kb + 1) * block_k - 1 >= qb * block_q - window + 1

    @pl.when(live)
    def _step():
        q = q_ref[0]  # [block_q, d] bf16
        k = k_ref[0]  # [block_k, d]
        v = v_ref[0]
        mask = _block_mask(
            qb * block_q, kb * block_k, block_q=block_q, block_k=block_k,
            seq_len=seq_len, causal=causal, window=window)
        m_new, l, acc = _online_softmax_step(
            q, k, v, m_s[...], l_s[...], acc_s[...], sm_scale, mask, softcap
        )
        m_s[...] = m_new
        l_s[...] = l
        acc_s[...] = acc

    @pl.when(kb == n_kb - 1)
    def _finish():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0] = (acc_s[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_s[...] + jnp.log(l))[:, 0]


def _fwd_streamed(q, k, v, sm_scale, causal, window, block_q, block_k,
                  true_len, softcap=None):
    bh, seq, d = q.shape
    d_v = v.shape[-1]
    n_kb = pl.cdiv(seq, block_k)
    grid = (bh, pl.cdiv(seq, block_q), n_kb)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_streamed_kernel, sm_scale=sm_scale, causal=causal,
            window=window, block_q=block_q, block_k=block_k,
            seq_len=true_len, n_kb=n_kb, softcap=softcap,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d_v), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d_v), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret(),
        name="flash_fwd_streamed",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, sm_scale, causal, window, block_q, block_k, seq_len,
                   softcap):
    qb = pl.program_id(1)
    q = q_ref[0]  # bf16 into the MXU; f32 accumulation
    do = do_ref[0]
    lse = lse_ref[0, 0][:, None]
    delta = delta_ref[0, 0][:, None]

    def body(masked):
        def step(kb, dq):
            k = k_ref[0, pl.ds(kb * block_k, block_k), :]
            v = v_ref[0, pl.ds(kb * block_k, block_k), :]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * sm_scale
            if softcap is not None:
                s = _softcap_scores(s, softcap)
            p = jnp.exp(s - lse)
            if masked:
                p = _where_live(_block_mask(
                    qb * block_q, kb * block_k, block_q=block_q,
                    block_k=block_k, seq_len=seq_len, causal=causal,
                    window=window), p, 0.0)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta)
            if softcap is not None:
                # d/dx[cap*tanh(x/cap)] = 1 - tanh(x/cap)^2 = 1 - (s/cap)^2
                ds = ds * (1.0 - (s / softcap) ** 2)
            return dq + jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return step

    # ascending as one loop would run: the blocks the window's edge crosses,
    # the interior ones with no mask, the blocks the diagonal or the padded
    # tail crosses
    start_kb, lo, hi, num_kb = _segments(
        qb, "fwd_dq", seq_len=seq_len, window=window, block_q=block_q,
        block_k=block_k, causal=causal)
    dq = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    dq = jax.lax.fori_loop(start_kb, lo, body(True), dq)
    dq = jax.lax.fori_loop(lo, hi, body(False), dq)
    dq = jax.lax.fori_loop(hi, num_kb, body(True), dq)
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    *, sm_scale, causal, window, block_q, block_k, seq_len,
                    softcap):
    kb = pl.program_id(1)
    k = k_ref[0]  # bf16 into the MXU; f32 accumulation
    v = v_ref[0]

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :]
        do = do_ref[0, pl.ds(qb * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(qb * block_q, block_q)][:, None]
        delta = delta_ref[0, 0, pl.ds(qb * block_q, block_q)][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if softcap is not None:
            s = _softcap_scores(s, softcap)
        mask = _block_mask(
            qb * block_q, kb * block_k, block_q=block_q, block_k=block_k,
            seq_len=seq_len, causal=causal, window=window)
        p = _where_live(mask, jnp.exp(s - lse), 0.0)
        pb = p.astype(do.dtype)
        dv = dv + jax.lax.dot_general(pb, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        if softcap is not None:
            # d/dx[cap*tanh(x/cap)] = 1 - tanh(x/cap)^2 = 1 - (s/cap)^2
            ds = ds * (1.0 - (s / softcap) ** 2)
        dk = dk + jax.lax.dot_general(ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    start_qb, _, _, num_qb = _segments(
        kb, "dkv", seq_len=seq_len, window=window, block_q=block_q,
        block_k=block_k, causal=causal)
    dk0 = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    dv0 = jnp.zeros((block_k, v.shape[-1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(start_qb, num_qb, body, (dk0, dv0))
    dk_ref[0] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd(sm_scale, causal, window, block_q, block_k, true_len, res, dout,
         softcap=None):
    q, k, v, out, lse = res
    bh, seq, d = q.shape
    d_v = v.shape[-1]
    # [bh, 1, seq] to match the lse layout (TPU-tileable blocks)
    delta = jnp.sum(
        out.astype(jnp.float32) * dout.astype(jnp.float32), axis=-1
    )[:, None, :]

    kern = dict(sm_scale=sm_scale, causal=causal, window=window,
                block_q=block_q, block_k=block_k, seq_len=true_len,
                softcap=softcap)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kern),
        grid=(bh, pl.cdiv(seq, block_q)),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq, d_v), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, d_v), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
        compiler_params=_whole_seq_params(seq, d, d_v),
        interpret=interpret(),
        name="flash_bwd_dq",
    )(q, k, v, dout, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kern),
        grid=(bh, pl.cdiv(seq, block_k)),
        in_specs=[
            pl.BlockSpec((1, seq, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d_v), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq, d_v), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, seq), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, seq), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d_v), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, seq, d_v), q.dtype),
        ],
        compiler_params=_whole_seq_params(seq, d, d_v),
        interpret=interpret(),
        name="flash_bwd_dkv",
    )(q, k, v, dout, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def _lane_width(d: int) -> int:
    """A head size padded to whole lanes of 128."""
    return d + (-d) % 128


def _pad_d(x, dk):
    pad = dk - x.shape[-1]
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, sm_scale, causal, window, block_q, block_k, true_len,
           true_d, softcap):
    out, _ = _fwd(q, k, v, sm_scale, causal, window, block_q, block_k,
                  true_len, softcap=softcap)
    return out


def _flash_fwd(q, k, v, sm_scale, causal, window, block_q, block_k, true_len,
               true_d, softcap):
    out, lse = _fwd(q, k, v, sm_scale, causal, window, block_q, block_k,
                    true_len, softcap=softcap)
    # Tagged before the slice below: the primal output and the residual
    # both derive from the saved value, so a d=64 model's backward does
    # not re-run the kernel for either.
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    # Residuals store only the true head dims: padded columns are zeros by
    # construction, so slicing here and re-padding in backward is exact —
    # and halves attention residual HBM for d=64 models.
    d_qk, d_v = true_d
    res = (
        q[..., :d_qk], k[..., :d_qk], v[..., :d_v],
        out[..., :d_v], lse,
    )
    return out, res


# Bound at import (NOT an alias of the monkeypatchable dispatch knob): the
# backward kernels load whole-sequence tensors into VMEM and cannot fit
# beyond this — training longer sequences is context parallelism's job.
BWD_MAX_SEQ = 8192


def _flash_bwd(sm_scale, causal, window, block_q, block_k, true_len, true_d,
               softcap, res, dout):
    v_width = dout.shape[-1]
    qk_width = _lane_width(true_d[0])
    q, k, v, out, lse = res
    if true_len > BWD_MAX_SEQ:
        raise ValueError(
            f"flash_attention backward at seq {true_len} exceeds the "
            f"kernel's whole-sequence VMEM budget (max {BWD_MAX_SEQ}); "
            f"train long sequences with ring attention over a 'context' "
            f"mesh axis (ops/ring_attention.py) — the streamed forward "
            f"serves inference prefill only"
        )
    res = (
        _pad_d(q, qk_width), _pad_d(k, qk_width), _pad_d(v, v_width),
        _pad_d(out, v_width), lse,
    )
    return _bwd(sm_scale, causal, window, block_q, block_k, true_len, res,
                dout, softcap=softcap)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _snap_block(block: int) -> int:
    """Largest divisor of STREAM_MIN_SEQ that is <= block; sub-128 blocks
    (interpret mode only) pass through untouched."""
    if block < 128 or STREAM_MIN_SEQ % block == 0:
        return block
    p = 128
    while p * 2 <= min(block, STREAM_MIN_SEQ):
        p *= 2
    return p


def _pad_seq_to(x, target):
    pad = target - x.shape[1]
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    min_seq: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Blocked attention over [batch, q_heads, seq, head_dim] tensors.
    v's head size may differ from q's and k's (the output takes v's).

    GQA: k/v may have fewer heads (q_heads % kv_heads == 0); KV heads are
    broadcast to the query groups.

    window: sliding-window (Mistral-style) attention — query i attends
    keys in (i - window, i]. Requires causal=True. Dead K blocks are
    skipped in both directions, so compute scales with window, not seq.

    softcap (Gemma-2): cap*tanh(s/cap) on the scaled scores before
    masking, applied inside the kernel (forward AND the custom VJP —
    the backward multiplies dS by 1 - (s_capped/cap)^2).

    min_seq overrides the measured fused-vs-unfused crossover (default
    FLASH_MIN_SEQ, swept on v5e): pass 0 to prefer the fused kernel at
    any length — e.g. on a different TPU generation, or when the kernel's
    O(T)-per-block memory (not its speed) is the point. Sequences shorter
    than one 128 lane tile cannot tile onto the MXU and always take the
    unfused path.
    """
    b, hq, sq, d = q.shape
    hkv, d_v = k.shape[1], v.shape[-1]
    if k.shape[-1] != d:
        raise ValueError(f"q heads are {d} wide and k heads {k.shape[-1]}")
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True (sliding window "
                             "is a causal-attention concept)")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if softcap is not None and softcap <= 0:
        raise ValueError(f"softcap must be > 0 or None, got {softcap}")
    if hq != hkv:
        if hq % hkv:
            raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if sm_scale is None:
        sm_scale = 1.0 / (d**0.5)

    # Below the measured crossover the unfused path is simply faster —
    # this is dispatch policy, not degradation (no warning). Interpret
    # mode (CPU tests) keeps exercising the kernel at small shapes.
    if min_seq is None:
        min_seq = FLASH_MIN_SEQ
    # < 128 can never tile onto the MXU regardless of min_seq (silent: it's
    # a hardware constraint, not a degradation a caller could fix)
    if not interpret() and (sq < min_seq or sq < 128):
        return attention_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                                   window=window, softcap=softcap)

    # Lane-align the head dim by zero-padding to the next multiple of 128
    # (ViT-class 64, GQA oddballs): zero K columns add nothing to QK^T,
    # zero V columns produce zero output columns that are sliced off, and
    # autodiff through pad/slice keeps the VJP exact. At the sequence
    # lengths that reach here (>= FLASH_MIN_SEQ) the extra MXU work still
    # beats the unfused path's materialized [T, T] softmax (2.65x at
    # s=1024 d=64 on v5e).
    # q/k and v each to their own width: a value head narrower than the
    # keys (latent attention) costs the PV and dV products its own lanes.
    def lanes(x):
        pad = (-x.shape[-1]) % 128
        return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad))) if pad else x

    q, k, v = lanes(q), lanes(k), lanes(v)
    dk, dvw = q.shape[-1], v.shape[-1]

    # Clamp blocks to the sequence, keeping them lane-aligned (128) so
    # mid-size sequences stay on the fused kernel (padding fills the rest).
    if sq >= 128:
        cap = (sq // 128) * 128
        block_q = min(block_q, cap)
        block_k = min(block_k, cap)
    else:
        block_q = block_k = max(sq, 1)

    # Mosaic requires MXU-tileable blocks. The clamp above keeps the
    # defaults aligned, so only blocks the caller chose can land here;
    # CPU interpret mode is exempt.
    if not interpret() and (block_q % 128 or block_k % 128):
        raise ValueError(
            f"flash_attention: blocks ({block_q},{block_k}) are not "
            f"multiples of 128 and cannot tile onto the MXU; pass "
            f"128-aligned block_q/block_k or leave the defaults")

    # The whole-sequence kernels (fwd at <= STREAM_MIN_SEQ, bwd always)
    # budget VMEM for a padded length of at most STREAM_MIN_SEQ. Exotic
    # block sizes (640, 384, ...) have lcms that can pad PAST that budget
    # even when the true length is under it; only then snap them down to
    # divisors of STREAM_MIN_SEQ (all its divisors are pow2 multiples of
    # 128), which bounds the padded length by the budget again. In-budget
    # caller choices are preserved exactly.
    if sq <= STREAM_MIN_SEQ:
        lcm0 = math.lcm(block_q, block_k)
        if pl.cdiv(sq, lcm0) * lcm0 > STREAM_MIN_SEQ:
            block_q = _snap_block(block_q)
            block_k = _snap_block(block_k)

    # One COMMON padded length divisible by both blocks: padding q and k/v
    # to different lengths would send the K-block grid out of bounds when
    # block_q != block_k. The padded tail is masked via seq_len.
    lcm = math.lcm(block_q, block_k)
    target = pl.cdiv(sq, lcm) * lcm
    qf = _pad_seq_to(q.reshape(b * hq, sq, dk), target)
    kf = _pad_seq_to(k.reshape(b * hq, sq, dk), target)
    vf = _pad_seq_to(v.reshape(b * hq, sq, dvw), target)
    # A kernel's HLO instruction takes the innermost name on the stack.
    # Under this scope that is always the kernel's own name= (%flash_fwd.N,
    # %flash_bwd_dq.N); without one a bare jax.grad of this function would
    # wrap it (jvp(flash_fwd) reads %jvp_flash_fwd_.N).
    with jax.named_scope("flash_attention"):
        out = _flash(qf, kf, vf, sm_scale, causal, window, block_q, block_k,
                     sq, (d, d_v), softcap)
    return out[:, :sq, :d_v].reshape(b, hq, sq, d_v)


def attention_reference(q, k, v, *, causal: bool = True,
                        sm_scale: Optional[float] = None,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None):
    """Plain-XLA attention for correctness tests and softcapped configs
    (same GQA semantics, incl. the sliding window; softcap applies
    Gemma-2's cap*tanh(s/cap) to the scaled scores before masking)."""
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    if hq != hkv:
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
    if sm_scale is None:
        sm_scale = 1.0 / (d**0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * sm_scale
    if softcap is not None:
        s = _softcap_scores(s, softcap)
    if causal:
        mask = np.tril(np.ones((sq, sq), bool))
        if window is not None:
            mask &= ~np.tril(np.ones((sq, sq), bool), k=-window)
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
