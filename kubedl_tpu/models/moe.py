"""Mixture-of-Experts FFN with expert parallelism — the "expert" mesh axis.

The reference has no expert parallelism (SURVEY.md §2.4: "Expert parallelism
(EP): absent"); this is the net-new TPU-native path behind the JAXJob mesh
spec's `expert` axis:

  * top-k gating via ONE `jax.lax.top_k` over the router probs plus a
    sort-based slot assignment — no [S, E] one-hot planes, no per-k
    cumsum sweeps (the iterative argmax scheme built k such planes per
    layer; at bench shapes that was pure dispatch overhead on the VPU
    while the MXU idled). The old iterative scheme survives as
    `_top_k_gating_reference` for parity tests;
  * routing is GATHER/SCATTER, not GShard's dense one-hot einsums: the
    `[S,E,C] x [S,d]` dispatch/combine matmuls cost S*E*C*d FLOPs EACH —
    at bench shapes (S=8k, E=4, C=5.1k, d=1k) that equals the expert FFN
    compute itself and capped measured MFU at 0.30. Building the slot->
    token index map once (scatter of S indices) and gathering rows moves
    O(E*C*d) bytes instead, leaving the MXU to the expert matmuls.
    Dropped tokens and empty slots route to a zero row via a sentinel
    index — same static shapes, same Switch drop semantics;
  * the dropless expert FFN runs through the fused grouped-matmul
    kernels (ops/gmm.py): `gmm_swiglu` computes silu(x@w1)*(x@w3) in
    the accumulator (one launch, no [M, ffn] gate/up round-trips) and
    the w2 projection folds int8 per-expert output scales in its
    epilogue (`gmm_scaled`). `fused=False` keeps the original
    three-launch reference path selectable for parity tests;
  * the expert-parallel dispatch (`_dropless_shard_fn`) optionally
    CHUNKS the quota dimension so the all-to-all for chunk i+1 is
    issued before chunk i's local expert FFN — with TPU async
    collectives the ICI transfer overlaps the grouped matmuls instead
    of serializing against them (`a2a_chunks` knob; the comm/compute
    overlap arXiv:1810.08955 / arXiv:2412.14374 recover);
  * per-expert FFN on the capacity path is one batched einsum over the
    expert dim — E local matmuls on each expert shard, MXU-shaped;
  * auxiliary load-balance loss (mean-prob x mean-assignment, GShard
    eq. (4)-style) keeps the router from collapsing.

Tokens overflowing an expert's capacity are dropped (contribute zero) and
their residual path passes through — standard Switch behavior.

Two things a layer reads off its own arrays (docs/moe_performance.md):
  * which experts it HOLDS: the router keeps its published width
    (`router [d, n_out]`) while `w1/w3/w2` carry `e <= n_out` experts,
    `first_expert .. first_expert + e - 1`. The layer routes over all
    `n_out` outputs, turns choices of absent experts into the sentinel
    `_gmm_ffn` understands and returns the part of the result its own
    experts give — one chip's share of an expert-parallel deployment,
    without the exchange and without any stand-in for it. Dropless only;
  * the router's score function: softmax over all outputs with the
    GShard auxiliary loss (`_top_k_gating`), or, where the layer carries
    a `router_bias`, a sigmoid score whose top-k selection alone sees
    the bias and which has no auxiliary loss (`_sigmoid_gating`);
  * a shared expert (`shared_w1/w3/w2`): a dense SwiGLU of every token
    beside the routed ones, added to whatever part of the routed result
    the layer computes. In an expert-parallel deployment every chip
    computes it alike, so of the shares' results it counts once.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from kubedl_tpu.parallel.mesh import ShardingRules


def moe_param_specs(rules: Optional[ShardingRules] = None,
                    router_bias: bool = False, shared: bool = False) -> Dict:
    """PartitionSpec pytree matching moe_init() for one MoE FFN layer."""
    r = rules or ShardingRules()
    specs = {
        "router": r.spec("embed", "expert"),
        "w1": r.spec("expert", "embed", "mlp"),
        "w3": r.spec("expert", "embed", "mlp"),
        "w2": r.spec("expert", "mlp", "embed"),
    }
    if router_bias:
        specs["router_bias"] = r.spec(None)
    if shared:
        specs.update({"shared_w1": r.spec("embed", "mlp"),
                      "shared_w3": r.spec("embed", "mlp"),
                      "shared_w2": r.spec("mlp", "embed")})
    return specs


def moe_init(
    key: jax.Array, d_model: int, d_ff: int, n_experts: int, dtype=jnp.bfloat16,
    n_held: Optional[int] = None, router_bias: bool = False,
    d_ff_shared: int = 0,
) -> Dict:
    """One expert layer: a router over `n_experts` outputs and the
    `n_held` experts this layer holds (None = all of them). With
    `router_bias`, the sigmoid router's selection bias (zeros); with
    `d_ff_shared`, a shared expert of that width."""
    ks = jax.random.split(key, 4)
    n_held = n_held or n_experts

    def dense(k, shape, fan_in):
        return (
            jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
            * (1.0 / np.sqrt(fan_in))
        ).astype(dtype)

    layer = {
        # router stays f32: tiny, and gating is precision-sensitive
        "router": (
            jax.random.truncated_normal(ks[0], -2, 2, (d_model, n_experts), jnp.float32)
            * (1.0 / np.sqrt(d_model))
        ),
        "w1": dense(ks[1], (n_held, d_model, d_ff), d_model),
        "w3": dense(ks[2], (n_held, d_model, d_ff), d_model),
        "w2": dense(ks[3], (n_held, d_ff, d_model), d_ff),
    }
    if router_bias:
        layer["router_bias"] = jnp.zeros((n_experts,), jnp.float32)
    if d_ff_shared:
        sk = jax.random.split(jax.random.fold_in(key, 1), 3)
        layer.update({
            "shared_w1": dense(sk[0], (d_model, d_ff_shared), d_model),
            "shared_w3": dense(sk[1], (d_model, d_ff_shared), d_model),
            "shared_w2": dense(sk[2], (d_ff_shared, d_model), d_ff_shared),
        })
    return layer


def expert_capacity(
    n_tokens: int, n_experts: int, top_k: int, capacity_factor: float
) -> int:
    return max(1, int(np.ceil(top_k * n_tokens / n_experts * capacity_factor)))


def _top_k_gating(
    gate_logits: jax.Array,  # [S, E] f32
    top_k: int,
    capacity: int,
    need_slots: bool = True,
    bias: Optional[jax.Array] = None,
    routed_scale: float = 1.0,
    norm_eps: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
           Tuple[jax.Array, jax.Array]]:
    """Routing as INDICES instead of one-hot planes.

    Returns (experts [k,S] i32, slots [k,S] i32, weights [k,S] f32,
    keep [k,S] bool, (me, ce)): for each token and each of its k
    choices, which expert, which capacity slot inside that expert, the
    renormalized combine weight, and whether the slot fit under
    capacity. (me, ce) are the per-expert mean routing prob and mean
    top-1 assignment — the factors of the GShard load-balance loss
    aux = E * sum(me * ce), returned unfused so the expert-parallel
    path can pmean them to global means before combining.

    One `jax.lax.top_k` picks all k choices at once; slot assignment is
    a single stable sort of the k*S (choice, token) entries by expert —
    position within the expert's run IS the slot, and the choice-major
    entry order reproduces the classic priority (all k=0 choices claim
    slots before any k=1 choice). No [S, E] mask planes anywhere.

    `need_slots=False` skips the sort entirely for callers that run
    their own dispatch ordering (the dropless paths): slots come back
    zero, keeps all-true, and `capacity` is ignored.

    With a `bias` the score is the sigmoid router's (`_sigmoid_gating`):
    weights arrive normalised over all k choices and stay so, a choice
    that loses its slot simply adds nothing, and the load-balance
    factors are zero (that router trains without the auxiliary loss).
    """
    s, e = gate_logits.shape
    if bias is not None:
        experts, gates = _sigmoid_gating(gate_logits, bias, top_k,
                                         routed_scale, norm_eps)
        me = ce = jnp.zeros((e,), jnp.float32)
    else:
        probs = jax.nn.softmax(gate_logits, axis=-1)

        topv, topi = jax.lax.top_k(probs, top_k)  # [S, k] each
        experts = topi.T.astype(jnp.int32)  # [k, S], choice-major
        gates = topv.T.astype(jnp.float32)  # [k, S]

        # load-balance aux factors: mean(prob), mean(top-1 assignment)
        me = jnp.mean(probs, axis=0)
        ce = jnp.zeros((e,), jnp.float32).at[experts[0]].add(1.0 / s)

    if not need_slots:
        weights = gates if bias is not None else gates / jnp.maximum(
            jnp.sum(gates, axis=0, keepdims=True), 1e-9)
        return (
            experts,
            jnp.zeros((top_k, s), jnp.int32),
            weights,
            jnp.ones((top_k, s), bool),
            (me, ce),
        )

    # per-expert slot assignment: flatten entries choice-major
    # (f = kk*S + token), stable-sort by expert — within an expert the
    # run is ordered by f, i.e. k=0 entries first then token order,
    # exactly the iterative scheme's priority. The slot is the position
    # inside the run.
    ks = top_k * s
    ef = experts.reshape(ks)
    order = jnp.argsort(ef)  # stable
    sorted_ef = ef[order]
    counts = jnp.zeros((e,), jnp.int32).at[ef].add(1)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(ks, dtype=jnp.int32) - starts[sorted_ef]
    slots = jnp.zeros((ks,), jnp.int32).at[order].set(pos).reshape(top_k, s)
    keeps = slots < capacity

    weights = gates * keeps  # [k, S]
    if bias is None:
        # renormalize over the choices that actually kept the token
        weights = weights / jnp.maximum(
            jnp.sum(weights, axis=0, keepdims=True), 1e-9)
    return experts, slots, weights, keeps, (me, ce)


def _top_k_gating_reference(
    gate_logits: jax.Array,  # [S, E] f32
    top_k: int,
    capacity: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
           Tuple[jax.Array, jax.Array]]:
    """The original iterative argmax/one-hot/cumsum gating — k [S, E]
    mask planes per call. Kept ONLY as the parity reference for
    tests/test_gmm_moe.py; the hot path is `_top_k_gating`."""
    s, e = gate_logits.shape
    probs = jax.nn.softmax(gate_logits, axis=-1)

    remaining = probs
    masks, gates, experts = [], [], []
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)
        experts.append(idx.astype(jnp.int32))
        masks.append(onehot)
        gates.append(jnp.sum(probs * onehot, axis=-1))
        remaining = remaining * (1.0 - onehot)

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(masks[0], axis=0)

    slots, keeps = [], []
    pos_offset = jnp.zeros((e,), jnp.float32)
    for k in range(top_k):
        m = masks[k]
        pos_in_expert = jnp.cumsum(m, axis=0) - m + pos_offset  # [S, E]
        pos_offset = pos_offset + jnp.sum(m, axis=0)
        slot = jnp.sum(pos_in_expert * m, axis=-1)  # [S]
        slots.append(slot.astype(jnp.int32))
        keeps.append(slot < capacity)

    weights = jnp.stack(gates) * jnp.stack(keeps)  # [k, S]
    weights = weights / jnp.maximum(
        jnp.sum(weights, axis=0, keepdims=True), 1e-9)
    return (
        jnp.stack(experts),
        jnp.stack(slots),
        weights,
        jnp.stack(keeps),
        (me, ce),
    )


# a family's modelling code adds this to the sum that normalises the k
# selected scores (it is not a key of any config.json): LFM2's 1e-6, the
# default; DeepSeek-V3's family 1e-20 (LlamaConfig.moe_norm_eps)
SIGMOID_NORM_EPS = 1e-6


def _sigmoid_gating(
    gate_logits: jax.Array,  # [S, n_out] f32
    bias: jax.Array,  # [n_out] f32, moves the selection and nothing else
    top_k: int,
    routed_scale: float = 1.0,
    norm_eps: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid router with a selection bias: (experts [k, S] i32,
    weights [k, S] f32). The score of every output is its own sigmoid;
    the k outputs with the largest `score + bias` are chosen, and each
    weighs its score (without the bias) over the sum of all k chosen
    scores, held here or not, times `routed_scale` (the family's
    `routed_scaling_factor`; no multiply at 1). The bias takes no
    gradient: it reaches the result through the indices alone."""
    scores = jax.nn.sigmoid(gate_logits)
    _, topi = jax.lax.top_k(scores + bias.astype(scores.dtype), top_k)
    chosen = jnp.take_along_axis(scores, topi, axis=-1)  # [S, k]
    weights = chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True)
        + (SIGMOID_NORM_EPS if norm_eps is None else norm_eps))
    if routed_scale != 1.0:
        weights = weights * routed_scale
    return topi.T.astype(jnp.int32), weights.T


@jax.named_scope("shared_expert")
def _shared_expert(hf: jax.Array, params: Dict) -> jax.Array:
    """The shared expert's SwiGLU over every row of hf [S, d]."""
    from kubedl_tpu.models.quant import matmul as mm

    gate = jax.nn.silu(mm(hf, params["shared_w1"]).astype(jnp.float32))
    return mm(gate.astype(hf.dtype) * mm(hf, params["shared_w3"]),
              params["shared_w2"]).astype(hf.dtype)


def _dispatch_stats(eid: jax.Array, e: int) -> Dict:
    """What one dropless dispatch did, as counters (f32 scalars, so that
    layers add up and a step can return them as metrics): rows routed
    over all of the router's outputs, rows computed by the experts held
    here, the fullest held expert's rows (with `moe_rows_held` and the
    held count it gives the load's max over mean), the grouped matmuls'
    live row tiles beside the static grid's, and the rows the forward's
    two row moves copy (the live tiles' rows into the padded layout, the
    held entries' rows back out) beside the rows their outputs span."""
    m = eid.shape[0]
    tile = _row_tile(m, e)
    counts = jnp.zeros((e,), jnp.int32).at[eid].add(1, mode="drop")
    live_tiles = jnp.sum((counts + tile - 1) // tile)
    grid_tiles = ((m + tile - 1) // tile * tile + e * tile) // tile
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    return {
        "moe_rows_routed": f32(m),
        "moe_rows_held": f32(jnp.sum(counts)),
        "moe_rows_fullest": f32(jnp.max(counts)),
        "gmm_live_tiles": f32(live_tiles),
        "gmm_grid_tiles": f32(grid_tiles),
        "moe_rows_moved": f32(live_tiles * tile + jnp.sum(counts)),
        "moe_rows_spanned": f32(grid_tiles * tile + m),
    }


# ---------------------------------------------------------------------------
# dropless dispatch stages. _gmm_ffn composes plan -> permute -> ffn ->
# gather, each under a named scope of its own (moe_route, moe_permute,
# moe_experts, moe_combine), which is how a device trace is read by stage
# (hack/scope_shares.py).
# ---------------------------------------------------------------------------


def _row_tile(m: int, e: int) -> int:
    """Row-tile for the padded dispatch layout. The gmm kernels stream
    one [K, N] weight block per row-tile, so rhs HBM traffic scales as
    (m / tile) * K * N — larger tiles are the difference between
    bandwidth-bound and compute-bound expert matmuls (ops/gmm.py
    _row_tile_of). The price is up to e*tile padding rows; cap it at
    ~1/8 of the real rows so small dispatches keep the fine tile."""
    from kubedl_tpu.ops.gmm import TILE_M

    for tm in (512, 256):
        if e * tm * 8 <= m:
            return tm
    return TILE_M


def _dispatch_plan(eid: jax.Array, e: int):
    """Lay out M routed entries as per-expert row-tile-padded runs.

    Returns (order, dest, pos_of_entry, tile_expert, m_pad):
      * order [M]: stable expert-sort permutation of the entries;
      * dest [M]: padded-layout row of the p-th SORTED entry (sentinel
        entries, eid == e, point at the out-of-range row m_pad);
      * pos_of_entry [M]: padded-layout row of each ORIGINAL entry;
      * tile_expert [m_pad // tile]: owning expert per row-tile, where
        `tile = _row_tile(M, e)` (512 for large dispatches, TILE_M for
        small — the gmm kernels derive the tile size from this array's
        length). Tiles past the real rows carry the id `e`: dead, and
        the kernels' grids stop before them (ops/gmm.py), so device
        time follows the rows routed here and not `m_pad`;
      * m_pad: static worst case, rounded to whole row-tiles — the
        per-group padded runs sum to <= round_up(M) + e*tile and the
        gmm grid must cover every row (a ragged tail would silently
        never be written).
    """
    m = eid.shape[0]
    tile = _row_tile(m, e)
    order = jnp.argsort(eid)  # stable: equal experts keep entry order
    sorted_eid = eid[order]
    ones = jnp.ones((m,), jnp.int32)
    group_sizes = jnp.zeros((e,), jnp.int32).at[eid].add(ones, mode="drop")
    pad_sizes = ((group_sizes + tile - 1) // tile) * tile
    pad_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(pad_sizes)[:-1]])
    grp_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes)[:-1]])
    real_eid = jnp.clip(sorted_eid, 0, e - 1)
    pos_in_group = jnp.arange(m, dtype=jnp.int32) - grp_offsets[real_eid]
    m_pad = (m + tile - 1) // tile * tile + e * tile
    dest = jnp.where(sorted_eid < e,
                     pad_offsets[real_eid] + pos_in_group, m_pad)  # [M]
    tile_starts = jnp.arange(m_pad // tile, dtype=jnp.int32) * tile
    tile_expert = jnp.searchsorted(
        jnp.cumsum(pad_sizes), tile_starts, side="right").astype(jnp.int32)
    pos_of_entry = jnp.zeros((m,), jnp.int32).at[order].set(dest)
    return order, dest, pos_of_entry, tile_expert, m_pad


def _take(x: jax.Array, idx: jax.Array) -> jax.Array:
    """x[idx] by rows; an index of len(x) (or beyond) reads a zero row.
    XLA's gather: what `_move_rows` is defined by and the tests compare
    it with."""
    return jnp.take(x, idx, axis=0, mode="fill", fill_value=0)


def _take_sum(x: jax.Array, idx: jax.Array) -> jax.Array:
    """[n, d], [c, r] -> [r, d]: `_take(x, idx[j])` over j, added in the
    order of j in float32 and rounded to x's dtype once: what XLA's fused
    sum of c bfloat16 gathers gives on the chip, said outright."""
    y = _take(x, idx[0])
    if idx.shape[0] == 1:
        return y
    y = y.astype(jnp.float32)
    for j in range(1, idx.shape[0]):
        y = y + _take(x, idx[j]).astype(jnp.float32)
    return y.astype(x.dtype)


def _live_rows(tile_expert: jax.Array, e: int, m_pad: int) -> jax.Array:
    """Leading rows of the padded layout that live tiles cover, read off
    `tile_expert` as the `gmm*` grids read it (ops/gmm.py `_live_tiles`:
    at least one tile)."""
    from kubedl_tpu.ops.gmm import _live_tiles

    return _live_tiles(tile_expert, e) * (m_pad // tile_expert.shape[0])


# The kernel costs a live row 1.6 times XLA's gather and a dead one
# nothing (PERF.md section 6, PR 29): a move without a bound takes it
# where at most this share of its indices name a row
SPARSE_SHARE = 0.5
# rows a step of the bounded loop gathers (a power of two), or the most
# of them that divide the layout
LOOP_ROWS = 1024


@jax.jit  # a step traces and lowers each of its four moves once
def _move_rows(x: jax.Array, idx: jax.Array,
               live_rows: Optional[jax.Array] = None) -> jax.Array:
    """[n, d], [c, r] -> [r, d]: `y[i] = sum_j x[idx[j, i]]`, an index of
    len(x) reading a zero row: `_take_sum(x, idx)`.

    Only the rows that hold something move, where the caller or the
    indices say which. With `live_rows` (a traced scalar; c = 1) they are
    the leading `live_rows` (the padded layout's live tiles): a loop
    gathers those, `LOOP_ROWS` a step, and the rows past them are left
    zero, which no reader may count on. Without it they lie anywhere
    among the sentinels, and each call chooses by their count: the
    row-gather kernel (ops/row_gather.py), which copies no row for a
    sentinel, where at most `SPARSE_SHARE` of the indices name a row
    (a layer that holds a quarter of its router's experts), else one XLA
    gather over all r rows (a layer that holds them all: what every move
    was until PR 29)."""
    from kubedl_tpu.ops.row_gather import gather_rows

    c, r = idx.shape
    if live_rows is not None:
        step = math.gcd(r, LOOP_ROWS)

        def one(t, y):
            rows = _take(x, jax.lax.dynamic_slice(idx[0], (t * step,), (step,)))
            return jax.lax.dynamic_update_slice(y, rows, (t * step, 0))
        return jax.lax.fori_loop(
            0, (live_rows + step - 1) // step, one,
            jnp.zeros((r, x.shape[1]), x.dtype))
    named = jnp.sum(idx < x.shape[0], dtype=jnp.int32)
    return jax.lax.cond(
        named <= int(SPARSE_SHARE * c * r), gather_rows, _take_sum, x, idx)


@jax.custom_vjp
def _take_rows(x: jax.Array, idx: jax.Array, back: jax.Array,
               live_out: Optional[jax.Array] = None,
               live_in: Optional[jax.Array] = None) -> jax.Array:
    """y[i] = x[idx[i]], a zero row where idx[i] == len(x): `_take(x,
    idx)`, moved by `_move_rows`.

    `back` [c, len(x)] names, for each row of x, the rows of y that took
    it (len(y) = none). The transpose is then one move that adds its c
    rows in the order of c, where autodiff would scatter-add [rows, d]
    into x: a TPU scatters rows several times slower than it gathers
    them, and the dispatch moves k*S rows of d_model four times a layer
    and step.

    The bounds (traced scalars, or None for all rows) say how many
    leading rows of a padded layout are live: `live_out` of y, `live_in`
    of x and so of x's cotangent. No index may name a row past them."""
    return _move_rows(x, idx[None], live_out)


def _take_rows_fwd(x, idx, back, live_out, live_in):
    return _move_rows(x, idx[None], live_out), (idx, back, live_out, live_in)


def _take_rows_bwd(res, dy):
    idx, back, live_out, live_in = res
    dx = _move_rows(dy, back, live_in)
    zero = lambda a: None if a is None else np.zeros(a.shape, jax.dtypes.float0)
    return dx, zero(idx), zero(back), zero(live_out), zero(live_in)


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _permute(
    src: jax.Array,  # [n_src, d]
    order: jax.Array,
    dest: jax.Array,
    pos_of_entry: jax.Array,
    m_pad: int,
    live_rows: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Gather the routed rows into the padded expert-sorted layout: which
    entry each padded row holds (a scatter of M integers; sentinel
    entries target the out-of-range row m_pad and are dropped), then one
    gather of rows (entry f is row `f % n_src` of `src`). Padding rows
    read zero; no reader may count on that past `live_rows` (dead
    tiles). Returns (rows [m_pad, d], entry_of_row [m_pad], M where a
    row holds none)."""
    n_src, m = src.shape[0], order.shape[0]
    entry_of_row = jnp.full((m_pad,), m, jnp.int32).at[dest].set(
        order, mode="drop")
    row_src = jnp.where(entry_of_row < m, entry_of_row % n_src, n_src)
    x = _take_rows(src, row_src, pos_of_entry.reshape(m // n_src, n_src),
                   live_rows, None)
    return x, entry_of_row


def _ffn_rows(
    x: jax.Array,  # [m_pad, d] padded expert-sorted rows
    tile_expert: jax.Array,  # [m_pad // row_tile] i32
    params: Dict,
    fused: bool = True,
    row_tile: Optional[int] = None,
) -> jax.Array:
    """The expert SwiGLU FFN on the padded layout.

    fused=True (default): `gmm_swiglu` computes silu(x@w1)*(x@w3) in
    one launch with int8 scales (when present) folded in-kernel, then
    `gmm_scaled`/`gmm` projects through w2 — two launches, one [m_pad,
    ffn] intermediate. fused=False keeps the original three-launch path
    (scales still folded in-kernel — never materialized as [m_pad, ffn]
    row arrays) as the reference for parity tests."""
    from kubedl_tpu.ops.gmm import gmm, gmm_scaled, gmm_swiglu

    if row_tile is None:
        # trusted internal path: x and tile_expert come from the same
        # _dispatch_plan, so the tile is their ratio by construction
        row_tile = x.shape[0] // tile_expert.shape[0]
    w1, w3, w2 = params["w1"], params["w3"], params["w2"]
    if isinstance(w1, dict):
        # int8 experts: per-expert [E, out] scales applied inside the
        # kernel epilogues (no repeat(TILE_M) row-scale arrays)
        q1 = w1["q"].astype(x.dtype)
        q3 = w3["q"].astype(x.dtype)
        q2 = w2["q"].astype(x.dtype)
        s1 = w1["s"].astype(jnp.float32)
        s3 = w3["s"].astype(jnp.float32)
        s2 = w2["s"].astype(jnp.float32)
        if fused:
            h = gmm_swiglu(x, q1, q3, tile_expert, s1, s3, row_tile=row_tile)
        else:
            gate = jax.nn.silu(
                gmm_scaled(x, q1, tile_expert, s1, row_tile=row_tile)
                .astype(jnp.float32)
            ).astype(x.dtype)
            up = gmm_scaled(x, q3, tile_expert, s3, row_tile=row_tile)
            h = gate * up
        return gmm_scaled(h, q2, tile_expert, s2, row_tile=row_tile)
    if fused:
        ones = jnp.ones((w1.shape[0], w1.shape[-1]), jnp.float32)
        h = gmm_swiglu(x, w1, w3, tile_expert, ones, ones, row_tile=row_tile)
    else:
        gate = jax.nn.silu(
            gmm(x, w1, tile_expert, row_tile=row_tile)
            .astype(jnp.float32)).astype(x.dtype)
        up = gmm(x, w3, tile_expert, row_tile=row_tile)
        h = gate * up
    return gmm(h, w2, tile_expert, row_tile=row_tile)


def _gmm_ffn(
    src: jax.Array,  # [n_src, d] source rows
    eid: jax.Array,  # [M] i32 expert per entry, in [0, e]; e = empty sentinel
    params: Dict,
    e: int,
    fused: bool = True,
) -> jax.Array:
    """Route M entries through their experts' SwiGLU FFN via the grouped
    matmul kernels (ops/gmm.py): sort entries by expert, pad each
    expert's run to the row-tile, run the fused FFN. Entry f is row
    `f % n_src` of `src` (M a multiple of n_src: each row's k choices,
    choice-major). Returns [M, d] outputs aligned to the entries;
    sentinel entries (eid == e) come back as zero rows.

    Rows move by gathers in both directions and in both passes
    (`_take_rows`): into the padded layout by the entry each padded row
    holds, back out by the padded row of each entry. A layer that holds
    part of its router's experts moves only the rows that hold something
    (`_move_rows`): the padded layout is filled, and its cotangent with
    it, only as far as the last live row tile, where the `gmm*` grids
    stop too, and a sentinel entry costs no copy on the way out. Rows of
    dead tiles are then whatever that move left there (zeros today); the
    grouped matmuls leave theirs unwritten; nothing reads either."""
    n_src, m = src.shape[0], eid.shape[0]
    if m % n_src:
        raise ValueError(f"{m} entries do not tile {n_src} source rows")
    with jax.named_scope("moe_route"):
        order, dest, pos_of_entry, tile_expert, m_pad = _dispatch_plan(eid, e)
        live_rows = _live_rows(tile_expert, e, m_pad)
    with jax.named_scope("moe_permute"):
        x, entry_of_row = _permute(
            src, order, dest, pos_of_entry, m_pad, live_rows)
    with jax.named_scope("moe_experts"):
        rows = _ffn_rows(x, tile_expert, params, fused=fused)
    # entry f's output sits at padded row pos_of_entry[f]; a sentinel's
    # m_pad reads the zero row
    with jax.named_scope("moe_combine"):
        return _take_rows(rows, pos_of_entry, entry_of_row[None],
                          None, live_rows)


@jax.named_scope("moe_combine")
def _combine(
    rows: jax.Array,  # [k*S, d] FFN outputs, entry f = choice*S + token
    weights: jax.Array,  # [k, S] f32 combine weights
    out_dtype,
) -> jax.Array:
    """Weighted sum of each token's k expert outputs."""
    k, s = weights.shape
    d = rows.shape[1]
    y = jnp.zeros((s, d), out_dtype)
    for kk in range(k):
        y = y + weights[kk][:, None].astype(out_dtype) * rows[kk * s:(kk + 1) * s]
    return y


def _dropless_mlp(
    hf: jax.Array,  # [S, d]
    params: Dict,
    experts: jax.Array,  # [k, S] i32 expert choice per token
    weights: jax.Array,  # [k, S] f32 combine weights
    e: int,
    fused: bool = True,
) -> jax.Array:
    """Single-shard dropless dispatch: compute scales with the TOKENS
    ROUTED (k*S + E*tile rows), not with a capacity bound, and nothing
    is ever dropped."""
    s, d = hf.shape
    k = experts.shape[0]
    ks = k * s
    ef = experts.reshape(ks)  # flat id f = choice*S + token
    rows = _gmm_ffn(hf, ef, params, e, fused=fused)  # [ks, d]
    return _combine(rows, weights, hf.dtype)


def _dropless_shard_fn(
    hf_loc: jax.Array,  # [S_loc, d] this device's token rows
    params: Dict,  # expert blocks: w* leading dim = e_loc local experts
    *,
    top_k: int,
    e: int,
    e_loc: int,
    n_e: int,
    quota: int,
    expert_axis: str,
    token_axes: Tuple[str, ...],
    tensor_axes: Tuple[str, ...] = (),
    fused: bool = True,
    a2a_chunks: int = 1,
) -> Tuple[jax.Array, jax.Array]:
    """Per-device body of the expert-parallel dropless route (runs under
    shard_map). Tokens are sharded over `token_axes` (batch axes + the
    expert axis — every device owns a token block AND an expert block);
    expert weights are blocked over `expert_axis`.

    Dispatch: sort this device's k*S_loc (token, choice) entries by
    expert — runs destined to the same expert shard are contiguous —
    and pack each destination shard's run into a `quota`-row slot of a
    [n_e, quota, d] buffer. One all_to_all over the expert axis lands
    every entry on the shard that owns its expert; a local _gmm_ffn
    computes exactly the received rows (plus tile padding); the reverse
    all_to_all returns outputs to each entry's home device for the
    weighted combine. Entries past a destination's quota are dropped
    (weight renormalized over surviving choices) — drops happen at
    SHARD granularity (e_loc experts pooled), far coarser than the
    capacity path's per-expert slots, and vanish for quota factor >= 1
    under a balanced router.

    `a2a_chunks > 1` splits the quota dimension into chunks and issues
    the all-to-all for chunk i+1 BEFORE chunk i's local FFN: the chunks
    are dataflow-independent, so XLA's async collectives overlap the
    ICI transfer with the grouped matmuls instead of serializing
    (comm/compute pipelining per arXiv:1810.08955 / arXiv:2412.14374).
    Row-for-row identical results for any chunk count — each entry's
    slot, expert, and weight are unchanged."""
    s_loc, d = hf_loc.shape
    k = top_k
    ks = k * s_loc
    bias = params.get("router_bias")
    gate_logits = _router_logits(hf_loc, params["router"])
    experts, _, gates, _, (me, ce) = _top_k_gating(
        gate_logits, k, s_loc + 1, need_slots=False, bias=bias)
    # load-balance loss over GLOBAL means: every token axis partitions
    # the token set, so pmean over all of them is the global mean
    me = jax.lax.pmean(me, token_axes)
    ce = jax.lax.pmean(ce, token_axes)
    aux = e * jnp.sum(me * ce)

    ef = experts.reshape(ks)  # flat entry f = choice*S_loc + token
    src_rows = jnp.tile(jnp.arange(s_loc, dtype=jnp.int32), k)
    dest_shard = ef // e_loc  # owning expert shard per entry
    order = jnp.argsort(ef)  # stable; groups by expert => also by shard
    sorted_ef = ef[order]
    sorted_dest = sorted_ef // e_loc
    shard_counts = jnp.zeros((n_e,), jnp.int32).at[dest_shard].add(
        jnp.ones((ks,), jnp.int32))
    shard_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(shard_counts)[:-1]])
    pos = jnp.arange(ks, dtype=jnp.int32) - shard_offsets[sorted_dest]
    kept_sorted = pos < quota  # entries past the shard quota drop
    slot = jnp.where(kept_sorted, sorted_dest * quota + pos, n_e * quota)
    send_x = jnp.zeros((n_e * quota, d), hf_loc.dtype).at[slot].set(
        hf_loc[src_rows[order]], mode="drop")
    # expert id per slot; e = empty-slot sentinel
    send_eid = jnp.full((n_e * quota,), e, jnp.int32).at[slot].set(
        sorted_ef, mode="drop")

    ei = jax.lax.axis_index(expert_axis)
    send_xs = send_x.reshape(n_e, quota, d)
    send_es = send_eid.reshape(n_e, quota)
    # chunk count: a divisor of the quota's row-tiles so every chunk
    # keeps whole TILE_M runs (minimizes per-chunk gmm padding)
    from kubedl_tpu.ops.gmm import TILE_M

    q_tiles = max(quota // TILE_M, 1)
    nc = 1
    for c in range(min(max(a2a_chunks, 1), q_tiles), 0, -1):
        if q_tiles % c == 0:
            nc = c
            break
    qc = quota // nc

    def dispatch(ci: int):
        """Issue the forward all-to-all for chunk ci."""
        rx = jax.lax.all_to_all(
            send_xs[:, ci * qc:(ci + 1) * qc], expert_axis, 0, 0)
        re = jax.lax.all_to_all(
            send_es[:, ci * qc:(ci + 1) * qc], expert_axis, 0, 0)
        return rx, re

    def ffn_chunk(rx, re):
        """Local expert FFN on one received chunk + its reverse a2a."""
        flat_eid = re.reshape(n_e * qc)
        local_eid = jnp.where(flat_eid < e, flat_eid - ei * e_loc, e_loc)
        rows = rx.reshape(n_e * qc, d)
        y_rows = _gmm_ffn(rows, local_eid, params, e_loc, fused=fused)
        if tensor_axes:
            # tensor-parallel experts: w1/w3 are column-blocked and w2
            # row-blocked over the tensor axis (classic TP MLP), so each
            # shard's _gmm_ffn output is a partial sum over its ff block —
            # tokens are replicated across the tensor axis, so one psum
            # completes the FFN (int8 per-output-column scales distribute
            # over the sum)
            y_rows = jax.lax.psum(y_rows, tensor_axes)
        return jax.lax.all_to_all(
            y_rows.reshape(n_e, qc, d), expert_axis, 0, 0)

    # software pipeline: the a2a for chunk ci+1 is issued before chunk
    # ci's FFN, so the transfer and the matmuls are independent in the
    # dataflow graph and the TPU scheduler overlaps them
    backs = []
    nxt = dispatch(0)
    for ci in range(nc):
        cur = nxt
        if ci + 1 < nc:
            nxt = dispatch(ci + 1)
        backs.append(ffn_chunk(*cur))
    back = backs[0] if nc == 1 else jnp.concatenate(backs, axis=1)

    # combine at home: entry f's reply sits at slot_of_entry[f]; dropped
    # entries point at the appended zero row
    slot_of_entry = jnp.zeros((ks,), jnp.int32).at[order].set(slot)
    kept = jnp.zeros((ks,), bool).at[order].set(kept_sorted).reshape(k, s_loc)
    weights = gates * kept
    if bias is None:
        weights = weights / jnp.maximum(
            jnp.sum(weights, axis=0, keepdims=True), 1e-9)
    back_flat = jnp.concatenate(
        [back.reshape(n_e * quota, d), jnp.zeros((1, d), back.dtype)], axis=0)
    y = jnp.zeros((s_loc, d), hf_loc.dtype)
    for kk in range(k):
        rows_k = back_flat[slot_of_entry[kk * s_loc:(kk + 1) * s_loc]]
        y = y + weights[kk][:, None].astype(hf_loc.dtype) * rows_k
    return y, aux


def _dropless_mlp_sharded(
    hf: jax.Array,  # [S, d] global token rows
    params: Dict,
    *,
    top_k: int,
    quota_factor: float,
    mesh: Mesh,
    rules: ShardingRules,
    e: int,
    fused: bool = True,
    a2a_chunks: int = 1,
) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel dropless MoE: shard_map over the mesh with tokens
    sharded over (batch axes x expert axis) and expert weights blocked
    over the expert axis. Communication is two all_to_alls over ICI;
    compute per chip is proportional to the quota (~ routed tokens /
    n_shards * quota_factor), not to a per-expert capacity."""
    from jax.sharding import PartitionSpec as P

    from kubedl_tpu.ops.gmm import TILE_M

    s, d = hf.shape
    batch_axes = tuple(rules.rules.get("batch", ("data", "fsdp")))
    expert_axes = tuple(rules.rules.get("expert", ("expert",)))
    if len(expert_axes) != 1:
        raise ValueError(
            f"dropless expert parallelism needs exactly one expert mesh "
            f"axis, got {expert_axes}")
    expert_axis = expert_axes[0]
    token_axes = batch_axes + (expert_axis,)
    shape = dict(mesh.shape)
    n_e = shape.get(expert_axis, 1)
    n_tok = int(np.prod([shape.get(a, 1) for a in token_axes]))
    if e % n_e:
        raise ValueError(
            f"{e} experts not divisible by expert axis {expert_axis}={n_e}")
    if s % n_tok:
        raise ValueError(
            f"dropless dispatch shards {s} tokens over "
            f"{dict((a, shape.get(a, 1)) for a in token_axes)} = {n_tok} "
            f"ways; pad batch*seq to a multiple")
    e_loc = e // n_e
    s_loc = s // n_tok
    ks_loc = top_k * s_loc
    quota = int(np.ceil(ks_loc * quota_factor / n_e / TILE_M)) * TILE_M

    # tensor parallelism composes: the ff (mlp) dim blocks over the
    # tensor axes (w1/w3 columns, w2 rows) and the shard body psums the
    # partial FFN outputs — TP's usual MLP split, inside the EP dispatch
    mlp_axes = tuple(a for a in rules.rules.get("mlp", ("tensor",))
                     if shape.get(a, 1) > 1)
    mlp_spec = mlp_axes if len(mlp_axes) > 1 else (
        mlp_axes[0] if mlp_axes else None)
    if set(mlp_axes) & set(token_axes):
        # tokens must be REPLICATED over the mlp/tensor axes (the psum
        # completing the FFN assumes every tensor shard saw the same
        # tokens) — overlapping rules would sum different token blocks
        raise ValueError(
            f"mlp axes {mlp_axes} overlap token axes {token_axes}; "
            f"dropless EP x TP needs disjoint mesh axes")
    w1 = params["w1"]
    ff = (w1["q"] if isinstance(w1, dict) else w1).shape[-1]
    n_t = int(np.prod([shape.get(a, 1) for a in mlp_axes])) if mlp_axes else 1
    if ff % max(n_t, 1):
        raise ValueError(
            f"d_ff {ff} not divisible by tensor axes "
            f"{dict((a, shape.get(a, 1)) for a in mlp_axes)}")

    def wspec(w, transpose=False):
        ein, eout = (mlp_spec, None) if transpose else (None, mlp_spec)
        if isinstance(w, dict):
            return {"q": P(expert_axis, ein, eout),
                    "s": P(expert_axis, eout)}
        return P(expert_axis, ein, eout)

    layer_specs = {
        "router": P(None, None),
        "w1": wspec(params["w1"]),
        "w3": wspec(params["w3"]),
        "w2": wspec(params["w2"], transpose=True),
    }
    if "router_bias" in params:
        layer_specs["router_bias"] = P(None)
    fn = functools.partial(
        _dropless_shard_fn, top_k=top_k, e=e, e_loc=e_loc, n_e=n_e,
        quota=quota, expert_axis=expert_axis, token_axes=token_axes,
        tensor_axes=mlp_axes, fused=fused, a2a_chunks=a2a_chunks)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(token_axes, None), layer_specs),
        out_specs=(P(token_axes, None), P()),
        check_vma=False,
    )(hf, {k: params[k] for k in layer_specs})


def _router_logits(hf: jax.Array, router: jax.Array) -> jax.Array:
    """[S, n_out] float32 router logits. A top-k choice hinges on the gap
    between two scores, so the product keeps float32's mantissa on a TPU
    too (its default for a float32 matmul is one bf16 pass)."""
    return jnp.dot(hf.astype(jnp.float32), router.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def moe_mlp(h: jax.Array, params: Dict, **kw) -> Tuple[jax.Array, jax.Array]:
    """`moe_layer` without its counters: (output [b,t,d], aux loss)."""
    y, aux, _ = moe_layer(h, params, **kw)
    return y, aux


def moe_layer(
    h: jax.Array,  # [b, t, d] normed hidden states
    params: Dict,
    *,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    mesh: Optional[Mesh] = None,
    rules: Optional[ShardingRules] = None,
    dropless: Optional[bool] = None,
    fused: Optional[bool] = None,
    a2a_chunks: int = 1,
    first_expert: int = 0,
    routed_scale: float = 1.0,
    norm_eps: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array, Dict]:
    """Returns (output [b,t,d], aux_load_balance_loss scalar, counters).

    The counters (`_dispatch_stats`) are filled on the single-device
    dropless route and empty elsewhere.

    The router's width is `params["router"].shape[1]`; the experts held
    are the `w1.shape[0]` from `first_expert` on. Where that is fewer
    than the router's outputs the layer computes its own experts' part
    of the result (module docstring): single-device dropless route only,
    since the rest of the result lives on chips this program does not
    exchange with. A `router_bias` in `params` selects the sigmoid
    router (`_sigmoid_gating`), whose weights take `routed_scale` and
    `norm_eps` (None = SIGMOID_NORM_EPS). `shared_w1/w3/w2` in `params`
    are a shared expert: its output is added on every route, whatever
    part of the routed result the layer computes, and it is in none of
    the counters.

    dropless=None (auto): use the grouped-matmul kernel only when there
    is no multi-device mesh — it processes exactly the routed tokens (no
    capacity padding, no drops), lifting the capacity_factor MFU
    ceiling. Under ANY multi-device mesh the auto default is the
    capacity/scatter path (its static [E, C, d] buffer is what XLA turns
    into the token all-to-all). dropless=True (e.g. via
    LlamaConfig.moe_dropless) forces the gmm route: single-shard
    _dropless_mlp off-mesh, or the shard_map expert-parallel dispatch
    (_dropless_mlp_sharded — explicit all_to_all over the expert axis,
    per-shard gmm) on a mesh; there capacity_factor bounds the per-shard
    all-to-all quota instead of a per-expert slot count.

    fused=None (auto -> True): run the expert FFN through the fused
    SwiGLU grouped-matmul kernel (ops/gmm.py gmm_swiglu) — one launch
    for silu(x@w1)*(x@w3), int8 scales folded in-kernel. fused=False
    selects the original three-launch path (parity reference).

    a2a_chunks: expert-parallel dispatch pipelining — split the
    all-to-all quota into this many chunks so ICI transfer overlaps the
    local grouped matmuls (see _dropless_shard_fn). 1 = no chunking;
    only affects the sharded dropless route.
    """
    rules = rules or ShardingRules()
    b, t, d = h.shape
    s = b * t
    w1 = params["w1"]
    e = (w1["q"] if isinstance(w1, dict) else w1).shape[0]
    n_out = params["router"].shape[1]
    bias = params.get("router_bias")
    multi_device = mesh is not None and mesh.size > 1
    if e != n_out:
        if first_expert < 0 or first_expert + e > n_out:
            raise ValueError(
                f"experts {first_expert}..{first_expert + e - 1} are not "
                f"among the router's {n_out} outputs")
        if multi_device:
            raise NotImplementedError(
                f"a layer that holds {e} of its router's {n_out} experts "
                f"runs on one device: the token exchange (all-to-all over "
                f"the `expert` mesh axis) that would bring it the other "
                f"chips' rows is not implemented, mesh {dict(mesh.shape)}")
        if dropless is False:
            raise ValueError(
                "a layer that holds part of its experts is dropless only: "
                "capacity slots have no sentinel for an absent expert")
        dropless = True
    c = expert_capacity(s, e, top_k, capacity_factor)
    if dropless is None:
        # auto only where the gmm path is validated: no mesh (or a
        # 1-device one). Under ANY multi-device mesh the pallas_call
        # cannot be auto-partitioned by XLA — the sort/scatter + gmm
        # would force full replication of activations — so multi-device
        # meshes default to the capacity/scatter path; dropless=True
        # forces the gmm route regardless.
        dropless = not multi_device
    if fused is None:
        fused = True

    def constrain(x, *dims):
        if mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, rules.sharding(mesh, *dims))

    hf = h.reshape(s, d)

    def whole(y):
        """The layer's output from the routed part y [S, d]: plus the
        shared expert's, where the layer has one."""
        if "shared_w1" in params:
            y = y + _shared_expert(hf, params)
        return y.reshape(b, t, d)

    if dropless and multi_device:
        if routed_scale != 1.0 or norm_eps is not None:
            raise NotImplementedError(
                "the expert-parallel dropless route normalises its sigmoid "
                "router's weights inside the shard body, where "
                "routed_scale and norm_eps are not wired")
        # expert-parallel dropless: shard_map + all_to_all dispatch; the
        # router runs per-device inside the shard body
        y, aux = _dropless_mlp_sharded(
            hf, params, top_k=top_k, quota_factor=capacity_factor,
            mesh=mesh, rules=rules, e=e, fused=fused, a2a_chunks=a2a_chunks)
        return whole(y), aux, {}
    with jax.named_scope("moe_route"):
        gate_logits = _router_logits(hf, params["router"])
        experts, slots, weights, keeps, (me, ce) = _top_k_gating(
            gate_logits, top_k, c, need_slots=not dropless, bias=bias,
            routed_scale=routed_scale, norm_eps=norm_eps)
        aux = n_out * jnp.sum(me * ce)
        if dropless and e != n_out:
            # a choice of an expert held elsewhere becomes the sentinel e:
            # no row is computed for it and it adds nothing here
            local = experts - first_expert
            experts = jnp.where((local >= 0) & (local < e), local, e)
    if dropless:
        # unlimited capacity: every choice keeps, so `weights` arrives
        # normalized over all k choices — true dropless
        y = _dropless_mlp(hf, params, experts, weights, e, fused=fused)
        stats = _dispatch_stats(experts.reshape(-1), e)
        return whole(y), aux, stats

    def emm(x, w, eq):
        """Batched expert matmul; int8 stacks ({q, s}, models/quant.py)
        apply the [E, out] scale after the contraction — exact."""
        if isinstance(w, dict):
            return jnp.einsum(eq, x, w["q"].astype(x.dtype)) * w["s"].astype(
                x.dtype)[:, None, :]
        return jnp.einsum(eq, x, w)

    # tokens -> expert slots, by index: invert (expert, slot) -> token.
    # Unfilled slots and dropped tokens point at the sentinel row s, a
    # zero vector — slot uniqueness (sort-based assignment) makes set
    # order irrelevant; mode="drop" discards the sentinel writes themselves.
    flat = experts * c + slots  # [k, S] in [0, e*c)
    flat = jnp.where(keeps, flat, e * c)
    token_of_slot = jnp.full((e * c,), s, jnp.int32)
    arange_s = jnp.arange(s, dtype=jnp.int32)
    for k in range(flat.shape[0]):
        token_of_slot = token_of_slot.at[flat[k]].set(arange_s, mode="drop")
    hf_pad = jnp.concatenate([hf, jnp.zeros((1, d), hf.dtype)], axis=0)
    expert_in = hf_pad[token_of_slot].reshape(e, c, d)
    expert_in = constrain(expert_in, "expert", None, "embed")
    gate = jax.nn.silu(
        emm(expert_in, params["w1"], "ecd,edf->ecf").astype(jnp.float32)
    ).astype(h.dtype)
    up = emm(expert_in, params["w3"], "ecd,edf->ecf")
    out = emm(gate * up, params["w2"], "ecf,efd->ecd")
    out = constrain(out, "expert", None, "embed")
    # expert slots -> tokens: k weighted gathers (the reverse route)
    out_pad = jnp.concatenate(
        [out.reshape(e * c, d), jnp.zeros((1, d), out.dtype)], axis=0)
    y = jnp.zeros((s, d), h.dtype)
    for k in range(flat.shape[0]):
        y = y + weights[k][:, None].astype(h.dtype) * out_pad[flat[k]]
    return whole(y), aux, {}
