"""Pipeline parallelism — GPipe schedule over the mesh's "stage" axis.

The reference has no pipeline parallelism anywhere (SURVEY.md §2.4: "Pipeline
parallelism (PP): absent"); this is the net-new TPU-native implementation the
JAXJob mesh spec promises. Design is the canonical TPU pipelining recipe, not
a send/recv translation:

  * layers are stacked on a leading dim and sharded over the "stage" mesh
    axis, so each stage holds `n_layers / n_stages` layers;
  * a single `shard_map` runs the classic GPipe loop: at step i, stage 0
    ingests microbatch i, every stage applies its local layers (a
    `lax.scan` over the stacked leaf dim), and activations rotate to the
    next stage with one `ppermute` — a nearest-neighbor ICI hop, the
    cheapest collective on a TPU torus;
  * the loop itself is a `lax.scan` over `n_microbatches + n_stages - 1`
    steps — static control flow, one compiled program, no per-step
    dispatch;
  * autodiff flows through scan+ppermute, so `jax.grad` of a pipelined
    loss is the pipelined backward pass for free.

Composes with data parallelism (batch sharded over data+fsdp, params
replicated across those axes inside the stage shard_map) and with MoE
layers (experts replicated per stage, aux loss threaded through the
schedule — models/llama.py forward_pipelined_and_aux). Tensor/context/
expert MESH AXES inside a pipelined layer would need manual collectives
in shard_map and stay out of scope for the pipelined path — use
tp/cp/ep on the non-pipelined forward instead.
"""
from __future__ import annotations

from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from kubedl_tpu.api.validation import validate_pipeline_shapes
from kubedl_tpu.parallel.mesh import BATCH_AXES


def schedule_steps(n_micro: int, n_stages: int, interleave: int = 1) -> int:
    """Sequential sub-steps one schedule round takes. GPipe (interleave=1)
    runs M + S - 1 full-stage steps; the interleaved circular schedule
    runs M*v + S - 1 steps of 1/v the per-step work."""
    return n_micro * interleave + n_stages - 1


def bubble_fraction(n_micro: int, n_stages: int, interleave: int = 1) -> float:
    """Fill/drain bubble fraction of the schedule: (S-1)/(M*v + S-1).

    Each rank does M*v useful chunk-steps out of M*v + S - 1 total — the
    interleave-v schedule keeps the same S-1 idle chunk-steps but each
    chunk-step is 1/v the work, so the wasted FRACTION shrinks by ~1/v
    (the MPMD pipeline-parallelism paper's first-order bubble model)."""
    return (n_stages - 1) / schedule_steps(n_micro, n_stages, interleave)


def interleaved_layer_order(
    n_layers: int, n_stages: int, interleave: int
) -> np.ndarray:
    """Layer permutation for the interleaved schedule's stacked layout.

    The stacked-params leading dim is sharded contiguously over "stage"
    (rank s holds block [s*L/S, (s+1)*L/S)), but the interleaved schedule
    assigns rank s the NON-contiguous chunks {r*S + s : r < v} (each
    chunk is L/(S*v) layers). This permutation reorders natural layer
    order so each rank's contiguous block holds exactly its v chunks, in
    local chunk order — gather stacked leaves with it before shard_map.
    """
    chunk_len = n_layers // (n_stages * interleave)
    order = []
    for s in range(n_stages):
        for r in range(interleave):
            c = r * n_stages + s
            order.extend(range(c * chunk_len, (c + 1) * chunk_len))
    return np.asarray(order, dtype=np.int32)


def stack_layers(layers: Sequence[Any]) -> Any:
    """[{leaf...}] * L  ->  {leaf: [L, ...]} — the stacked-params layout the
    pipeline (and `lax.scan` over layers generally) wants."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)


def unstack_layers(stacked: Any, n_layers: int) -> list:
    return [
        jax.tree_util.tree_map(lambda x: x[i], stacked) for i in range(n_layers)
    ]


def pipeline_apply(
    stacked_params: Any,
    x_microbatches: jax.Array,  # [n_micro, micro_batch, ...feature dims]
    layer_fn: Callable[[jax.Array, Any], jax.Array],
    *,
    mesh: Mesh,
    stage_axis: str = "stage",
    batch_axes: Tuple[str, ...] = BATCH_AXES,
    remat: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Run every microbatch through all pipeline stages; returns
    (activations shaped like `x_microbatches`, aux_total scalar).

    `stacked_params` leaves have leading dim n_layers (divisible by the
    stage-axis size); `layer_fn(act, layer_params) -> (act, aux_scalar)`
    applies ONE layer, must be shape-preserving, and reports a per-layer
    aux scalar — e.g. the MoE load-balance loss (dense layers return a
    zero scalar). Microbatch dim 0 is the pipeline's time axis; dim 1
    (micro batch) is sharded over `batch_axes`.

    Aux contributions are gated to each stage's VALID window (the GPipe
    fill/drain steps feed clipped garbage that must not count), summed
    over this stage's layers and steps, psummed across stages, and
    averaged over microbatches — the microbatch-mean approximation of
    the full-batch aux every per-shard MoE implementation uses.
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = x_microbatches.shape[0]
    if n_micro < n_stages:
        raise ValueError(
            f"need >= {n_stages} microbatches to fill a {n_stages}-stage "
            f"pipeline, got {n_micro}"
        )
    n_layers = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if n_layers % n_stages:
        raise ValueError(
            f"stacked layer count {n_layers} not divisible by the "
            f"{stage_axis}-axis size {n_stages}"
        )
    x_rank = x_microbatches.ndim

    per_layer = layer_fn
    if remat:
        per_layer = jax.checkpoint(per_layer)

    def run_local_layers(act, params_local):
        def body(carry, layer):
            a, aux = carry
            a, da = per_layer(a, layer)
            return (a, aux + da), None

        (act, aux), _ = jax.lax.scan(
            body, (act, jnp.zeros((), jnp.float32)), params_local)
        return act, aux

    perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]
    n_steps = n_micro + n_stages - 1

    def pipelined(params_local, x_mub):
        stage = jax.lax.axis_index(stage_axis)
        out_buf = jnp.zeros_like(x_mub)
        act = jnp.zeros_like(x_mub[0])

        def step(carry, i):
            act, out_buf, aux_acc = carry
            # stage 0 ingests microbatch i (clipped: trailing drain steps
            # feed garbage that never reaches an output slot)
            inp = jax.lax.dynamic_index_in_dim(
                x_mub, jnp.clip(i, 0, n_micro - 1), 0, keepdims=False
            )
            act = jnp.where(stage == 0, inp, act)
            act, aux = run_local_layers(act, params_local)
            # stage s does REAL work on microbatch i-s; fill/drain steps
            # process clipped garbage whose aux must not count
            valid = jnp.logical_and(i - stage >= 0, i - stage < n_micro)
            aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
            # last stage banks finished microbatch i-(n_stages-1)
            out_idx = jnp.clip(i - (n_stages - 1), 0, n_micro - 1)
            cur = jax.lax.dynamic_index_in_dim(out_buf, out_idx, 0, keepdims=False)
            bank = jnp.where(
                jnp.logical_and(stage == n_stages - 1, i >= n_stages - 1), act, cur
            )
            out_buf = jax.lax.dynamic_update_index_in_dim(out_buf, bank, out_idx, 0)
            # rotate activations one ICI hop to the next stage
            act = jax.lax.ppermute(act, stage_axis, perm)
            return (act, out_buf, aux_acc), None

        (act, out_buf, aux_acc), _ = jax.lax.scan(
            step, (act, out_buf, jnp.zeros((), jnp.float32)),
            jnp.arange(n_steps, dtype=jnp.int32)
        )
        # every stage contributes its own layers' aux; mean over
        # microbatches approximates the full-batch value, pmean over the
        # batch axes makes it a true global (replicated) scalar
        aux_total = jax.lax.psum(aux_acc, stage_axis) / n_micro
        aux_total = jax.lax.pmean(aux_total, batch_axes)
        # leading singleton picks out this stage's copy; only the last
        # stage's buffer holds real outputs and the caller slices it.
        return out_buf[None], aux_total

    params_spec = jax.tree_util.tree_map(lambda _: P(stage_axis), stacked_params)
    x_spec = P(None, batch_axes, *([None] * (x_rank - 2)))
    out_spec = P(stage_axis, None, batch_axes, *([None] * (x_rank - 2)))

    out, aux = jax.shard_map(
        pipelined,
        mesh=mesh,
        in_specs=(params_spec, x_spec),
        out_specs=(out_spec, P()),
        check_vma=False,
    )(stacked_params, x_microbatches)
    return out[-1], aux


def pipeline_apply_1f1b(
    stacked_params: Any,
    x_microbatches: jax.Array,  # [n_micro, micro_batch, ...feature dims]
    layer_fn: Callable[[jax.Array, Any], jax.Array],
    *,
    mesh: Mesh,
    interleave: int = 1,
    stage_axis: str = "stage",
    batch_axes: Tuple[str, ...] = BATCH_AXES,
    remat: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Interleaved circular 1F1B schedule (virtual pipeline stages).

    Same contract as `pipeline_apply` (which stays the GPipe parity
    oracle), but each rank holds `interleave` NON-contiguous layer chunks
    and every microbatch circulates the ring `interleave` times: rank s,
    repeat r applies global chunk r*S + s at work index u = r*M + i, step
    t = u + s. An activation leaving the last rank at repeat r < v-1
    wraps to rank 0 (through a per-rank wrap buffer: the ring ppermute
    delivers it S steps after it was computed, and rank 0 holds it until
    step (r+1)*M + i — which requires M >= S, the same fill constraint
    GPipe has). The loop is one `lax.scan` over M*v + S - 1 sub-steps,
    each costing 1/v of a GPipe step — the fill/drain bubble FRACTION
    drops from (S-1)/(M+S-1) to (S-1)/(M*v+S-1), ~1/v (bubble_fraction).

    `interleave=1` degenerates to the GPipe schedule on a different code
    path (wrap buffer never used) — the parity tests pin all three ways.
    Autodiff through scan+ppermute+gather gives the pipelined backward;
    the steady-state one-forward-one-backward alternation of true 1F1B
    is realized in the MPMD runtime (train/pipeline_runtime.py), where
    forward and backward are separate per-microbatch programs.
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = x_microbatches.shape[0]
    n_layers = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    errs = validate_pipeline_shapes(
        n_stages, n_micro, interleave, n_layers=n_layers,
        path="pipeline_apply_1f1b")
    if errs:
        raise ValueError("; ".join(errs))
    v = interleave
    chunk_len = n_layers // (n_stages * v)
    x_rank = x_microbatches.ndim

    per_layer = layer_fn
    if remat:
        per_layer = jax.checkpoint(per_layer)

    def run_chunk(act, chunk_params):
        def body(carry, layer):
            a, aux = carry
            a, da = per_layer(a, layer)
            return (a, aux + da), None

        (act, aux), _ = jax.lax.scan(
            body, (act, jnp.zeros((), jnp.float32)), chunk_params)
        return act, aux

    # reorder layers so each rank's contiguous stacked block holds its v
    # chunks (differentiable gather: grads scatter back to natural order)
    order = jnp.asarray(interleaved_layer_order(n_layers, n_stages, v))
    permuted = jax.tree_util.tree_map(lambda p: p[order], stacked_params)

    ring = [(j, (j + 1) % n_stages) for j in range(n_stages)]
    n_work = n_micro * v
    n_steps = n_work + n_stages - 1

    def pipelined(params_local, x_mub):
        stage = jax.lax.axis_index(stage_axis)
        out_buf = jnp.zeros_like(x_mub)
        wrap_buf = jnp.zeros_like(x_mub)
        act = jnp.zeros_like(x_mub[0])
        # local block [v*chunk_len, ...] -> [v, chunk_len, ...] for the
        # traced repeat-index gather
        chunks = jax.tree_util.tree_map(
            lambda p: p.reshape((v, chunk_len) + p.shape[1:]), params_local)

        def step(carry, t):
            act, out_buf, wrap_buf, aux_acc = carry
            u = t - stage  # this rank's work index at step t
            valid = jnp.logical_and(u >= 0, u < n_work)
            uc = jnp.clip(u, 0, n_work - 1)
            r, mb = uc // n_micro, uc % n_micro
            # -- rank 0: bank the wrapped activation that just arrived.
            # The carried `act` was sent by rank S-1 at step t-1, work
            # index t - S; repeats below v-1 recirculate (the final
            # repeat's output banks into out_buf instead).
            us = jnp.clip(t - n_stages, 0, n_work - 1)
            r_s, mb_s = us // n_micro, us % n_micro
            wrap_store = jnp.logical_and(
                jnp.logical_and(stage == 0, r_s < v - 1),
                jnp.logical_and(t - n_stages >= 0, t - n_stages < n_work))
            cur_wrap = jax.lax.dynamic_index_in_dim(
                wrap_buf, mb_s, 0, keepdims=False)
            wrap_buf = jax.lax.dynamic_update_index_in_dim(
                wrap_buf, jnp.where(wrap_store, act, cur_wrap), mb_s, 0)
            # -- rank 0 input: fresh microbatch on repeat 0, the wrap
            # buffer afterwards (store-before-read covers M == S, where
            # the wrap arrives exactly when it is needed)
            fresh = jax.lax.dynamic_index_in_dim(x_mub, mb, 0, keepdims=False)
            wrapped = jax.lax.dynamic_index_in_dim(
                wrap_buf, mb, 0, keepdims=False)
            act = jnp.where(stage == 0, jnp.where(r == 0, fresh, wrapped), act)
            # -- apply this rank's repeat-r chunk
            chunk = jax.tree_util.tree_map(
                lambda p: jax.lax.dynamic_index_in_dim(p, r, 0, keepdims=False),
                chunks)
            act, aux = run_chunk(act, chunk)
            aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
            # -- last rank, final repeat: bank finished microbatch mb
            bank = jnp.logical_and(
                jnp.logical_and(stage == n_stages - 1, valid), r == v - 1)
            cur_out = jax.lax.dynamic_index_in_dim(out_buf, mb, 0, keepdims=False)
            out_buf = jax.lax.dynamic_update_index_in_dim(
                out_buf, jnp.where(bank, act, cur_out), mb, 0)
            # -- rotate one ICI hop (S-1 -> 0 carries the wrap)
            act = jax.lax.ppermute(act, stage_axis, ring)
            return (act, out_buf, wrap_buf, aux_acc), None

        (act, out_buf, wrap_buf, aux_acc), _ = jax.lax.scan(
            step, (act, out_buf, wrap_buf, jnp.zeros((), jnp.float32)),
            jnp.arange(n_steps, dtype=jnp.int32)
        )
        # every layer contributes once per microbatch, same normalization
        # as the GPipe oracle: psum stage contributions, mean over
        # microbatches, pmean to a replicated scalar over batch axes
        aux_total = jax.lax.psum(aux_acc, stage_axis) / n_micro
        aux_total = jax.lax.pmean(aux_total, batch_axes)
        return out_buf[None], aux_total

    params_spec = jax.tree_util.tree_map(lambda _: P(stage_axis), permuted)
    x_spec = P(None, batch_axes, *([None] * (x_rank - 2)))
    out_spec = P(stage_axis, None, batch_axes, *([None] * (x_rank - 2)))

    out, aux = jax.shard_map(
        pipelined,
        mesh=mesh,
        in_specs=(params_spec, x_spec),
        out_specs=(out_spec, P()),
        check_vma=False,
    )(permuted, x_microbatches)
    return out[-1], aux


def microbatch(x: jax.Array, n_microbatches: int) -> jax.Array:
    """[B, ...] -> [n_micro, B/n_micro, ...]."""
    if x.shape[0] % n_microbatches:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by {n_microbatches} microbatches"
        )
    return x.reshape((n_microbatches, x.shape[0] // n_microbatches) + x.shape[1:])


def unmicrobatch(x: jax.Array) -> jax.Array:
    """[n_micro, mb, ...] -> [n_micro*mb, ...]."""
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])
