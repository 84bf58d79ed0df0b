"""Ulysses-style sequence parallelism — all-to-all head<->sequence swap.

The second of the two long-context strategies (alongside
ops/ring_attention.py; the reference has neither — SURVEY §5
"long-context: entirely absent"). Where ring attention keeps queries
local and ROTATES K/V around the mesh (P-1 ppermute hops overlapped
with compute), Ulysses runs TWO all-to-alls: the sequence-sharded
[b, h, t/P, d] projections swap into head-sharded [b, h/P, t, d], each
rank computes ordinary full-sequence attention for its head group (the
flash kernel applies unchanged), and one all-to-all swaps back.

Trade-off (why both exist): Ulysses moves each token's Q,K,V,O exactly
once (4 all-to-alls of 1/P-sized tensors) regardless of sequence length
— cheaper than the ring when P is small and heads are plentiful — but
its parallelism is capped at n_kv_heads and the full-sequence scores
live on one rank; the ring scales to any P and keeps score memory at
t/P per rank. Both ride the ICI `context` axis placed innermost by
AXIS_ORDER (parallel/mesh.py).

Public entry matches ring_attention's, so models swap strategies by
name (LlamaConfig.context_parallel = "ring" | "ulysses").
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def _ulysses_sharded(q, k, v, *, axis_name, sm_scale, causal, use_flash):
    """Runs inside shard_map: q/k/v are [b, h, t_local, d] seq shards."""
    def seq_to_heads(x):
        # [b, h, t/P, d] -> [b, h/P, t, d]: split heads, gather sequence
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    q, k, v = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if use_flash:
        from kubedl_tpu.ops.flash_attention import flash_attention

        o = flash_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    else:
        from kubedl_tpu.ops.flash_attention import attention_reference

        o = attention_reference(q, k, v, causal=causal, sm_scale=sm_scale)
    return heads_to_seq(o)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis_name: str = "context",
    causal: bool = True,
    sm_scale: Optional[float] = None,
    use_flash: bool = False,
    q_spec: P = P(("data", "fsdp"), "tensor", "context", None),
) -> jax.Array:
    """Sequence-parallel attention over [batch, heads, seq, head_dim]
    with the seq dim sharded over `axis_name`.

    Heads must divide by the context-axis size (after any tensor-axis
    head sharding) — Ulysses' parallelism lives in the head dimension.
    GQA broadcast must happen in the caller (models/llama.py does), so
    K/V enter with the same head count as Q.
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    ctx = mesh.shape.get(axis_name, 1)
    heads = q.shape[1]
    tensor = mesh.shape.get("tensor", 1)
    local_heads = heads // max(tensor, 1)
    if local_heads % ctx != 0:
        raise ValueError(
            f"ulysses needs heads-per-tensor-shard ({local_heads}) divisible "
            f"by the context axis ({ctx}); use ring attention instead")
    fn = functools.partial(
        _ulysses_sharded, axis_name=axis_name, sm_scale=sm_scale,
        causal=causal, use_flash=use_flash,
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(q_spec, q_spec, q_spec), out_specs=q_spec,
        check_vma=False,
    )(q, k, v)
