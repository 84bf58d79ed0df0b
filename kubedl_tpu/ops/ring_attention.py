"""Ring attention — context-parallel attention over the mesh "context" axis.

Long-context path (SURVEY.md §5/§7 step 9 — entirely absent in the
reference): the sequence is sharded across devices; K/V chunks rotate around
the ring with jax.lax.ppermute (XLA lowers to ICI neighbor transfers —
the slice admitter places consecutive ranks on ICI-adjacent hosts via
executor/tpu_topology.ring_order), while each device's Q stays resident.
Per-chunk partial attentions merge through their log-sum-exp, so softmax
normalization is exact regardless of arrival order.

Implementation notes:
  * the per-step chunk attention is wrapped in jax.checkpoint so autodiff
    recomputes the [Tq_local, Tk_chunk] scores instead of saving c of them —
    activation memory stays O(T/c * d) per device;
  * communication overlaps compute: ppermute of the NEXT chunk is issued
    alongside the CURRENT chunk's attention inside one lax.scan step, and
    XLA schedules the transfer behind the matmuls;
  * causal masking is by global position: chunks entirely in the future are
    skipped via a zero-weight merge (lse = -inf), the diagonal chunk gets a
    triangular mask.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30


def _chunk_attention(q, k, v, sm_scale, causal_mode, q_offset, k_offset):
    """Partial attention of local Q against one K/V chunk.

    causal_mode: 0 = full (chunk entirely in the past), 1 = diagonal
    (triangular mask), 2 = skip (entirely in the future).
    Returns (out [b,h,tq,d] f32, lse [b,h,tq] f32).
    """
    b, h, tq, d = q.shape
    tk = k.shape[2]
    # bf16 inputs straight into the MXU (full-rate); f32 accumulation via
    # preferred_element_type — casting to f32 first would run the MXU at
    # its reduced f32 rate.
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * sm_scale
    q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    k_pos = k_offset + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    diag_mask = k_pos <= q_pos
    mask = jnp.where(
        causal_mode == 1,
        diag_mask,
        jnp.full_like(diag_mask, True),
    )
    mask = jnp.where(causal_mode == 2, jnp.zeros_like(mask), mask)
    s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)  # [b,h,tq]
    # fully-masked rows: keep exp() finite
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(s - m_safe[..., None])
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    lse = jnp.where(l > 0, m_safe + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)
    out = jnp.where(l[..., None] > 0, out / jnp.maximum(l[..., None], 1e-30), 0.0)
    return out, lse


def _merge(o1, lse1, o2, lse2):
    """Merge two partial attentions via their log-sum-exp."""
    m = jnp.maximum(lse1, lse2)
    m = jnp.maximum(m, NEG_INF / 2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    tot = jnp.maximum(w1 + w2, 1e-30)
    out = (o1 * w1[..., None] + o2 * w2[..., None]) / tot[..., None]
    lse = m + jnp.log(tot)
    return out, lse


def _ring_attention_sharded(q, k, v, *, axis_name, sm_scale, causal):
    """Runs inside shard_map: q/k/v are the LOCAL sequence chunks."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, h, tq, d = q.shape
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    o0 = jnp.zeros((b, h, tq, d), jnp.float32)
    lse0 = jnp.full((b, h, tq), NEG_INF, jnp.float32)

    @jax.checkpoint
    def chunk_step(q, k, v, kv_idx):
        if causal:
            mode = jnp.where(kv_idx < my_idx, 0, jnp.where(kv_idx == my_idx, 1, 2))
        else:
            mode = jnp.zeros((), jnp.int32)
        return _chunk_attention(
            q, k, v, sm_scale, mode, my_idx * tq, kv_idx * tq
        )

    def scan_body(carry, step):
        o, lse, k_cur, v_cur = carry
        kv_idx = (my_idx - step) % axis_size
        o_c, lse_c = chunk_step(q, k_cur, v_cur, kv_idx)
        o, lse = _merge(o, lse, o_c, lse_c)
        # rotate KV to the next rank; XLA overlaps this with the matmuls
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o, lse, k_nxt, v_nxt), None

    (o, lse, _, _), _ = jax.lax.scan(
        scan_body, (o0, lse0, k, v), jnp.arange(axis_size)
    )
    return o.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis_name: str = "context",
    causal: bool = True,
    sm_scale: Optional[float] = None,
    q_spec: P = P(("data", "fsdp"), "tensor", "context", None),
) -> jax.Array:
    """Context-parallel attention over [batch, heads, seq, head_dim].

    The seq dimension is sharded over `axis_name`; batch/heads follow
    `q_spec`. GQA broadcast should be done by the caller (models/llama.py
    does) so the ring rotates the small KV tensors.
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    fn = functools.partial(
        _ring_attention_sharded, axis_name=axis_name, sm_scale=sm_scale, causal=causal
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(q_spec, q_spec, q_spec), out_specs=q_spec,
        check_vma=False,
    )(q, k, v)
