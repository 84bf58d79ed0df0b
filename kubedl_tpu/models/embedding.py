"""SparseCore-style sharded embeddings — TPU-native replacement for XDL's PS.

The reference's XDL workload (api/xdl/v1alpha1/types.go:83-99) holds its
sparse-embedding shards on parameter-server pods (PS replica type, reconciled
first — controllers/xdl/xdljob_controller.go:234-241); lookups and gradient
pushes are RPC round-trips to those servers. On TPU the same capability is
in-chip (SURVEY.md §2.4 "Parameter-server parallelism" row): embedding tables
are row-block-sharded over a mesh axis — the SPMD analogue of SparseCore's
row partitions — and a lookup is one collective over ICI instead of a PS RPC:

  * tables `[V, d]` carry `PartitionSpec(axis, None)` — shard s owns the
    contiguous row block `[s*V/n, (s+1)*V/n)`;
  * ids `[B, L]` are batch-sharded (replicated along the table axis), so
    inside `shard_map` every table shard sees its batch slice's full id set;
  * each shard does a masked local `take` of the rows it owns, then one
    `psum` over the table axis assembles complete embeddings — tiny compute,
    one ICI collective, no host round-trips;
  * the backward pass is the transpose: `psum`'s gradient is the identity
    broadcast and `take`'s gradient is a scatter-add into the owning shard
    only — exactly the PS "push" semantics, compiled by XLA.

Bag pooling (sum/mean over the multi-hot dim, `id < 0` = padding, optional
per-id weights) matches sparse-ads feature-group semantics.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from kubedl_tpu.parallel.mesh import BATCH_AXES

# Default mesh axis carrying table rows. "tensor" is the model-parallel axis;
# SparseCore-style deployments give it the whole slice (mesh {"tensor": N}).
TABLE_AXIS = "tensor"


@dataclass(frozen=True)
class FeatureSpec:
    """One sparse feature group (an XDL "feature column")."""

    name: str
    vocab_size: int
    dim: int
    multi_hot: int = 1  # ids per example (bag length, padded with -1)
    combiner: str = "sum"  # "sum" | "mean"


def round_up(v: int, n: int) -> int:
    return -(-v // n) * n


def table_spec(axis: str = TABLE_AXIS) -> P:
    """PartitionSpec for one embedding table: rows over `axis`."""
    return P(axis, None)


def table_specs(features: Tuple[FeatureSpec, ...], axis: str = TABLE_AXIS) -> Dict[str, P]:
    return {f.name: table_spec(axis) for f in features}


def init_table(
    key: jax.Array,
    vocab_size: int,
    dim: int,
    n_shards: int = 1,
    dtype=jnp.float32,
    scale: Optional[float] = None,
) -> jax.Array:
    """[round_up(vocab, n_shards), dim] table; padding rows train as dead rows."""
    rows = round_up(vocab_size, max(n_shards, 1))
    scale = scale if scale is not None else 1.0 / np.sqrt(dim)
    return (
        jax.random.truncated_normal(key, -2, 2, (rows, dim), jnp.float32) * scale
    ).astype(dtype)


def init_tables(
    key: jax.Array,
    features: Tuple[FeatureSpec, ...],
    n_shards: int = 1,
    dtype=jnp.float32,
) -> Dict[str, jax.Array]:
    keys = jax.random.split(key, len(features))
    return {
        f.name: init_table(k, f.vocab_size, f.dim, n_shards, dtype)
        for f, k in zip(features, keys)
    }


def sparse_lookup(
    table: jax.Array,  # [V, d], sharded P(axis, None)
    ids: jax.Array,  # [B, L] int32, -1 = padding; batch-sharded
    mesh: Mesh,
    *,
    axis: str = TABLE_AXIS,
    weights: Optional[jax.Array] = None,  # [B, L] per-id weights
    combiner: Optional[str] = "sum",  # "sum" | "mean" | None (no pooling)
    batch_axes=BATCH_AXES,
) -> jax.Array:
    """Pooled [B, d] (or [B, L, d] with combiner=None) embedding lookup.

    One masked local gather per table shard + one psum over `axis`; the
    gradient scatter-adds into the owning shard only.
    """
    n_shards = mesh.shape[axis]
    if table.shape[0] % n_shards:
        raise ValueError(
            f"table rows {table.shape[0]} not divisible by mesh axis "
            f"{axis!r}={n_shards}; pad with round_up()"
        )
    if weights is None:
        weights = jnp.ones(ids.shape, jnp.float32)

    def pool(emb, ids_l, w_l):
        mask = (ids_l >= 0).astype(jnp.float32)
        wm = (w_l * mask)[..., None].astype(emb.dtype)
        if combiner is None:
            return emb * wm
        pooled = jnp.sum(emb * wm, axis=-2)
        if combiner == "mean":
            denom = jnp.sum(wm, axis=-2)
            pooled = pooled / jnp.maximum(denom, jnp.asarray(1e-9, denom.dtype))
        return pooled

    if n_shards == 1:
        # Single-shard fast path: the ownership mask and psum are no-ops,
        # and skipping shard_map lets XLA fuse the plain gather+pool (the
        # padded -1 ids still gather row 0 but are zeroed by the mask).
        d = table.shape[1]
        safe = jnp.maximum(ids, 0)
        emb = jnp.take(table, safe.reshape(-1), axis=0).reshape(*ids.shape, d)
        return pool(emb, ids, weights)

    bspec = P(batch_axes) if isinstance(batch_axes, str) else P(tuple(batch_axes))
    ids_spec = P(bspec[0], None)
    out_spec = ids_spec if combiner else P(bspec[0], None, None)

    def body(tab, ids_l, w_l):
        rows, d = tab.shape
        shard = jax.lax.axis_index(axis)
        local = ids_l - shard * rows
        owned = (ids_l >= 0) & (local >= 0) & (local < rows)
        safe = jnp.where(owned, local, 0)
        emb = jnp.take(tab, safe.reshape(-1), axis=0).reshape(*ids_l.shape, d)
        emb = jnp.where(owned[..., None], emb, jnp.zeros((), tab.dtype))
        emb = jax.lax.psum(emb, axis)
        return pool(emb, ids_l, w_l)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis, None), ids_spec, ids_spec),
        out_specs=out_spec,
        check_vma=False,
    )(table, ids, weights)


def lookup_features(
    tables: Dict[str, jax.Array],
    batch_ids: Dict[str, jax.Array],
    features: Tuple[FeatureSpec, ...],
    mesh: Mesh,
    *,
    axis: str = TABLE_AXIS,
    batch_axes=BATCH_AXES,
) -> jax.Array:
    """Concatenate pooled embeddings of every feature group -> [B, sum(dims)]."""
    outs = []
    for f in features:
        outs.append(
            sparse_lookup(
                tables[f.name],
                batch_ids[f.name],
                mesh,
                axis=axis,
                combiner=f.combiner,
                batch_axes=batch_axes,
            )
        )
    return jnp.concatenate(outs, axis=-1)
