"""Gated short convolution: the token mixer of a layer whose state is
not keys and values.

    [B, C, z] = split3(u @ W_in)          W_in  [d, 3d]
    g = B * z
    c[t] = sum_j w[:, j] * g[t - (K-1) + j]   w [d, K], depthwise, causal,
                                               g zero before the sequence
    mixer = (C * c) @ W_out               W_out [d, d]

`u` is the layer's normed input. The convolution reaches K - 1 tokens
back and no token ahead, so what a decoder would carry from step to step
is the last K - 1 rows of `g` per layer, not a key/value cache: training
needs no state at all, and the cached paths (models/decode.py,
models/serving.py) have none for it yet and refuse such a layer.

Two projections on the MXU, and between them the gates and the taps in
one of two forms, chosen by `conv_takes_kernel` from the shapes, the
backend and the mesh alone: on a TPU, where the sequence is whole
128-token blocks, d whole 128-lane blocks and 2 <= K <= 8, under no mesh
that shards the channels, ops/causal_conv.py's `short_conv_fwd`, which
reads B, C and z where they lie in the in projection's output and writes
the gated output once, and a hand-written backward `short_conv_bwd`,
which writes that output's cotangent [b, t, 3d] whole; everywhere else
(the CPU among them) the XLA operations of `causal_taps`, which fuse into
elementwise passes over [tokens, d], with autodiff's backward. The
kernels round where autodiff's jaxpr of the XLA form does: the gated
output is that form's bit for bit, the gradients its autodiff's but for
the order of the taps' float32 sum (ops/causal_conv.py says where XLA's
own fusion rounds less). `short_conv_kernel_layers` counts the layers
that took them.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from kubedl_tpu.models.quant import matmul as _mm
from kubedl_tpu.ops import causal_conv, interpret
from kubedl_tpu.parallel.mesh import ShardingRules


def short_conv_param_specs(rules: Optional[ShardingRules] = None) -> Dict:
    """PartitionSpec pytree matching short_conv_init()."""
    r = rules or ShardingRules()
    return {
        "conv_in": r.spec("embed", "mlp"),
        "conv_w": r.spec("mlp", None),
        "conv_out": r.spec("mlp", "embed"),
    }


def short_conv_init(key: jax.Array, d_model: int, kernel: int,
                    dtype=jnp.bfloat16) -> Dict:
    ks = jax.random.split(key, 3)

    def dense(k, shape, fan_in):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in))).astype(dtype)

    return {
        "conv_in": dense(ks[0], (d_model, 3 * d_model), d_model),
        "conv_w": dense(ks[1], (d_model, kernel), kernel),
        "conv_out": dense(ks[2], (d_model, d_model), d_model),
    }


def causal_taps(g: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal convolution over [b, t, d] with taps w [d, K]:
    tap K-1 weighs the token itself, tap 0 the one K-1 back. Summed in
    float32, returned in g's dtype."""
    t, k = g.shape[1], w.shape[1]
    gp = jnp.pad(g, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    wf = w.astype(jnp.float32)
    c = gp[:, 0:t] * wf[:, 0]
    for j in range(1, k):
        c = c + gp[:, j:j + t] * wf[:, j]
    return c.astype(g.dtype)


def conv_takes_kernel(seq: int, d: int, taps: int, mesh=None) -> bool:
    """Whether the gates and taps run as the Pallas kernels
    (ops/causal_conv.py): on a TPU, where B, C and z are whole 128-lane
    blocks of the in projection's output and the sequence whole 128-token
    blocks, and under no mesh that shards the channels (over `batch` it
    rides a shard_map, as models/ssm.py's convolution does)."""
    if interpret() or not causal_conv.supports(seq, 0, (d, d, d), taps):
        return False
    return mesh is None or mesh.shape.get("tensor", 1) == 1


def gated_taps(u: jax.Array, w: jax.Array, mesh=None,
               rules: Optional[ShardingRules] = None) -> Tuple[jax.Array, bool]:
    """C * causal_taps(B * z, w) for [B, C, z] = split3(u), u [b, t, 3d]
    the in projection's output, in u's dtype; and whether the kernels
    made it.

    One step in two forms, chosen by `conv_takes_kernel` from the shapes
    and the mesh: ops/causal_conv.py's kernels or the XLA operations
    below."""
    if conv_takes_kernel(u.shape[1], u.shape[2] // 3, w.shape[1], mesh):
        conv = causal_conv.gated_conv
        if mesh is not None and mesh.size > 1:
            # each device its own sequences; the taps' gradient is summed
            # over the devices by the map's transpose
            rows = (rules or ShardingRules()).spec("batch", None, None)
            conv = jax.shard_map(conv, mesh=mesh, in_specs=(rows, P()),
                                 out_specs=rows, check_vma=False)
        return conv(u, w), True
    b_, c_, z = jnp.split(u, 3, axis=-1)
    return c_ * causal_taps(b_ * z, w), False


def short_conv(u: jax.Array, layer: Dict, mesh=None,
               rules: Optional[ShardingRules] = None) -> Tuple[jax.Array, Dict]:
    """The mixer's output for normed input u [b, t, d], and the layer's
    counters: one layer, and whether its gates and taps ran as the
    kernels."""
    y, took = gated_taps(_mm(u, layer["conv_in"]), layer["conv_w"], mesh, rules)
    stats = {"short_conv_layers": jnp.ones((), jnp.float32),
             "short_conv_kernel_layers": jnp.asarray(took, jnp.float32)}
    return _mm(y, layer["conv_out"]), stats
