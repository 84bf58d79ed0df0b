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

Plain XLA: two projections on the MXU and K shifted multiply-adds that
fuse into one elementwise pass over [tokens, d].
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from kubedl_tpu.models.quant import matmul as _mm
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


def short_conv(u: jax.Array, layer: Dict) -> jax.Array:
    """The mixer's output for normed input u [b, t, d]."""
    b_, c_, z = jnp.split(_mm(u, layer["conv_in"]), 3, axis=-1)
    c = causal_taps(b_ * z, layer["conv_w"])
    return _mm(c_ * c, layer["conv_out"])
