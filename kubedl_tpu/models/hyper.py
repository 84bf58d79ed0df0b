"""Manifold-constrained hyper-connections: a residual path of n streams.

A layer's residual is not one `x` but `X` of n streams, [b, t, n, d]. Each
sublayer F (a mixer, an FFN) reads one mix of the streams and writes back
into all of them, by three mappings made from the token's own streams
(mHC, arXiv:2512.24880, over the streams of Hyper-Connections,
arXiv:2409.19606):

    x~     = vec(X) / sqrt(mean(vec(X)^2) + eps)            over all n*d, no learned scale
    H~pre  = a_pre  (x~ P_pre)  + b_pre     in R^n          P_pre, P_post [n*d, n]
    H~post = a_post (x~ P_post) + b_post    in R^n          P_res [n*d, n*n]
    H~res  = a_res mat(x~ P_res) + b_res    in R^{n x n}    a_* scalars
    H_pre  = sigmoid(H~pre);  H_post = 2 sigmoid(H~post)
    M = exp(clip(H~res, lo, hi)); `iters` times: M <- M / (colsum(M) + eps);
                                                 M <- M / (rowsum(M) + eps);  H_res = M
    u = sum_j H_pre[j] X[j];  y = F(u);  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

Sinkhorn-Knopp's alternation drives `H_res` to a doubly stochastic matrix,
so the streams' mean passes a layer unscaled however deep the stack. The
mappings, the iterations and the two mixes accumulate in float32; the
streams are stored in the model's dtype. Plain XLA: the projections are
one [tokens, n*d] x [n*d, n*(n+2)] product, the rest elementwise passes
over the streams (`hc_map`, `hc_mix` in a device trace).

Training only: the paths that carry state from token to token hold one
residual a token and refuse a model of several streams
(LlamaConfig.require_one_stream).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubedl_tpu.parallel.mesh import ShardingRules


def hc_param_specs(rules: Optional[ShardingRules] = None) -> Dict:
    """PartitionSpec pytree matching hc_init(): one sublayer's mappings,
    small enough to live whole on every device."""
    r = rules or ShardingRules()
    return {"p_pre": r.spec(None, None), "p_post": r.spec(None, None),
            "p_res": r.spec(None, None), "b_pre": r.spec(None),
            "b_post": r.spec(None), "b_res": r.spec(None, None),
            "a_pre": r.spec(), "a_post": r.spec(), "a_res": r.spec()}


def hc_init(key: jax.Array, d_model: int, n: int) -> Dict:
    """One sublayer's mappings, float32, started where the streams pass
    nearly unmixed (the papers' start): `H_res` near the identity, `H_pre`
    picking the streams evenly, `H_post` near 1, the dynamic part small."""
    ks = jax.random.split(key, 3)
    std = 1.0 / np.sqrt(n * d_model)

    def proj(k, cols):
        return jax.random.normal(k, (n * d_model, cols), jnp.float32) * std

    return {"p_pre": proj(ks[0], n), "p_post": proj(ks[1], n),
            "p_res": proj(ks[2], n * n),
            "b_pre": jnp.zeros((n,), jnp.float32),
            "b_post": jnp.zeros((n,), jnp.float32),
            "b_res": 4.0 * jnp.eye(n, dtype=jnp.float32),
            "a_pre": jnp.full((), 0.01, jnp.float32),
            "a_post": jnp.full((), 0.01, jnp.float32),
            "a_res": jnp.full((), 0.01, jnp.float32)}


def sinkhorn(logits: jax.Array, iters: int, eps: float,
             clamp: Tuple[float, float]) -> jax.Array:
    """[.., n, n] float32 -> the Sinkhorn-Knopp projection of
    exp(clip(logits)) towards the doubly stochastic matrices: columns
    first, then rows, `iters` times."""
    m = jnp.exp(jnp.clip(logits, clamp[0], clamp[1]))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


@jax.named_scope("hc_map")
def hc_map(x: jax.Array, hc: Dict, iters: int, eps: float,
           clamp: Tuple[float, float]) -> Dict:
    """The three mappings of a sublayer from the streams x [b, t, n, d]:
    `pre` [b, t, n], `post` [b, t, n], `res` [b, t, n, n], float32, and
    the sublayer's counters under `stats`."""
    b, t, n, d = x.shape
    flat = x.reshape(b, t, n * d).astype(jnp.float32)
    # x~ P = (vec(X) P) / rms: the norm is one scalar a token, applied to
    # the n * (n + 2) products and never to a float32 copy of the streams
    w = jnp.concatenate([hc["p_pre"], hc["p_post"], hc["p_res"]], axis=1)
    proj = jnp.dot(flat, w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    inv = jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
    proj = proj * inv
    pre = jax.nn.sigmoid(hc["a_pre"] * proj[..., :n] + hc["b_pre"])
    post = 2.0 * jax.nn.sigmoid(hc["a_post"] * proj[..., n:2 * n] + hc["b_post"])
    res = sinkhorn(
        hc["a_res"] * proj[..., 2 * n:].reshape(b, t, n, n) + hc["b_res"],
        iters, eps, clamp)
    diag = jnp.mean(jnp.sum(jnp.diagonal(res, axis1=-2, axis2=-1), axis=-1))
    off_one = jnp.maximum(jnp.max(jnp.abs(jnp.sum(res, axis=-1) - 1.0)),
                          jnp.max(jnp.abs(jnp.sum(res, axis=-2) - 1.0)))
    stats = {"hc_mappings": jnp.ones((), jnp.float32),
             # mean mass of H_res off its diagonal, a row's mass being 1:
             # 0 = streams that never mix, (n - 1) / n = uniform mixing
             "hc_res_offdiag": jax.lax.stop_gradient(
                 (jnp.mean(jnp.sum(res, axis=(-2, -1))) - diag) / n),
             "hc_sinkhorn_residual": jax.lax.stop_gradient(off_one),
             "hc_pre_mean": jax.lax.stop_gradient(jnp.mean(pre)),
             "hc_post_mean": jax.lax.stop_gradient(jnp.mean(post))}
    return {"pre": pre, "post": post, "res": res, "stats": stats}


# The two mixes are written stream by stream: n (or n * n) multiply-adds
# of [b, t, d] that fuse into one elementwise pass in float32. As an
# einsum they would be a matrix product with a contraction of n = 4, whose
# float32 operands a TPU rounds to bfloat16 on the way in.


@jax.named_scope("hc_mix")
def hc_pre(x: jax.Array, mapping: Dict) -> jax.Array:
    """What the sublayer reads: u = sum_j H_pre[j] X[j], [b, t, d]."""
    xf, pre = x.astype(jnp.float32), mapping["pre"]
    u = sum(pre[:, :, j, None] * xf[:, :, j] for j in range(x.shape[2]))
    return u.astype(x.dtype)


@jax.named_scope("hc_mix")
def hc_mix(x: jax.Array, y: jax.Array, mapping: Dict) -> jax.Array:
    """X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y: the streams after the
    sublayer's output y [b, t, d]."""
    xf, res = x.astype(jnp.float32), mapping["res"]
    mixed = mapping["post"][..., None] * y.astype(jnp.float32)[:, :, None, :]
    for j in range(x.shape[2]):
        mixed = mixed + res[:, :, :, j, None] * xf[:, :, j, None, :]
    return mixed.astype(x.dtype)


# the one counter that layers combine by their largest, not their sum
WORST_OF = ("hc_sinkhorn_residual",)


def finish_stats(stats: Dict) -> Dict:
    """The step's hc_* counters from the sublayers' sums: the count stays
    a sum, the means are over the mappings, the residual is the worst
    mapping's (`WORST_OF`: whoever adds counters up takes its largest)."""
    if "hc_mappings" not in stats:
        return stats
    out = dict(stats)
    for k in ("hc_res_offdiag", "hc_pre_mean", "hc_post_mean"):
        out[k] = out[k] / out["hc_mappings"]
    return out
