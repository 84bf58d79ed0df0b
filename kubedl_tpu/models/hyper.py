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
streams are stored in the model's dtype. A model carries them as one
array [b, t, n*d], stream j at lanes j*d .. (`hc_streams`, `hc_sum`), so
that a token's streams are one dense row.

One algorithm in two forms, chosen by `mix_takes_kernel` from shapes,
backend and mesh (`hc_branch`, `hc_merge`): on a TPU at shapes of whole
tiles, ops/hyper_mix.py's kernels, which read the streams once a pass and
keep their float32 in VMEM (`hc_pre_fwd`: the norm, the projections and
u; `hc_post_fwd`: the streams after the sublayer; hand-written backwards
`hc_post_bwd`, `hc_pre_bwd`, the second making all of the streams'
cotangent), with the sigmoids and Sinkhorn-Knopp over each token's
n (n + 2) logits left to XLA operations (`_post_res`, run by `pre` and
differentiated in its backward); everywhere else the XLA
operations below (`hc_map`, `hc_pre`, `hc_mix`), the projections one
[tokens, n*d] x [n*d, n*(n+2)] product and the rest elementwise passes.
`hc_kernel_mappings` counts the mappings that took the kernels. Both
read as `hc_map` and `hc_mix` in a device trace.

Training only: the paths that carry state from token to token hold one
residual a token and refuse a model of several streams
(LlamaConfig.require_one_stream).
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from kubedl_tpu import ops
from kubedl_tpu.ops import hyper_mix
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
    first, then rows, `iters` times. A loop in the program: unrolled, each
    iteration's passes and their backward are fusions of their own, whose
    code grows with the tokens (19 MB of a TPU executable a mapping at
    16,384 tokens, 1.7 MB as a loop)."""
    def iteration(_, m):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-1, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, iteration,
                             jnp.exp(jnp.clip(logits, clamp[0], clamp[1])))


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
    proj = jnp.dot(flat, _projection(hc).astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    inv = jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
    return _mappings(proj * inv, hc, n, iters, eps, clamp)


def _projection(hc: Dict) -> jax.Array:
    return jnp.concatenate([hc["p_pre"], hc["p_post"], hc["p_res"]], axis=1)


def _post_res(logits: jax.Array, hc: Dict, n: int, iters: int, eps: float,
              clamp: Tuple[float, float]) -> Tuple[jax.Array, jax.Array]:
    """H_post [b, t, n] and H_res [b, t, n, n] from the logits x~ P."""
    b, t, _ = logits.shape
    post = 2.0 * jax.nn.sigmoid(hc["a_post"] * logits[..., n:2 * n] + hc["b_post"])
    res = sinkhorn(
        hc["a_res"] * logits[..., 2 * n:].reshape(b, t, n, n) + hc["b_res"],
        iters, eps, clamp)
    return post, res


# how a step combines these counters (models/llama.py `_add_counters`):
# the residual is the worst mapping's, the three means are over the mappings
COMBINE = {"hc_sinkhorn_residual": "max",
           **dict.fromkeys(("hc_res_offdiag", "hc_pre_mean", "hc_post_mean"),
                           "hc_mappings")}


def _stats(pre: jax.Array, post: jax.Array, res: jax.Array, n: int,
           kernel: bool) -> Dict:
    """A sublayer's counters from its mappings."""
    diag = jnp.mean(jnp.sum(jnp.diagonal(res, axis1=-2, axis2=-1), axis=-1))
    off_one = jnp.maximum(jnp.max(jnp.abs(jnp.sum(res, axis=-1) - 1.0)),
                          jnp.max(jnp.abs(jnp.sum(res, axis=-2) - 1.0)))
    return {"hc_mappings": jnp.ones((), jnp.float32),
            "hc_kernel_mappings": jnp.asarray(kernel, jnp.float32),
            # mean mass of H_res off its diagonal, a row's mass being 1:
            # 0 = streams that never mix, (n - 1) / n = uniform mixing
            "hc_res_offdiag": jax.lax.stop_gradient(
                (jnp.mean(jnp.sum(res, axis=(-2, -1))) - diag) / n),
            "hc_sinkhorn_residual": jax.lax.stop_gradient(off_one),
            "hc_pre_mean": jax.lax.stop_gradient(jnp.mean(pre)),
            "hc_post_mean": jax.lax.stop_gradient(jnp.mean(post))}


def _mappings(logits: jax.Array, hc: Dict, n: int, iters: int, eps: float,
              clamp: Tuple[float, float]) -> Dict:
    """The mappings and counters from the logits x~ P [b, t, n (n + 2)]."""
    pre = jax.nn.sigmoid(hc["a_pre"] * logits[..., :n] + hc["b_pre"])
    post, res = _post_res(logits, hc, n, iters, eps, clamp)
    return {"pre": pre, "post": post, "res": res,
            "stats": _stats(pre, post, res, n, kernel=False)}


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


def hc_streams(x: jax.Array, n: int) -> jax.Array:
    """x [b, t, d] as n streams that each start as x: [b, t, n*d]."""
    return jnp.concatenate([x] * n, axis=-1)


@jax.named_scope("hc_mix")
def hc_sum(x: jax.Array, n: int) -> jax.Array:
    """The streams x [b, t, n*d] summed into one [b, t, d], in float32."""
    d = x.shape[-1] // n
    out = x[..., :d].astype(jnp.float32)
    for j in range(1, n):
        out = out + x[..., j * d:(j + 1) * d].astype(jnp.float32)
    return out.astype(x.dtype)


def mix_takes_kernel(seq: int, n: int, d: int, dtype, mesh=None) -> bool:
    """Whether a sublayer's mappings and mixes run as the Pallas kernels
    (ops/hyper_mix.py): for streams in bfloat16 (whose values the
    projection's split takes as exact), where a stream is whole 128-lane
    blocks and a sequence whole token tiles, and where ops.takes_kernel
    lets a kernel run."""
    return (dtype == jnp.bfloat16 and hyper_mix.supports(seq, n, d)
            and ops.takes_kernel(mesh))


_POST_RES_LEAVES = ("a_post", "b_post", "a_res", "b_res")


class Onto(NamedTuple):
    """What a sublayer's output goes back onto: the streams [b, t, n*d],
    the mappings made from them, whether the kernels made them, and the
    mesh they ran under."""
    streams: jax.Array
    mapping: Dict
    kernel: bool
    mesh: object = None
    rules: Optional[ShardingRules] = None


def hc_branch(x: jax.Array, hc: Dict, n: int, iters: int, eps: float,
              clamp: Tuple[float, float], mesh=None,
              rules: Optional[ShardingRules] = None) -> Tuple[jax.Array, Onto]:
    """(what a sublayer reads of the streams x [b, t, n*d]: u = sum_j
    H_pre[j] X[j], [b, t, d]; what its output goes back onto)."""
    b, t, nd = x.shape
    d = nd // n
    if mix_takes_kernel(t, n, d, x.dtype, mesh):
        # H_post and H_res inside `pre`, whose backward takes their VJP
        maps_of = functools.partial(_post_res, n=n, iters=iters, eps=eps, clamp=clamp)
        rows = (rules or ShardingRules()).spec("batch", None, None)
        pre = ops.on_rows(
            lambda x_, w, a, b_, p: hyper_mix.pre(x_, w, a, b_, p, maps_of, eps),
            mesh, (rows,) + (P(),) * 4, (rows,) * 5)
        with jax.named_scope("hc_map"):
            u, lp, post, res, x = pre(x, _projection(hc), hc["a_pre"], hc["b_pre"],
                                      {k: hc[k] for k in _POST_RES_LEAVES})
            h_pre = jax.nn.sigmoid(hc["a_pre"] * lp[..., :n] + hc["b_pre"])  # counters' only
            mapping = {"post": post, "res": res,
                       "stats": _stats(h_pre, post, res, n, kernel=True)}
        return u, Onto(x, mapping, True, mesh, rules)
    streams = x.reshape(b, t, n, d)
    mapping = hc_map(streams, hc, iters, eps, clamp)
    return hc_pre(streams, mapping), Onto(x, mapping, False)


def hc_merge(onto: Onto, y: jax.Array) -> jax.Array:
    """The streams [b, t, n*d] after the sublayer's output y [b, t, d]:
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y."""
    x, mapping = onto.streams, onto.mapping
    b, t, nd = x.shape
    n = mapping["post"].shape[-1]
    if onto.kernel:
        rows = (onto.rules or ShardingRules()).spec("batch", None, None)
        post = ops.on_rows(hyper_mix.post, onto.mesh, (rows,) * 4, rows)
        with jax.named_scope("hc_mix"):
            return post(x, y, mapping["post"], mapping["res"])
    return hc_mix(x.reshape(b, t, n, nd // n), y, mapping).reshape(b, t, nd)

