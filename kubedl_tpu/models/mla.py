"""Multi-head latent attention (MLA): queries, keys and values through
low-rank projections, one rotary key for all heads.

    cq = rmsnorm(u W_qa)                     [q_rank]
    q  = cq W_qb -> heads x [q_nope | q_rope]             nope + rope wide
    [ckv | kr] = u W_kva                     [kv_rank | rope]
    c  = rmsnorm(ckv)
    [k_nope | v] a head = c W_kvb            heads x (nope + v_dim)
    k_rope = RoPE(kr)                        one for all heads
    q_h = [q_nope | RoPE(q_rope)];  k_h = [k_nope | k_rope]
    o = softmax(q k^T * s + causal) v;  out = concat(o) W_o   [heads * v_dim, d]

`u` is the layer's normed input. Keys are nope + rope wide and values
v_dim (DeepSeek-V2/V3's layer, arXiv:2405.04434, arXiv:2412.19437): the
attention core is handed q, k and v of unlike widths
(ops/flash_attention.py). The scale `s` is (nope + rope)^-0.5, times
YaRN's (0.1 mscale_all_dim ln factor + 1)^2 where the rotary
frequencies are YaRN's (LlamaConfig.softmax_scale).

This module is the projections: the caller owns the norm's arithmetic
(`norm`), the rotary embedding (`rope`) and the attention core. Training
and the uncached forward only: what a decoder would cache is `c` and
`k_rope`, 576 numbers a token and layer in place of 2 x heads x
head_dim, and the cached paths (models/decode.py, models/serving.py,
serving/kv_pool.py) hold keys and values a head and refuse such a layer
(LlamaConfig.require_kv_heads).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubedl_tpu.models.quant import matmul as _mm
from kubedl_tpu.parallel.mesh import ShardingRules


def mla_param_specs(rules: Optional[ShardingRules] = None) -> Dict:
    """PartitionSpec pytree matching mla_init(): the low-rank inputs are
    split as any projection's input is, the per-head outputs by head."""
    r = rules or ShardingRules()
    return {
        "wq_a": r.spec("embed", None), "q_a_norm": r.spec(None),
        "wq_b": r.spec(None, "heads"),
        "wkv_a": r.spec("embed", None), "kv_a_norm": r.spec(None),
        "wkv_b": r.spec(None, "heads"), "wo": r.spec("heads", "embed"),
    }


def mla_init(key: jax.Array, d_model: int, n_heads: int, q_rank: int,
             kv_rank: int, nope: int, rope: int, v_dim: int,
             dtype=jnp.bfloat16) -> Dict:
    ks = jax.random.split(key, 5)

    def dense(k, shape, fan_in):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in))).astype(dtype)

    return {
        "wq_a": dense(ks[0], (d_model, q_rank), d_model),
        "q_a_norm": jnp.ones((q_rank,), jnp.float32),
        "wq_b": dense(ks[1], (q_rank, n_heads * (nope + rope)), q_rank),
        "wkv_a": dense(ks[2], (d_model, kv_rank + rope), d_model),
        "kv_a_norm": jnp.ones((kv_rank,), jnp.float32),
        "wkv_b": dense(ks[3], (kv_rank, n_heads * (nope + v_dim)), kv_rank),
        "wo": dense(ks[4], (n_heads * v_dim, d_model), n_heads * v_dim),
    }


def mla_qkv(u: jax.Array, layer: Dict, n_heads: int, nope: int, rope_dim: int,
            v_dim: int, norm: Callable, rope: Callable
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(q, k, v) over [b, heads, t, .] from the normed input u [b, t, d]:
    q and k nope + rope_dim wide, v v_dim. `norm(x, weight)` is the
    model's RMSNorm, `rope(x)` its rotary embedding over [b, h, t, rope_dim]."""
    b, t, _ = u.shape
    heads = lambda x, w: x.reshape(b, t, n_heads, w).transpose(0, 2, 1, 3)
    with jax.named_scope("mla_q"):
        q = heads(_mm(norm(_mm(u, layer["wq_a"]), layer["q_a_norm"]),
                      layer["wq_b"]), nope + rope_dim)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:])], axis=-1)
    with jax.named_scope("mla_kv"):
        kv_a = _mm(u, layer["wkv_a"])
        kv_rank = kv_a.shape[-1] - rope_dim
        c = norm(kv_a[..., :kv_rank], layer["kv_a_norm"])
        kv = heads(_mm(c, layer["wkv_b"]), nope + v_dim)
        k_rope = rope(kv_a[:, None, :, kv_rank:])
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope, (b, n_heads, t, rope_dim))], axis=-1)
        v = kv[..., nope:]
    return q, k, v
