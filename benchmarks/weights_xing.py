"""The benchmark's own weights for a model of latent-attention layers on
several residual streams, a dense FFN first and expert FFNs with a shared
expert after it, and a multi-token prediction module, as `weights.py`
makes a dense decoder's: on the device from the seed in one jitted call,
in the layout `kubedl_tpu.models.llama` trains and owing nothing else to
the program. The plain reference calls the same function.

Matrices are normal(0, initializer_range) in bfloat16 and norm weights
ones in float32, as there; the router's matrix and its selection bias
float32 (`weights_hybrid.py`'s, the bias at a tenth of the range).

The hyper-connection leaves, float32 (the configuration file's
`assumed`): `p_pre`, `p_post`, `p_res` normal(0, initializer_range);
`a_*` 0.1; `b_pre` and `b_post` 0 (H_pre near 1/2, H_post near 1);
`b_res` 2 on its diagonal and 0 off it. The normed streams have unit
mean square over 14,336 entries, so a projection's spread is
0.02 sqrt(14,336) = 2.4 and the dynamic part moves a logit by 0.24: H_res
starts with about 0.7 on its diagonal and a tenth off it, and differs by
a few percent from token to token. With the papers' near-identity start
nothing the comparison reads would feel the residual mapping.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from benchmarks.weights import is_shape
from benchmarks.weights_hybrid import BIAS_SHARE, FLOAT32_MATRICES

HC_GAIN = 0.1  # a_pre, a_post, a_res
HC_RES_DIAGONAL = 2.0  # b_res on its diagonal


def hc_shapes(cfg: Dict) -> Dict:
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    return {"p_pre": (n * d, n), "p_post": (n * d, n), "p_res": (n * d, n * n),
            "b_pre": (n,), "b_post": (n,), "b_res": (n, n),
            "a_pre": (), "a_post": (), "a_res": ()}


def layer_shapes(cfg: Dict, routed: bool) -> Dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    layer = {"attn_norm": (d,), "wq_a": (d, qr), "q_a_norm": (qr,),
             "wq_b": (qr, h * (nope + rope)), "wkv_a": (d, kvr + rope),
             "kv_a_norm": (kvr,), "wkv_b": (kvr, h * (nope + vd)),
             "wo": (h * vd, d), "mlp_norm": (d,),
             "hc_mixer": hc_shapes(cfg), "hc_mlp": hc_shapes(cfg)}
    if routed:
        ff, held, out = cfg["moe_intermediate_size"], cfg["n_routed_experts"], cfg["router_outputs"]
        shared = ff * cfg["n_shared_experts"]
        layer["moe"] = {"router": (d, out), "router_bias": (out,),
                        "w1": (held, d, ff), "w3": (held, d, ff), "w2": (held, ff, d),
                        "shared_w1": (d, shared), "shared_w3": (d, shared),
                        "shared_w2": (shared, d)}
    else:
        ff = cfg["intermediate_size"]
        layer.update({"w1": (d, ff), "w3": (d, ff), "w2": (ff, d)})
    return layer


def leaf_shapes(cfg: Dict) -> Dict:
    """Shape of every leaf, in the program's layout."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    dense = cfg["first_k_dense_replace"]
    tree = {"embed": (v, d),
            "layers": [layer_shapes(cfg, i >= dense)
                       for i in range(cfg["num_hidden_layers"])],
            "final_norm": (d,), "lm_head": (d, v)}
    if cfg["num_nextn_predict_layers"]:
        tree["mtp"] = {"embed_norm": (d,), "hidden_norm": (d,), "w_eh": (2 * d, d),
                       "block": layer_shapes(cfg, True), "final_norm": (d,)}
    return tree


def make_fn(cfg: Dict):
    """key -> parameter tree."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        leaf_shapes(cfg), is_leaf=is_shape)
    std = float(cfg["initializer_range"])

    def leaf(k, path, shape):
        name = path[-1].key
        in_hc = len(path) > 1 and getattr(path[-2], "key", "").startswith("hc_")
        if in_hc and name.startswith("p_"):
            return jax.random.normal(k, shape, jnp.float32) * std
        if in_hc and name.startswith("a_"):
            return jnp.full(shape, HC_GAIN, jnp.float32)
        if in_hc and name == "b_res":
            return HC_RES_DIAGONAL * jnp.eye(shape[0], dtype=jnp.float32)
        if in_hc:
            return jnp.zeros(shape, jnp.float32)
        if name in FLOAT32_MATRICES:
            scale = std * (BIAS_SHARE if name == "router_bias" else 1.0)
            return jax.random.normal(k, shape, jnp.float32) * scale
        if len(shape) == 1:
            return jnp.ones(shape, jnp.float32)
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(jnp.bfloat16)

    def make(key):
        keys = jax.random.split(key, len(paths))
        return jax.tree_util.tree_unflatten(
            treedef, [leaf(k, path, shape) for k, (path, shape) in zip(keys, paths)])

    return make


def maker(cfg: Dict, shardings=None):
    """seed -> the whole tree in one jitted call."""
    fn = jax.jit(make_fn(cfg), out_shardings=shardings)
    return lambda seed: fn(jax.random.PRNGKey(seed))
