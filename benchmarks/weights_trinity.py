"""The benchmark's own weights for a model of gated grouped-query attention
layers with four norms each, a dense FFN first and expert FFNs with a
shared expert after it (Trinity's `afmoe` layers), as `weights.py` makes a
dense decoder's: on the device from the seed in one jitted call, in the
layout `kubedl_tpu.models.llama` trains and owing nothing else to the
program. The plain reference calls the same function.

Matrices, the gate's `wg` among them, are normal(0, initializer_range) in
bfloat16; norm weights, the per-head q and k norms among them, ones in
float32; the router's matrix and its selection bias float32
(`weights_hybrid.py`'s, the bias at a tenth of the range).

But for the two norms after each sublayer (`post_attn_norm`,
`post_mlp_norm`), which start at `post_norm_gain`: 1 / sqrt(2 x the
published depth), 0.0913 at 60 layers. The family calls its sandwich norm
"depth-scaled" and config.json gives no rule (the configuration file's
`assumed`). At gains of 1 each sublayer adds a vector of unit mean square
to a residual whose embedding has about that: the attention's output,
an average over thousands of keys, is nearly the same for every token,
and normed to that size it is 46% of the FFN input's norm on every token
alike. The seeded routers then send a third of a layer's tokens to one
expert and from one to three times an even share to the eight held here,
as the seed falls: the step's work would be the seed's. At 0.0913 that
common part is 8% and the held experts see 0.83-1.07 times an even share
(the full widths on the CPU: three seeds of 1,024 tokens at gains of 1,
four of 2,048 at 0.0913).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from benchmarks import flops
from benchmarks.weights import is_shape
from benchmarks.weights_hybrid import BIAS_SHARE, FLOAT32_MATRICES

POST_NORMS = ("post_attn_norm", "post_mlp_norm")


def post_norm_gain(cfg: Dict) -> float:
    """1 / sqrt(2 x the published depth), or the file's own where it states
    none."""
    depth = cfg.get("published", {}).get("num_hidden_layers", cfg["num_hidden_layers"])
    return float((2 * depth) ** -0.5)


def layer_shapes(cfg: Dict, routed: bool) -> Dict:
    d, hd = cfg["hidden_size"], flops.head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = {"attn_norm": (d,), "wq": (d, nq * hd), "wk": (d, nkv * hd),
             "wv": (d, nkv * hd), "wo": (nq * hd, d), "wg": (d, nq * hd),
             "q_norm": (hd,), "k_norm": (hd,), "post_attn_norm": (d,),
             "mlp_norm": (d,), "post_mlp_norm": (d,)}
    if routed:
        ff, held, out = cfg["moe_intermediate_size"], cfg["num_experts"], cfg["router_outputs"]
        shared = ff * cfg["num_shared_experts"]
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
    dense = cfg["num_dense_layers"]
    return {"embed": (v, d),
            "layers": [layer_shapes(cfg, i >= dense)
                       for i in range(cfg["num_hidden_layers"])],
            "final_norm": (d,), "lm_head": (d, v)}


def make_fn(cfg: Dict):
    """key -> parameter tree."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        leaf_shapes(cfg), is_leaf=is_shape)
    std = float(cfg["initializer_range"])
    gain = post_norm_gain(cfg)

    def leaf(k, path, shape):
        name = path[-1].key
        if name in POST_NORMS:
            return jnp.full(shape, gain, jnp.float32)
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
