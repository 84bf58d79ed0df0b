"""The benchmark's own weights: made on the device from the seed in one
jitted call, in the type they are trained in.

The tree has the layout `kubedl_tpu.models.llama` trains (that layout is
the interface of the system under test); the values owe nothing to the
program. The plain reference calls the same function, so both sides
start from the same bf16 numbers without one handing arrays to the other.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from benchmarks import flops


def leaf_shapes(cfg: Dict) -> Dict:
    """Shape of every leaf, in the program's layout."""
    d, ff, hd = cfg["hidden_size"], cfg["intermediate_size"], flops.head_dim(cfg)
    nq, nkv, v = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["vocab_size"]
    layer = {
        "attn_norm": (d,), "wq": (d, nq * hd), "wk": (d, nkv * hd),
        "wv": (d, nkv * hd), "wo": (nq * hd, d), "mlp_norm": (d,),
        "w1": (d, ff), "w3": (d, ff), "w2": (ff, d),
    }
    tree = {
        "embed": (v, d),
        "layers": [dict(layer) for _ in range(cfg["num_hidden_layers"])],
        "final_norm": (d,),
    }
    if not cfg.get("tie_word_embeddings"):
        tree["lm_head"] = (d, v)
    return tree


def is_shape(x) -> bool:
    return isinstance(x, tuple)


def make_fn(cfg: Dict):
    """key -> parameter tree. Matrices are normal(0, initializer_range)
    in bfloat16; norm weights are ones in float32."""
    shapes = leaf_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=is_shape)
    std = float(cfg["initializer_range"])

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, shape in zip(keys, leaves):
            if len(shape) == 1:
                out.append(jnp.ones(shape, jnp.float32))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32) * std)
                           .astype(jnp.bfloat16))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make


def maker(cfg: Dict, shardings=None):
    """seed -> the whole tree in one jitted call; with `shardings` (a
    matching tree) every leaf is born in its shards, no unsharded copy."""
    fn = jax.jit(make_fn(cfg), out_shardings=shardings)
    return lambda seed: fn(jax.random.PRNGKey(seed))
