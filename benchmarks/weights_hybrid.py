"""The benchmark's own weights for a model of unlike layers (short
convolutions among attention layers, a dense FFN before routed ones),
as `weights.py` makes a dense decoder's: on the device from the seed in
one jitted call, in the layout `kubedl_tpu.models.llama` trains and owing
nothing else to the program. The plain reference calls the same
function.

Matrices are normal(0, initializer_range) in bfloat16 and norm weights
ones in float32, as there. The router's matrix and its selection bias
are float32, as the configuration states them: the matrix
normal(0, initializer_range), the bias normal(0, a tenth of that) (the
pre-training rule that moves it is not part of config.json; see
`BIAS_SHARE` and the configuration file's `assumed`).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from benchmarks import flops
from benchmarks.weights import is_shape

FLOAT32_MATRICES = ("router", "router_bias")
# The selection bias is seeded at a tenth of the matrices' range. A
# trained model's bias evens the load out; a seeded one unbalances it: at
# these widths a score's spread is 0.19 and 4 of 32 are chosen, so an
# offset of 0.02 moves an output's share of the choices by a sixth, and
# with it, from seed to seed, the rows that reach the experts held here
# and the step's time. 0.002 still flips the choice wherever two scores
# lie closer than that, which is what `correct` needs of it.
BIAS_SHARE = 0.1


def layer_shapes(cfg: Dict, i: int) -> Dict:
    d, hd = cfg["hidden_size"], flops.head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    if cfg["layer_types"][i] == "conv":
        layer = {"conv_norm": (d,), "conv_in": (d, 3 * d),
                 "conv_w": (d, cfg["conv_L_cache"]), "conv_out": (d, d)}
    else:
        layer = {"attn_norm": (d,), "wq": (d, nq * hd), "wk": (d, nkv * hd),
                 "wv": (d, nkv * hd), "wo": (nq * hd, d),
                 "q_norm": (hd,), "k_norm": (hd,)}
    layer["mlp_norm"] = (d,)
    if i < cfg["num_dense_layers"]:
        ff = cfg["intermediate_size"]
        layer.update({"w1": (d, ff), "w3": (d, ff), "w2": (ff, d)})
    else:
        ff, held, out = cfg["moe_intermediate_size"], cfg["num_experts"], cfg["router_outputs"]
        layer["moe"] = {"router": (d, out), "router_bias": (out,),
                        "w1": (held, d, ff), "w3": (held, d, ff), "w2": (held, ff, d)}
    return layer


def leaf_shapes(cfg: Dict) -> Dict:
    """Shape of every leaf, in the program's layout."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    tree = {"embed": (v, d),
            "layers": [layer_shapes(cfg, i) for i in range(cfg["num_hidden_layers"])],
            "final_norm": (d,)}
    if not cfg.get("tie_word_embeddings"):
        tree["lm_head"] = (d, v)
    return tree


def make_fn(cfg: Dict):
    """key -> parameter tree."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        leaf_shapes(cfg), is_leaf=is_shape)
    std = float(cfg["initializer_range"])

    def make(key):
        keys = jax.random.split(key, len(paths))
        out = []
        for k, (path, shape) in zip(keys, paths):
            name = path[-1].key
            if name in FLOAT32_MATRICES:
                scale = std * (BIAS_SHARE if name == "router_bias" else 1.0)
                out.append(jax.random.normal(k, shape, jnp.float32) * scale)
            elif len(shape) == 1:
                out.append(jnp.ones(shape, jnp.float32))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32) * std)
                           .astype(jnp.bfloat16))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make


def maker(cfg: Dict, shardings=None):
    """seed -> the whole tree in one jitted call."""
    fn = jax.jit(make_fn(cfg), out_shardings=shardings)
    return lambda seed: fn(jax.random.PRNGKey(seed))
