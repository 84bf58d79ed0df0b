"""The benchmark's own weights for a model of state-space and attention
layers (Mamba-2 mixers among GQA layers, a dense SwiGLU in every layer),
as `weights.py` makes a dense decoder's: on the device from the seed in
one jitted call, in the layout `kubedl_tpu.models.llama` trains and owing
nothing else to the program. The plain reference calls the same function.

Matrices are normal(0, initializer_range) in bfloat16; norm weights and
`D` are ones in float32. A state-space layer's other leaves cannot be
seeded so (the configuration file's `assumed` has the same list):

  ssm_A_log    log(uniform(1, 16)), float32
  ssm_dt_bias  the inverse softplus of dt drawn log-uniform in
               [0.001, 0.1], float32: the Mamba-2 paper's own
               initialisation, as remembered. With normal(0, 0.02) in
               their place every head forgets within two tokens (dt 0.69,
               A -1) and the chunk-to-chunk pass would carry nothing a
               comparison could see; with these ranges a head's per-token
               decay runs from 0.2 to 0.999 and about a tenth of the heads
               carry state across several chunks.
  ssm_conv_w,  uniform(-1/2, 1/2), float32 both (the program keeps a
  ssm_conv_b   state-space layer's small leaves in float32): what a
               depthwise Conv1d of 4 taps is seeded with (1 / sqrt(taps)).
               Taps at 0.02 leave x, B and C at a fiftieth of their
               input: the scan's part of y is then a thousandth of D x
               beside it, the gated norm rescales the sum, and no number
               `correct` compares would feel the scan at all. At these the
               scan is a fifth of y at the published widths, and a state
               dropped at every 256th token moves y by 3.5%.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from benchmarks import flops
from benchmarks.weights import is_shape

DT_RANGE = (1e-3, 1e-1)
A_RANGE = (1.0, 16.0)


def ssm_sizes(cfg: Dict) -> Dict[str, int]:
    """A state-space layer's widths from the published keys."""
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner = h * p
    if inner != cfg["mamba_expand"] * cfg["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x hidden_size")
    conv = inner + 2 * cfg["mamba_n_groups"] * n
    return {"heads": h, "head_dim": p, "state": n, "inner": inner, "conv": conv,
            "in_proj": inner + conv + h, "taps": cfg["mamba_d_conv"]}


def layer_shapes(cfg: Dict, i: int) -> Dict:
    d, ff, hd = cfg["hidden_size"], cfg["intermediate_size"], flops.head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    if cfg["layer_types"][i] == "mamba":
        s = ssm_sizes(cfg)
        layer = {"ssm_norm": (d,), "ssm_in": (d, s["in_proj"]),
                 "ssm_conv_w": (s["conv"], s["taps"]), "ssm_conv_b": (s["conv"],),
                 "ssm_dt_bias": (s["heads"],), "ssm_A_log": (s["heads"],),
                 "ssm_D": (s["heads"],), "ssm_gate_norm": (s["inner"],),
                 "ssm_out": (s["inner"], d)}
    else:
        layer = {"attn_norm": (d,), "wq": (d, nq * hd), "wk": (d, nkv * hd),
                 "wv": (d, nkv * hd), "wo": (nq * hd, d)}
    layer.update({"mlp_norm": (d,), "w1": (d, ff), "w3": (d, ff), "w2": (ff, d)})
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
    f32 = jnp.float32

    def leaf(k, name: str, shape):
        if name == "ssm_A_log":
            return jnp.log(jax.random.uniform(k, shape, f32, *A_RANGE))
        if name == "ssm_dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, f32, math.log(DT_RANGE[0]), math.log(DT_RANGE[1])))
            return dt + jnp.log(-jnp.expm1(-dt))  # softplus(bias) = dt
        if name in ("ssm_conv_w", "ssm_conv_b"):
            half = 1.0 / math.sqrt(cfg["mamba_d_conv"])
            return jax.random.uniform(k, shape, f32, -half, half)
        if len(shape) == 1:
            return jnp.ones(shape, f32)
        return (jax.random.normal(k, shape, f32) * std).astype(jnp.bfloat16)

    def make(key):
        keys = jax.random.split(key, len(paths))
        return jax.tree_util.tree_unflatten(
            treedef, [leaf(k, path[-1].key, shape)
                      for k, (path, shape) in zip(keys, paths)])

    return make


def maker(cfg: Dict, shardings=None):
    """seed -> the whole tree in one jitted call."""
    fn = jax.jit(make_fn(cfg), out_shardings=shardings)
    return lambda seed: fn(jax.random.PRNGKey(seed))
