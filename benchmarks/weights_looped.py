"""The benchmark's own weights for a looped decoder (a stack applied
several times over the same weights, four norms a layer, an exit gate
beside the head), as `weights.py` makes a plain decoder's: on the device
from the seed in one jitted call, in the layout
`kubedl_tpu.models.llama` trains and owing nothing else to the program.
The plain reference calls the same function.

Matrices are normal(0, initializer_range) in bfloat16; norm weights are
ones and the gate's bias zero, both float32; the gate's
`Linear(hidden, 1)` weight is bfloat16 at a tenth of the matrices' range
(`GATE_SHARE`; the configuration file's `assumed`).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from benchmarks import weights


# The gate's weight is seeded at a tenth of the matrices' range. Seeded
# layers leave every token's state with a large common part, so a gate at
# the full range reads nearly the same logit on every token, anywhere in
# about -3..3 by the seed: one seed put 82% of the exit mass on the last
# pass (my chip run, PR 30), another would put 90% on the first and make
# the later passes' loss and a pass left out invisible to `correct`. At a
# tenth the seeded exit distribution stays near (1/2, 1/4, 1/8, 1/8) on
# every seed and every pass carries loss, as under the objective's uniform
# prior it should at the start.
GATE_SHARE = 0.1


def leaf_shapes(cfg: Dict) -> Dict:
    """Shape of every leaf, in the program's layout: `weights.py`'s tree
    with a norm on each branch's output and the gate's two leaves."""
    d = cfg["hidden_size"]
    tree = weights.leaf_shapes(cfg)
    for layer in tree["layers"]:
        layer.update({"post_attn_norm": (d,), "post_mlp_norm": (d,)})
    tree["exit_gate"] = {"w": (d, 1), "b": (1,)}
    return tree


def make_fn(cfg: Dict):
    """key -> parameter tree."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        leaf_shapes(cfg), is_leaf=weights.is_shape)
    std = float(cfg["initializer_range"])
    gate_w = (jax.tree_util.DictKey("exit_gate"), jax.tree_util.DictKey("w"))

    def make(key):
        keys = jax.random.split(key, len(paths))
        out = []
        for k, (path, shape) in zip(keys, paths):
            if len(shape) == 1:
                fill = jnp.zeros if path[-1].key == "b" else jnp.ones
                out.append(fill(shape, jnp.float32))
            else:
                scale = std * (GATE_SHARE if path[-2:] == gate_w else 1.0)
                out.append((jax.random.normal(k, shape, jnp.float32) * scale)
                           .astype(jnp.bfloat16))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make


def maker(cfg: Dict, shardings=None):
    """seed -> the whole tree in one jitted call."""
    fn = jax.jit(make_fn(cfg), out_shardings=shardings)
    return lambda seed: fn(jax.random.PRNGKey(seed))
