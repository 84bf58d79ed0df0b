"""The comparison that decides `correct` for a training cell.

Three numbers; the cell's file gives a limit to each one that is
compared (PERF.md says which are not, and why):

  loss_gap    the widest relative gap between a step's loss as the
              timed path gave it and as the float32 reference gives it;
  grad_gap    the worst leaf's gap between the norm of the first
              gradient as the optimizer got it (out of its first moment
              after one step) and the reference's norm;
  change_gap  the worst leaf's gap between the norm of the parameters'
              change over the followed steps and the reference's.

A leaf's gap is the difference of the two norms (not the norm of a
difference), over the reference's norm of that leaf or of the median
leaf, whichever is larger. Leaves whose reference gradient is under a
thousandth of the median leaf's move under Adam by round-off alone and
are left out of change_gap.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Tuple

import jax

QUIET_LEAF = 1e-3


def _flat(tree) -> Dict[str, float]:
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): float(v) for path, v in leaves}


def leaf_gaps(program, reference, skip=()) -> Dict[str, float]:
    prog, ref = _flat(program), _flat(reference)
    if prog.keys() != ref.keys():
        raise ValueError("the two sides have different leaves")
    median = statistics.median(ref.values())
    return {k: abs(prog[k] - ref[k]) / max(ref[k], median)
            for k in ref if k not in skip}


def quiet_leaves(ref_grad_norm) -> List[str]:
    ref = _flat(ref_grad_norm)
    median = statistics.median(ref.values())
    return [k for k, v in ref.items() if v < QUIET_LEAF * median]


def numbers(program: Dict, reference: Dict) -> Dict[str, float]:
    """The compared numbers, from the two sides' readings."""
    n = min(len(program["loss"]), len(reference["loss"]))
    loss_gap = max(abs(p - r) / abs(r) for p, r in
                   zip(program["loss"][:n], reference["loss"][:n]))
    grad = leaf_gaps(program["grad_norm"], reference["grad_norm"])
    change = leaf_gaps(program["change_norm"], reference["change_norm"],
                       skip=quiet_leaves(reference["grad_norm"]))
    return {"loss_gap": loss_gap, "grad_gap": max(grad.values()),
            "change_gap": max(change.values())}


def worst_leaves(program: Dict, reference: Dict, top: int = 3) -> Dict:
    """Where the two leaf numbers come from, for the record."""
    out = {}
    for key in ("grad_norm", "change_norm"):
        gaps = leaf_gaps(program[key], reference[key])
        out[key] = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return out


def decide(values: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, Dict]:
    """Every compared number beside its limit. The cell's file names the
    numbers that are compared (those with a limit); the others are
    carried along with a null limit and only have to be finite. With no
    limit at all nothing is proven."""
    compared = {name: {"value": value, "limit": limits.get(name)}
                for name, value in values.items()}
    ok = bool(limits) and all(math.isfinite(v) for v in values.values())
    for name, limit in limits.items():
        if name not in values or not values[name] <= limit:
            ok = False
    return ok, compared
