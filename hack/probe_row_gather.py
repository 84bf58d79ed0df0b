#!/usr/bin/env python3
"""The dispatch's four row moves, alone, on the chip, at the LFM2 cell's
shapes: XLA's gather over all rows (`models/moe.py:_take`, what every
move was until PR 29), `_move_rows` (what the dispatch calls: it
chooses), the row-gather kernel (`ops/row_gather.py`) forced on every
move, and the loop over live tiles of the two moves that have a bound at
several step sizes (`moe.LOOP_ROWS`).

    python hack/probe_row_gather.py [--calls 30] [--out chiprun_out/probe_row_gather.json]

`x` [16384, 2048] bf16, k = 4, a seeded `eid` and its `_dispatch_plan`,
twice: one entry in four held by 8 experts (the cell), and every entry
held by 8 of 8 (a layer that holds all its experts). Each move is a
jitted call of its own, timed on the host's clock over `--calls` calls
with one closing wait. Also prints how many elements differ from XLA's
(under the bound, where there is one). Needs a TPU: a CPU time is no
device number and is refused.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from kubedl_tpu.models import moe
from kubedl_tpu.ops.row_gather import gather_rows

S, D, K, E = 16384, 2048, 4, 8


def plan_of(seed: int, held_of: int):
    """eid over `held_of` outputs of which the first E are held."""
    eid = jax.random.randint(jax.random.PRNGKey(seed), (K * S,), 0, held_of)
    eid = jnp.where(eid < E, eid, E).astype(jnp.int32)
    order, dest, pos_of_entry, tile_expert, m_pad = moe._dispatch_plan(eid, E)
    m = K * S
    entry_of_row = jnp.full((m_pad,), m, jnp.int32).at[dest].set(
        order, mode="drop")
    row_src = jnp.where(entry_of_row < m, entry_of_row % S, S)
    tile = m_pad // tile_expert.shape[0]
    live = moe._live_rows(tile_expert, E, m_pad)
    return dict(pos_of_entry=pos_of_entry, entry_of_row=entry_of_row,
                row_src=row_src, live=live, m_pad=m_pad, tile=tile,
                held=int(jnp.sum(eid < E)))


def timed(fn, args, calls):
    out = jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        last = fn(*args)
    jax.block_until_ready(last)
    return (time.perf_counter() - t0) / calls * 1e3, out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=30)
    ap.add_argument("--out", default="chiprun_out/probe_row_gather.json")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    result = {"device_kind": dev.device_kind, "calls": a.calls, "loads": {}}
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    for load, held_of in (("quarter_held", 4 * E), ("all_held", E)):
        p = plan_of(11, held_of)
        m_pad, live, tile = p["m_pad"], p["live"], p["tile"]
        n_live = int(live)
        src = jax.random.normal(keys[0], (S, D), jnp.float32).astype(jnp.bfloat16)
        rows = jax.random.normal(keys[1], (m_pad, D), jnp.float32).astype(jnp.bfloat16)
        dy = jax.random.normal(keys[2], (K * S, D), jnp.float32).astype(jnp.bfloat16)
        back = p["pos_of_entry"].reshape(K, S)

        def take_sum(x, idx):
            # the parent's own form (bfloat16 adds), not `moe._take_sum`:
            # whether XLA's fused bits are the float32 sum's is a finding
            y = moe._take(x, idx[0])
            for c in range(1, idx.shape[0]):
                y = y + moe._take(x, idx[c])
            return y

        moves = {
            "permute_fwd": (src, p["row_src"][None], live),
            "combine_fwd": (rows, p["pos_of_entry"][None], None),
            "combine_bwd": (dy, p["entry_of_row"][None], live),
            "permute_bwd": (rows, back, None),
        }
        rec = {"m_pad": m_pad, "live_rows": n_live, "rows_held": p["held"],
               "tile": tile, "moves": {}}
        for name, (x, idx, bound) in moves.items():
            upto = idx.shape[1] if bound is None else n_live
            xla_ms, want = timed(jax.jit(take_sum), (x, idx), a.calls)
            w = np.asarray(want[:upto].astype(jnp.float32))

            def unequal(got):
                return int(np.sum(
                    w != np.asarray(got[:upto].astype(jnp.float32))))

            one = {"xla_ms": xla_ms, "rows_out": int(idx.shape[1]),
                   "rows_compared": upto,
                   "copies": int(jnp.sum(idx < x.shape[0]))}
            one["move_rows_ms"], got = timed(
                moe._move_rows, (x, idx, bound), a.calls)
            one["move_rows_unequal"] = unequal(got)
            one["kernel_ms"], got = timed(jax.jit(gather_rows), (x, idx), a.calls)
            one["kernel_unequal"] = unequal(got)
            if bound is not None:
                step = moe.LOOP_ROWS
                for t in (512, 1024, 2048, 4096):
                    moe.LOOP_ROWS = t  # read when the loop is traced
                    one[f"loop_{t}_ms"], got = timed(
                        jax.jit(lambda *a: moe._move_rows.__wrapped__(*a)),
                        (x, idx, bound), a.calls)
                    one[f"loop_{t}_unequal"] = unequal(got)
                moe.LOOP_ROWS = step
            rec["moves"][name] = one
            print(load, name, json.dumps(one), flush=True)
        result["loads"][load] = rec
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
