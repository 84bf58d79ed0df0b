"""Operations and bytes of a training step of gated grouped-query attention
layers, windowed or full by layer, with a dense FFN first and expert FFNs
with a shared expert after it (Trinity's `afmoe` layers), and of its
flash calls, from shapes and from the one thing shapes cannot give: how
many rows the router sent to the experts held here, which the step counts
(`moe_rows_held`).

A layer is attention (q, k, v, o and the gate's `wg`, q and k normed a
head), four norms, and a dense FFN (the first `num_dense_layers` layers)
or a routed expert layer that holds `num_experts` of the router's
`router_outputs` experts beside `num_shared_experts` shared ones. Each
layer attends over its own window: `layer_types` names it
(`sliding_attention` at `sliding_window`, `full_attention` causal over the
whole sequence). Arithmetic on a configuration file and a cell file, as
`flops.py`; nothing is read from the program but that count. Recomputed
operations (remat) never count as required, and the gate's sigmoid and
product, the norms and RoPE count nothing.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from benchmarks import flops

WINDOWED, FULL = "sliding_attention", "full_attention"


def layer_windows(cfg: Dict) -> List[Optional[int]]:
    """Each layer's attention window, None for a full layer."""
    out = []
    for kind in cfg["layer_types"]:
        if kind not in (WINDOWED, FULL):
            raise ValueError(f"layer type {kind!r} is not an attention kind")
        out.append(int(cfg["sliding_window"]) if kind == WINDOWED else None)
    return out


def attn_matmul_params(cfg: Dict) -> int:
    """q, k, v, o and the gate."""
    d, hd = cfg["hidden_size"], flops.head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 3 * d * nq * hd + 2 * d * nkv * hd


def expert_params(cfg: Dict) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_fixed_matmul_params(cfg: Dict, routed: bool) -> int:
    """Weights of one layer that every token is multiplied by whatever
    the router says."""
    d = cfg["hidden_size"]
    if routed:
        return (attn_matmul_params(cfg) + d * cfg["router_outputs"]
                + cfg["num_shared_experts"] * expert_params(cfg))
    return attn_matmul_params(cfg) + 3 * d * cfg["intermediate_size"]


def layer_params(cfg: Dict, routed: bool) -> int:
    """All stored parameters of one layer."""
    d = cfg["hidden_size"]
    total = attn_matmul_params(cfg) + 2 * flops.head_dim(cfg) + 4 * d
    if routed:
        return (total + (d + 1) * cfg["router_outputs"]
                + (cfg["num_shared_experts"] + cfg["num_experts"]) * expert_params(cfg))
    return total + 3 * d * cfg["intermediate_size"]


def total_params(cfg: Dict) -> int:
    """All stored parameters (memory arithmetic, not FLOPs)."""
    d, dense = cfg["hidden_size"], cfg["num_dense_layers"]
    return (2 * cfg["vocab_size"] * d + d + dense * layer_params(cfg, False)
            + (cfg["num_hidden_layers"] - dense) * layer_params(cfg, True))


def expert_layers(cfg: Dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def uniform_rows_held(cfg: Dict, tokens: int) -> float:
    """Rows one expert layer computes here when every output is as likely
    as any other: each token's k choices, the share of outputs held."""
    return (tokens * cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_outputs"])


def step_flops(cfg: Dict, batch: int, seen_len: int, rows_held: float) -> Dict[str, float]:
    """Required FLOPs of one training step. `rows_held` is the step's
    count of rows computed by experts held here, summed over the expert
    layers. 6 per matmul weight and row; the scores 2 matmuls forward and
    4 backward over the keys each layer's own window attends."""
    tokens, dense = batch * seen_len, cfg["num_dense_layers"]
    layers = 6.0 * tokens * (
        dense * layer_fixed_matmul_params(cfg, False)
        + expert_layers(cfg) * layer_fixed_matmul_params(cfg, True))
    head = 6.0 * flops.head_params(cfg) * tokens
    experts = 6.0 * expert_params(cfg) * rows_held
    attention = float(sum(
        batch * flops.attention_matmul_flops(dict(cfg, sliding_window=w), seen_len, 6)
        for w in layer_windows(cfg)))
    return {"layers": layers, "head": head, "experts": experts,
            "attention": attention, "tokens": float(tokens),
            "total": layers + head + experts + attention}


def flash_call_cost(cfg: Dict, rows: int, seq: int, kind: str) -> Dict[str, float]:
    """Least FLOPs and HBM bytes of one flash call over `rows` sequences
    where the windowed and the full layers' calls share the kernels'
    names: the mean over the configuration's layers of `flops.py`'s cost
    at each layer's own window. kind: "fwd" or "bwd"."""
    costs = [flops.flash_call_cost(dict(cfg, sliding_window=w), rows, seq, kind)
             for w in layer_windows(cfg)]
    return {k: sum(c[k] for c in costs) / len(costs) for k in ("flops", "bytes")}
