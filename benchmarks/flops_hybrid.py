"""Operations and bytes of a training step whose layers are not all one
dense decoder layer, and of its grouped-matmul calls, from shapes and
from the one thing shapes cannot give: how many rows the router sent to
the experts held here, which the step counts (`moe_rows_held`).

A layer's mixer is a gated short convolution or grouped-query attention
(`layer_types`); its FFN is dense (the first `num_dense_layers`) or a
routed expert layer that holds `num_experts` of the router's
`router_outputs` experts. Arithmetic on a configuration file and a cell
file, as `flops.py`; nothing is read from the program but that count.
Recomputed operations (remat) never count as required.
"""
from __future__ import annotations

from typing import Dict, List

from benchmarks import flops


def layer_kinds(cfg: Dict) -> List[str]:
    return list(cfg["layer_types"])


def routed(cfg: Dict, i: int) -> bool:
    return i >= cfg["num_dense_layers"]


def mixer_matmul_params(cfg: Dict, kind: str) -> int:
    d, hd = cfg["hidden_size"], flops.head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    if kind == "conv":
        return d * 3 * d + d * d  # in_proj and out_proj
    return d * nq * hd + 2 * d * nkv * hd + nq * hd * d


def expert_params(cfg: Dict) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def fixed_matmul_params(cfg: Dict) -> int:
    """Weights every token is multiplied by whatever the router says:
    mixers, dense FFNs, routers and the head (the tied embedding's
    transpose; the lookup itself is left out)."""
    d = cfg["hidden_size"]
    total = flops.head_params(cfg)
    for i, kind in enumerate(layer_kinds(cfg)):
        total += mixer_matmul_params(cfg, kind)
        total += (d * cfg["router_outputs"] if routed(cfg, i)
                  else 3 * d * cfg["intermediate_size"])
    return total


def total_params(cfg: Dict) -> int:
    """All stored parameters (memory arithmetic, not FLOPs)."""
    d, hd = cfg["hidden_size"], flops.head_dim(cfg)
    total = cfg["vocab_size"] * d + d  # embedding, final norm
    if not cfg.get("tie_word_embeddings"):
        total += flops.head_params(cfg)
    for i, kind in enumerate(layer_kinds(cfg)):
        total += mixer_matmul_params(cfg, kind) + 2 * d  # two norms
        total += d * cfg["conv_L_cache"] if kind == "conv" else 2 * hd
        if routed(cfg, i):
            total += (d + 1) * cfg["router_outputs"]  # router and its bias
            total += cfg["num_experts"] * expert_params(cfg)
        else:
            total += 3 * d * cfg["intermediate_size"]
    return total


def uniform_rows_held(cfg: Dict, tokens: int) -> float:
    """Rows one expert layer computes here when every output is as likely
    as any other: each token's k choices, the share of outputs held."""
    return tokens * cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["router_outputs"]


def step_flops(cfg: Dict, batch: int, seen_len: int, rows_held: float) -> Dict[str, float]:
    """Required FLOPs of one training step. `rows_held` is the step's
    count of rows computed by experts held here, summed over the expert
    layers. 6 per matmul weight and row; the convolution's taps 2 per
    tap, channel and token forward and twice that backward; attention in
    the attention layers only, 2 score-shaped matmuls forward and 4
    backward over the attended keys."""
    tokens = batch * seen_len
    kinds = layer_kinds(cfg)
    fixed = 6.0 * fixed_matmul_params(cfg) * tokens
    experts = 6.0 * expert_params(cfg) * rows_held
    taps = 6.0 * cfg["conv_L_cache"] * cfg["hidden_size"] * tokens * kinds.count("conv")
    attention = float(kinds.count("full_attention") * batch
                      * flops.attention_matmul_flops(cfg, seen_len, 6))
    return {"fixed_matmul": fixed, "experts": experts, "conv_taps": taps,
            "attention": attention, "tokens": float(tokens),
            "total": fixed + experts + taps + attention}


# matrix products of shape [rows, hidden] x [hidden, expert width] (or its
# transposes, which cost the same) that one call of each kernel performs
GMM_PRODUCTS = {"gmm": 1, "gmm_scaled": 1, "gmm_swiglu": 2, "gmm_drhs": 1}


def gmm_call_cost(cfg: Dict, kernel: str, rows: float,
                  bytes_per_el: int = 2) -> Dict[str, float]:
    """Least FLOPs and HBM bytes of one grouped-matmul call over `rows`
    routed rows (tile padding is not required work): each row in and out
    once, each held expert's matrix once."""
    d, ff, e = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts"]
    n = GMM_PRODUCTS[kernel]
    if kernel == "gmm_drhs":
        # both row operands in, one float32 gradient per held expert out
        byts = rows * (d + ff) * bytes_per_el + e * d * ff * 4
    else:
        byts = (rows * (d + ff) + n * e * d * ff) * bytes_per_el
    return {"flops": 2.0 * n * rows * d * ff, "bytes": float(byts)}
