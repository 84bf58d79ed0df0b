"""Operations and bytes of a training step of latent-attention blocks on
several residual streams with a multi-token prediction module, and of its
flash calls, from shapes and from the one thing shapes cannot give: how
many rows the router sent to the experts held here, which the step counts
(`moe_rows_held`, the module's block included).

A block is latent attention (q through `q_lora_rank`, keys and values
through `kv_lora_rank`, keys `qk_nope_head_dim + qk_rope_head_dim` wide
and values `v_head_dim`), two hyper-connection mappings, and a dense FFN
(the first `first_k_dense_replace` blocks) or a routed expert layer that
holds `n_routed_experts` of the router's `router_outputs` experts beside
`n_shared_experts` shared ones. The module is a projection of
[embedding; state], one expert block and the head again. Arithmetic on a
configuration file and a cell file, as `flops.py`; nothing is read from
the program but that count. Recomputed operations (remat) never count as
required, and Sinkhorn's and the mixes' elementwise work counts nothing.
"""
from __future__ import annotations

from typing import Dict

from benchmarks import flops

# score-shaped matrix products of the flash kernels, by the width of the
# contraction or of the output: the forward's QK^T at the q/k width and PV
# at the value width; the backward's S again, dK and dQ at the q/k width,
# dP and dV at the value width
FWD_PRODUCTS = {"qk": 1, "v": 1}
BWD_PRODUCTS = {"qk": 3, "v": 2}


def widths(cfg: Dict) -> Dict[str, int]:
    return {"qk": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"]}


def score_columns(cfg: Dict, products: Dict[str, int]) -> int:
    w = widths(cfg)
    return sum(n * w[k] for k, n in products.items())


def mla_matmul_params(cfg: Dict) -> int:
    d, h, w = cfg["hidden_size"], cfg["num_attention_heads"], widths(cfg)
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (d * qr + qr * h * w["qk"] + d * (kvr + cfg["qk_rope_head_dim"])
            + kvr * h * (cfg["qk_nope_head_dim"] + w["v"]) + h * w["v"] * d)


def hc_matmul_params(cfg: Dict) -> int:
    """One mapping's three projections."""
    n = cfg["hc_mult"]
    return n * cfg["hidden_size"] * n * (n + 2)


def hc_params(cfg: Dict) -> int:
    n = cfg["hc_mult"]
    return hc_matmul_params(cfg) + n + n + n * n + 3


def expert_params(cfg: Dict) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def block_fixed_matmul_params(cfg: Dict, routed: bool) -> int:
    """Weights of one block that every token is multiplied by whatever
    the router says."""
    d = cfg["hidden_size"]
    total = mla_matmul_params(cfg) + 2 * hc_matmul_params(cfg)
    if routed:
        return total + d * cfg["router_outputs"] + cfg["n_shared_experts"] * expert_params(cfg)
    return total + 3 * d * cfg["intermediate_size"]


def block_params(cfg: Dict, routed: bool) -> int:
    """All stored parameters of one block."""
    d = cfg["hidden_size"]
    total = (mla_matmul_params(cfg) + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
             + 2 * hc_params(cfg) + 2 * d)
    if routed:
        return (total + (d + 1) * cfg["router_outputs"]
                + (cfg["n_shared_experts"] + cfg["n_routed_experts"]) * expert_params(cfg))
    return total + 3 * d * cfg["intermediate_size"]


def total_params(cfg: Dict) -> int:
    """All stored parameters (memory arithmetic, not FLOPs)."""
    d, dense = cfg["hidden_size"], cfg["first_k_dense_replace"]
    total = 2 * cfg["vocab_size"] * d + d  # embedding, head, final norm
    total += dense * block_params(cfg, False)
    total += (cfg["num_hidden_layers"] - dense) * block_params(cfg, True)
    if cfg["num_nextn_predict_layers"]:
        total += 2 * d * d + 3 * d + block_params(cfg, True)
    return total


def expert_blocks(cfg: Dict) -> int:
    """Blocks with a routed expert layer, the module's among them."""
    return (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
            + cfg["num_nextn_predict_layers"])


def uniform_rows_held(cfg: Dict, tokens: int) -> float:
    """Rows one expert layer computes here when every output is as likely
    as any other: each token's k choices, the share of outputs held."""
    return (tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_outputs"])


def step_flops(cfg: Dict, batch: int, seen_len: int, rows_held: float) -> Dict[str, float]:
    """Required FLOPs of one training step. `rows_held` is the step's
    count of rows computed by experts held here, summed over the expert
    blocks. 6 per matmul weight and row; the scores' products forward and
    backward over the attended keys at their own widths; the module's
    head over the positions whose second-next token was fed."""
    tokens, mtp = batch * seen_len, cfg["num_nextn_predict_layers"]
    dense = cfg["first_k_dense_replace"]
    blocks = 6.0 * tokens * (
        dense * block_fixed_matmul_params(cfg, False)
        + (cfg["num_hidden_layers"] - dense) * block_fixed_matmul_params(cfg, True))
    head = 6.0 * flops.head_params(cfg) * tokens
    module = 0.0
    if mtp:
        module = 6.0 * tokens * (2 * cfg["hidden_size"] ** 2
                                 + block_fixed_matmul_params(cfg, True))
        module += 6.0 * flops.head_params(cfg) * batch * (seen_len - 1)
    experts = 6.0 * expert_params(cfg) * rows_held
    layers = cfg["num_hidden_layers"] + mtp
    columns = score_columns(cfg, FWD_PRODUCTS) + score_columns(cfg, BWD_PRODUCTS)
    attention = float(layers * batch * 2 * cfg["num_attention_heads"]
                      * flops.attended_keys(seen_len, None) * columns)
    return {"blocks": blocks, "head": head, "mtp_fixed": module, "experts": experts,
            "attention": attention, "tokens": float(tokens),
            "total": blocks + head + module + experts + attention}


def flash_call_cost(cfg: Dict, rows: int, seq: int, kind: str,
                    bytes_per_el: int = 2) -> Dict[str, float]:
    """Least FLOPs and HBM bytes of one flash call over `rows` sequences
    of one layer at the model's own widths: q, k, dq, dk at the q/k width
    and v, o, do, dv at the value width. kind: "fwd" or "bwd" (dq and
    dk/dv together)."""
    h, w = cfg["num_attention_heads"], widths(cfg)
    per_lane = rows * h * seq * bytes_per_el
    lse = rows * h * seq * 4
    keys = flops.attended_keys(seq, None)
    if kind == "fwd":
        columns = score_columns(cfg, FWD_PRODUCTS)
        byts = per_lane * (2 * w["qk"] + 2 * w["v"]) + lse  # q, k, v in; o, lse out
    elif kind == "bwd":
        columns = score_columns(cfg, BWD_PRODUCTS)
        # q, k, v, o, do in; dq, dk, dv out; lse and delta
        byts = per_lane * (4 * w["qk"] + 4 * w["v"]) + 2 * lse
    else:
        raise ValueError(f"unknown flash call kind {kind!r}")
    return {"flops": float(rows * 2 * h * keys * columns), "bytes": float(byts)}
