"""Operations and bytes that a training step and its attention kernels
require, from shapes alone.

Everything here is arithmetic on a configuration file (the published
keys of `benchmarks/configs/*.json`) and a cell file (`batch`,
`seen_len`). Nothing is read from the program: a later PR cannot move
these counts. Recomputed operations (remat) never count as required.
"""
from __future__ import annotations

from typing import Dict, Optional


def head_dim(cfg: Dict) -> int:
    return int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])


def layer_matmul_params(cfg: Dict) -> int:
    """Weights of one decoder layer that enter a matrix multiplication."""
    d, ff, hd = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = d * nq * hd + 2 * d * nkv * hd + nq * hd * d
    return attn + 3 * d * ff


def head_params(cfg: Dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def matmul_params(cfg: Dict) -> int:
    """Every weight a token is multiplied by: the layers and the output
    head. The embedding table is a lookup and is left out."""
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg) + head_params(cfg)


def total_params(cfg: Dict) -> int:
    """All stored parameters (for memory arithmetic, not for FLOPs)."""
    d = cfg["hidden_size"]
    embed = cfg["vocab_size"] * d
    head = 0 if cfg.get("tie_word_embeddings") else head_params(cfg)
    norms = (2 * cfg["num_hidden_layers"] + 1) * d
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg) + embed + head + norms


def attended_keys(seq: int, window: Optional[int]) -> int:
    """Sum over the queries of one causal sequence of the keys each one
    attends: query i sees min(i + 1, window) keys."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_matmul_flops(cfg: Dict, seq: int, n_matmuls: int) -> int:
    """FLOPs of `n_matmuls` score-shaped matrix multiplications (QK^T, PV
    and their transposes all cost the same) over one sequence and one
    layer, all query heads, only the attended keys."""
    return (2 * n_matmuls * cfg["num_attention_heads"] * head_dim(cfg)
            * attended_keys(seq, cfg.get("sliding_window")))


def step_flops(cfg: Dict, batch: int, seen_len: int) -> Dict[str, float]:
    """Required FLOPs of one training step: forward and backward, no
    recompute. 6 per matmul weight and token; attention is 2 matmuls
    forward and 4 backward over the attended keys."""
    tokens = batch * seen_len
    matmul = 6.0 * matmul_params(cfg) * tokens
    attention = float(cfg["num_hidden_layers"] * batch
                      * attention_matmul_flops(cfg, seen_len, 6))
    return {"matmul": matmul, "attention": attention,
            "total": matmul + attention, "tokens": float(tokens)}


# matrix multiplications each flash kernel performs (score-shaped): the
# forward does QK^T and PV; the backward as an algorithm needs S again,
# dP, dV, dK and dQ. The program splits the backward into two kernels
# that each recompute S and dP (7 in all); the two extra are not required
# work and are not counted.
FLASH_FWD_MATMULS = 2
FLASH_BWD_MATMULS = 5


def flash_call_cost(cfg: Dict, rows: int, seq: int, kind: str,
                    bytes_per_el: int = 2) -> Dict[str, float]:
    """Least FLOPs and HBM bytes of one flash call over `rows` sequences
    of one layer, with K/V already broadcast to the query heads as the
    program hands them to the kernel. kind: "fwd" or "bwd" (dq and dk/dv
    together)."""
    h, hd = cfg["num_attention_heads"], head_dim(cfg)
    tensor = rows * h * seq * hd * bytes_per_el
    lse = rows * h * seq * 4
    if kind == "fwd":
        flops = rows * attention_matmul_flops(cfg, seq, FLASH_FWD_MATMULS)
        byts = 4 * tensor + lse  # q, k, v in; o, lse out
    elif kind == "bwd":
        flops = rows * attention_matmul_flops(cfg, seq, FLASH_BWD_MATMULS)
        byts = 8 * tensor + 2 * lse  # q, k, v, o, do in; dq, dk, dv out
    else:
        raise ValueError(f"unknown flash call kind {kind!r}")
    return {"flops": float(flops), "bytes": float(byts)}


def roofline_seconds(flops: float, byts: float, peak: Dict) -> Dict[str, float]:
    """Least time the chip could take: the larger of FLOPs over peak
    FLOP/s and bytes over peak bytes/s, and which of the two binds."""
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = byts / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_f, t_b), "bound": "flops" if t_f >= t_b else "bytes"}
