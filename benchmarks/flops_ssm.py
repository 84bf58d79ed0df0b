"""Operations of a training step of a model whose layers are Mamba-2
state-space mixers or grouped-query attention (`layer_types`), a dense
SwiGLU in every layer, a tied head.

6 FLOPs a matmul weight and token by layer kind, the tied head once (the
lookup is left out), the attention layers' causal scores by
`flops.attention_matmul_flops`, and each state-space layer's scan *as the
algorithm at the configuration's chunk*, whatever the program's own
chunking is: in a chunk of Q tokens the causal C B^T once a group
(Q (Q + 1) / 2 pairs of `mamba_d_state` entries), its product with x a
head, the chunk's end state from B and x, and the carried state's part of
each output; forward, and twice that backward. The convolution's taps,
the decays and the gated norm run on the vector unit and are left out.
Arithmetic on a configuration file and a cell file, as `flops.py`;
nothing is read from the program. Recomputed operations (remat of the
layers, of the head's pieces) never count as required.
"""
from __future__ import annotations

from typing import Dict

from benchmarks import flops


def ssm_inner(cfg: Dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def ssm_conv_channels(cfg: Dict) -> int:
    return ssm_inner(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def mixer_matmul_params(cfg: Dict, kind: str) -> int:
    d = cfg["hidden_size"]
    if kind == "mamba":
        in_proj = ssm_inner(cfg) + ssm_conv_channels(cfg) + cfg["mamba_n_heads"]
        return d * in_proj + ssm_inner(cfg) * d
    hd, nq, nkv = flops.head_dim(cfg), cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * nq * hd + 2 * d * nkv * hd + nq * hd * d


def mixer_vector_params(cfg: Dict, kind: str) -> int:
    """A mixer's leaves that enter no matmul: taps and their bias, dt_bias,
    A_log, D, the gated norm."""
    if kind != "mamba":
        return 0
    return (ssm_conv_channels(cfg) * (cfg["mamba_d_conv"] + 1)
            + 3 * cfg["mamba_n_heads"] + ssm_inner(cfg))


def mlp_params(cfg: Dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def matmul_params(cfg: Dict) -> int:
    """Every weight a token is multiplied by: mixers, FFNs and the head
    (the tied embedding's transpose)."""
    return flops.head_params(cfg) + sum(
        mixer_matmul_params(cfg, kind) + mlp_params(cfg) for kind in cfg["layer_types"])


def total_params(cfg: Dict) -> int:
    """All stored parameters (memory arithmetic, not FLOPs)."""
    d = cfg["hidden_size"]
    total = cfg["vocab_size"] * d + d  # embedding, final norm
    if not cfg.get("tie_word_embeddings"):
        total += flops.head_params(cfg)
    for kind in cfg["layer_types"]:
        total += (mixer_matmul_params(cfg, kind) + mixer_vector_params(cfg, kind)
                  + mlp_params(cfg) + 2 * d)
    return total


def scan_flops_per_token(cfg: Dict) -> float:
    """Forward FLOPs a token of one state-space layer's chunked scan."""
    q, n, inner = cfg["mamba_chunk_size"], cfg["mamba_d_state"], ssm_inner(cfg)
    pairs = (q + 1) / 2  # causal pairs a token, inside its chunk
    scores = pairs * 2 * n * cfg["mamba_n_groups"]
    applied = pairs * 2 * inner
    state_and_carried = 2 * 2 * inner * n
    return scores + applied + state_and_carried


def step_flops(cfg: Dict, batch: int, seen_len: int) -> Dict[str, float]:
    """Required FLOPs of one training step: forward and backward."""
    tokens = batch * seen_len
    kinds = list(cfg["layer_types"])
    matmul = 6.0 * matmul_params(cfg) * tokens
    attention = float(kinds.count("attention") * batch
                      * flops.attention_matmul_flops(cfg, seen_len, 6))
    scans = 3.0 * kinds.count("mamba") * scan_flops_per_token(cfg) * tokens
    return {"matmul": matmul, "attention": attention, "scans": scans,
            "tokens": float(tokens), "total": matmul + attention + scans}
