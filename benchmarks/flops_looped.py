"""Operations of a training step of a looped decoder: a stack of
`num_hidden_layers` layers applied `total_ut_steps` times over the same
weights, with the head and an exit gate after every pass.

A weight that is used in T passes multiplies every token T times, so the
count is by use and not by stored parameter: 6 FLOPs a matmul weight,
token and pass, T heads and T gates, the causal scores of T x N layer
applications (2 score-shaped matmuls forward, 4 backward). The number of
passes is the one the step counted (`loop_passes`); everything else is
arithmetic on the configuration file and the cell file, as `flops.py`.
Recomputed operations (remat of the layers, of each pass's head) never
count as required.
"""
from __future__ import annotations

from typing import Dict

from benchmarks import flops


def gate_params(cfg: Dict) -> int:
    """The exit gate's Linear(hidden, 1) weight."""
    return cfg["hidden_size"]


def pass_matmul_params(cfg: Dict) -> int:
    """Weights a token is multiplied by in one pass: every layer, the
    head, the gate."""
    return (cfg["num_hidden_layers"] * flops.layer_matmul_params(cfg)
            + flops.head_params(cfg) + gate_params(cfg))


def total_params(cfg: Dict) -> int:
    """All stored parameters (memory arithmetic, not FLOPs): four norms a
    layer, the final norm, an untied head, the gate and its bias."""
    d = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"] * (flops.layer_matmul_params(cfg) + 4 * d)
    head = 0 if cfg.get("tie_word_embeddings") else flops.head_params(cfg)
    return layers + cfg["vocab_size"] * d + head + d + gate_params(cfg) + 1


def step_flops(cfg: Dict, batch: int, seen_len: int, passes: float) -> Dict[str, float]:
    """Required FLOPs of one training step that ran `passes` passes."""
    tokens = batch * seen_len
    layers = 6.0 * cfg["num_hidden_layers"] * flops.layer_matmul_params(cfg) * tokens * passes
    heads = 6.0 * (flops.head_params(cfg) + gate_params(cfg)) * tokens * passes
    attention = float(passes * cfg["num_hidden_layers"] * batch
                      * flops.attention_matmul_flops(cfg, seen_len, 6))
    return {"layers": layers, "heads": heads, "attention": attention,
            "tokens": float(tokens), "total": layers + heads + attention}
