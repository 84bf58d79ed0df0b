"""The whole step's share of the chips' peak: required FLOPs (no
recompute) of the tokens the window trained, over the window's time,
over chips times the peak."""
from benchmarks import flops


def reduce(ctx, params):
    if not ctx.get("window"):
        return None
    cell, win = ctx["cell"], ctx["window"]
    per_step = flops.step_flops(ctx["cfg"], cell["batch"], cell["seen_len"])["total"]
    rate = per_step * win["steps"] / win["elapsed_s"]
    return 100.0 * rate / (cell["chips"] * ctx["peak"]["bf16_flops_per_s"])
