"""Median time of the traced steps, each ended by a wait (host clock)."""
import statistics


def reduce(ctx, params):
    traced = ctx.get("traced")
    if not traced or not traced.get("step_s"):
        return None
    return 1000.0 * statistics.median(traced["step_s"])
