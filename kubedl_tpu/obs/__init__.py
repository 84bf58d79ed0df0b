"""Flight recorder (docs/observability.md): cross-plane trace spans
(obs/trace.py), per-step telemetry + straggler detection (obs/steps.py),
goodput accounting over the span timeline (obs/goodput.py), and, in a
process that runs JAX, every compile as JAX reports it (obs/compiles.py:
`from kubedl_tpu.obs import compiles`, not imported here, so that the
operator stays off JAX)."""
from kubedl_tpu.obs.goodput import GoodputReporter, classify, goodput
from kubedl_tpu.obs.steps import StepAggregator, StepStream, load_step_records
from kubedl_tpu.obs.trace import (
    ENV_TRACE_DIR,
    ENV_TRACE_ID,
    Tracer,
    chrome_trace,
    job_trace_dir,
    load_spans,
    trace_id_for,
    tracer_from_env,
)

__all__ = [
    "ENV_TRACE_DIR",
    "ENV_TRACE_ID",
    "GoodputReporter",
    "StepAggregator",
    "StepStream",
    "Tracer",
    "chrome_trace",
    "classify",
    "goodput",
    "job_trace_dir",
    "load_spans",
    "load_step_records",
    "trace_id_for",
    "tracer_from_env",
]
