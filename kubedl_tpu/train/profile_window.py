"""Shared JAX-profiler window for the trainer entrypoints.

One class owns the ``--profile-dir`` start/stop discipline so the SPMD
trainer (train/trainer.py) and the MPMD stage trainer
(train/pipeline_trainer.py) cannot drift: the trace covers
``[start_step+1, start_step+1+n_steps)`` — skipping the first step after
a start, which traces and lowers the step and compiles it or reads it
from the cache (obs/compiles.py says which, on its ``train.compile``
record; a recompile inside the window shows as ``jax.trace``,
``jax.lower``, ``jax.compile`` on the host plane) — and ``stop()`` is

  * idempotent: the flag flips BEFORE the profiler call, so the SIGTERM
    preemption path, the end-of-loop path, and the ``finally`` backstop
    can all call it without a double-stop error;
  * exception-safe: a profiler that refuses to stop (e.g. it already
    tore down during interpreter shutdown) logs and moves on — a trace
    hiccup must never turn a clean checkpoint exit into a crash.

The ``finally`` backstop matters for SIGTERM *during* the traced window:
the preemption flag is polled after each step, but a step that raises
while tracing would otherwise leave the profiler open past os._exit and
drop the trace.
"""
from __future__ import annotations

import sys
from typing import Optional


class ProfileWindow:
    def __init__(
        self,
        profile_dir: str,
        start_step: int,
        n_steps: int = 5,
        profiler=None,
    ) -> None:
        self.profile_dir = profile_dir
        # [start+1, start+1+n): skip the first step after a start
        self.start_at = start_step + 1 if profile_dir else -1
        self.stop_after = self.start_at + max(n_steps, 1)
        self.tracing = False
        self._profiler = profiler  # test seam; None = jax.profiler, lazily

    def _jax_profiler(self):
        if self._profiler is None:
            import jax

            self._profiler = jax.profiler
        return self._profiler

    def maybe_start(self, step: int) -> None:
        """Call at the TOP of the step loop, before dispatching the step."""
        if step == self.start_at and not self.tracing:
            self.tracing = True
            try:
                self._jax_profiler().start_trace(self.profile_dir)
            except Exception as e:  # noqa: BLE001 — profiling is best-effort
                self.tracing = False
                print(f"profiler start failed: {e}", file=sys.stderr)

    def should_stop(self, step: int) -> bool:
        """True when the step just completed closes the traced window
        (the caller syncs the device before stop() so the trace holds
        finished work, not in-flight dispatches)."""
        return self.tracing and step + 1 >= self.stop_after

    def stop(self) -> None:
        """Idempotent, exception-safe stop — safe from the preemption
        path, the normal end, and the finally backstop alike."""
        if not self.tracing:
            return
        self.tracing = False  # flip FIRST: re-entry must be a no-op
        try:
            self._jax_profiler().stop_trace()
            print(f"profile written to {self.profile_dir}", flush=True)
        except Exception as e:  # noqa: BLE001 — trace loss must not crash exit
            print(f"profiler stop failed: {e}", file=sys.stderr)


def window_from_args(args, start_step: int,
                     profiler=None) -> Optional[ProfileWindow]:
    """ProfileWindow from the shared --profile-dir/--profile-steps flags;
    None when profiling is off."""
    profile_dir = getattr(args, "profile_dir", "")
    if not profile_dir:
        return None
    return ProfileWindow(
        profile_dir, start_step,
        n_steps=getattr(args, "profile_steps", 5), profiler=profiler)
