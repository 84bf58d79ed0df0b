"""Flight-recorder trace spans — the cross-plane timeline primitive.

An OTel-shaped but dependency-free span API: every interesting interval
(gang queue wait, reconcile, checkpoint save, a train step, a reshard
ladder rung) becomes one JSON record

    {"name", "trace_id", "span_id", "parent_id", "service",
     "ts" (epoch seconds), "dur" (seconds), "attrs": {...}}

kept in a bounded in-process ring buffer and appended to a JSONL file.
Durations come from the monotonic clock (``perf_counter``); ``ts`` is the
wall clock, which is the shared axis that lets the operator process and
its workload pods — separate OS processes on the local executor — merge
into one timeline.

A span used as a context manager is also a ``jax.profiler.TraceAnnotation``
of the same name while it is open, in a process that has imported ``jax``
already (this module never imports it: the operator stays off JAX). With
no profiler session an annotation is a no-op; under one (the trainers'
``--profile-dir`` window) the span lies on the profiler's clock beside the
device's operations, so a gap on the device has a name. ``record()`` and
``span().end()`` without ``with`` have no open interval to mirror.

Correlation works the way ``KUBEDL_CONTROL_DIR`` already travels: the
executor derives a deterministic gang-level trace id from the job key and
injects ``KUBEDL_TRACE_ID`` + a per-job ``KUBEDL_TRACE_DIR`` into every
container, while the operator's tracer routes its own spans into the same
per-job directory (``operator.jsonl``). `kubedl-tpu trace <job>` and the
goodput accountant (obs/goodput.py) read the merged directory back.
"""
from __future__ import annotations

import hashlib
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

ENV_TRACE_DIR = "KUBEDL_TRACE_DIR"
ENV_TRACE_ID = "KUBEDL_TRACE_ID"

# step-record streams (obs/steps.py) share the trace dir but are NOT
# spans; load_spans must skip them
STEP_SUFFIX = ".steps.jsonl"


def trace_id_for(namespace: str, name: str) -> str:
    """Deterministic gang-level trace id: stable across pod restarts and
    preemption re-admissions, so one job's whole life — including the
    downtime — is ONE timeline."""
    return hashlib.sha1(f"{namespace}/{name}".encode()).hexdigest()[:32]


def job_trace_dir(root: str, namespace: str, name: str) -> str:
    """The per-job trace directory both planes agree on (the executor
    injects it as KUBEDL_TRACE_DIR; the operator exports into it)."""
    return os.path.join(root, f"{namespace}_{name}")


class Span:
    """One open span; finishes on end() or context-manager exit (an
    exception stamps an ``error`` attribute before closing). ``dur`` is
    None until then. ``export=False`` keeps the record in the ring and
    out of the JSONL (sub-step spans that the profiler shows and whose
    durations ride on another record)."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "service",
                 "ts", "attrs", "dur", "export", "_tracer", "_t0", "_done",
                 "_annotation")

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 parent_id: str, attrs: Dict, export: bool = True) -> None:
        self._tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = tracer._next_id()
        self.parent_id = parent_id
        self.service = tracer.service
        self.ts = time.time()
        self._t0 = time.perf_counter()
        self.attrs = dict(attrs)
        self.dur: Optional[float] = None
        self.export = export
        self._done = False
        self._annotation = None

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def end(self) -> Dict:
        if self._done:
            return {}
        self._done = True
        self.dur = time.perf_counter() - self._t0
        return self._tracer._finish(self, self.dur)

    def __enter__(self) -> "Span":
        self._tracer._push(self)
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is not None:
            self._annotation = profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        self._tracer._pop(self)
        if exc is not None:
            self.attrs.setdefault("error", f"{type(exc).__name__}: {exc}"[:200])
        self.end()

    def cancel(self) -> None:
        """Close a span opened with ``__enter__`` and make no record of
        it: the profiler has its annotation, the ring and the file get
        nothing (obs/compiles.py, which learns only later whether a
        compile was worth a record, and writes it then with JAX's own
        times)."""
        self._done = True
        self.__exit__(None, None, None)


class Tracer:
    """Bounded flight recorder: in-process ring + optional JSONL export.

    Export modes (at most one):
      * ``export_path`` — every span appends to ONE file (workload pods:
        ``<KUBEDL_TRACE_DIR>/<pod>.jsonl``);
      * ``export_root`` — spans route per job into
        ``<root>/<ns>_<job>/<service>.jsonl`` using their ``namespace``/
        ``job`` attrs (the operator's control-plane tracer); spans with
        no job attr stay ring-only.

    ``max_export_spans`` bounds the file footprint PER FILE: past it,
    spans keep landing in the ring but stop being written to that file
    (``dropped`` counts them) — the recorder degrades to a ring, it
    never grows without bound. The budget is per file, not fleet-wide:
    a long-lived operator's reconcile churn on old jobs must never
    silence the queue-wait evidence of a NEW job's timeline.
    """

    def __init__(
        self,
        service: str = "",
        trace_id: str = "",
        export_path: Optional[str] = None,
        export_root: Optional[str] = None,
        ring_size: int = 2048,
        max_export_spans: int = 20000,
    ) -> None:
        self.service = service
        self.trace_id = trace_id
        self.export_path = export_path
        self.export_root = export_root
        self.max_export_spans = max_export_spans
        self._ring: deque = deque(maxlen=ring_size)
        self._lock = threading.Lock()
        self._files: Dict[str, object] = {}
        self._exported: Dict[str, int] = {}  # per export file
        self.dropped = 0
        self._ids = itertools.count(1)
        self._local = threading.local()

    @property
    def exporting(self) -> bool:
        return bool(self.export_path or self.export_root)

    # -- span lifecycle --------------------------------------------------

    def _next_id(self) -> str:
        return f"{os.getpid():x}-{next(self._ids):x}"

    def _stack(self) -> List[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span) -> None:
        st = self._stack()
        if st and st[-1] is span:
            st.pop()

    def current(self) -> Optional[Span]:
        st = self._stack()
        return st[-1] if st else None

    def span(self, name: str, trace_id: Optional[str] = None,
             export: bool = True, **attrs) -> Span:
        """Open a span (use as a context manager for nesting: the parent
        is whatever span the calling thread currently has open). Children
        inherit the parent's trace id and job/namespace routing attrs, so
        a nested span lands in the same per-job file. Under ``with`` the
        span is mirrored into an open JAX profiler session (module
        docstring); ``export=False`` keeps it out of the JSONL."""
        parent = self.current()
        if parent is not None:
            for key in ("job", "namespace"):
                if key in parent.attrs and key not in attrs:
                    attrs[key] = parent.attrs[key]
        return Span(
            self, name,
            trace_id=trace_id or (parent.trace_id if parent else "") or self.trace_id,
            parent_id=parent.span_id if parent else "",
            attrs=attrs, export=export,
        )

    def record(
        self,
        name: str,
        duration_s: float = 0.0,
        end_ts: Optional[float] = None,
        trace_id: Optional[str] = None,
        **attrs,
    ) -> Dict:
        """Retroactively record a finished interval (e.g. a queue wait
        measured from monotonic timestamps): ``ts`` is back-dated so the
        span COVERS the interval that just ended. An interval that is
        already over cannot be mirrored into a profiler session: what
        the profiler should show is opened with ``span()``."""
        end_ts = time.time() if end_ts is None else end_ts
        rec = {
            "name": name,
            "trace_id": trace_id if trace_id is not None else self.trace_id,
            "span_id": self._next_id(),
            "parent_id": "",
            "service": self.service,
            "ts": end_ts - max(duration_s, 0.0),
            "dur": max(duration_s, 0.0),
            "attrs": dict(attrs),
        }
        self._commit(rec)
        return rec

    def _finish(self, span: Span, dur: float) -> Dict:
        rec = {
            "name": span.name,
            "trace_id": span.trace_id,
            "span_id": span.span_id,
            "parent_id": span.parent_id,
            "service": span.service,
            "ts": span.ts,
            "dur": dur,
            "attrs": span.attrs,
        }
        self._commit(rec, export=span.export)
        return rec

    # -- sinks -----------------------------------------------------------

    def _commit(self, rec: Dict, export: bool = True) -> None:
        with self._lock:
            self._ring.append(rec)
            path = self._path_for(rec) if export else None
            if path is None:
                return
            if self._exported.get(path, 0) >= self.max_export_spans:
                self.dropped += 1
                return
            try:
                fh = self._files.get(path)
                if fh is None:
                    os.makedirs(os.path.dirname(path), exist_ok=True)
                    fh = self._files[path] = open(path, "a")
                fh.write(json.dumps(rec, default=str) + "\n")
                fh.flush()
                self._exported[path] = self._exported.get(path, 0) + 1
            except OSError:
                self.dropped += 1

    def _path_for(self, rec: Dict) -> Optional[str]:
        if self.export_path:
            return self.export_path
        if self.export_root:
            job = rec["attrs"].get("job")
            if not job:
                return None
            namespace = rec["attrs"].get("namespace", "default")
            return os.path.join(
                job_trace_dir(self.export_root, namespace, job),
                f"{self.service or 'operator'}.jsonl",
            )
        return None

    def spans(self) -> List[Dict]:
        """Snapshot of the ring (oldest first)."""
        with self._lock:
            return list(self._ring)

    def close(self) -> None:
        with self._lock:
            for fh in self._files.values():
                try:
                    fh.close()
                except OSError:
                    pass
            self._files.clear()


def tracer_from_env(service: str = "") -> Tracer:
    """Workload-side tracer from the injected env: exports to
    ``<KUBEDL_TRACE_DIR>/<service>.jsonl`` with the gang trace id from
    ``KUBEDL_TRACE_ID``. Without the env the tracer stays ring-only
    (``exporting`` False), so uninstrumented runs pay nothing."""
    service = service or os.environ.get("POD_NAME", "") or f"pid-{os.getpid()}"
    d = os.environ.get(ENV_TRACE_DIR, "")
    path = None
    if d:
        try:
            os.makedirs(d, exist_ok=True)
            path = os.path.join(d, f"{service}.jsonl")
        except OSError:
            path = None
    return Tracer(
        service=service,
        trace_id=os.environ.get(ENV_TRACE_ID, ""),
        export_path=path,
    )


def load_spans(trace_dir: str) -> List[Dict]:
    """Merge every span JSONL in a job's trace dir, sorted by start time.
    Step-record streams (``*.steps.jsonl``) and unparseable lines are
    skipped — a half-written tail line must not sink the whole timeline."""
    spans: List[Dict] = []
    try:
        names = sorted(os.listdir(trace_dir))
    except OSError:
        return spans
    for fname in names:
        if not fname.endswith(".jsonl") or fname.endswith(STEP_SUFFIX):
            continue
        try:
            with open(os.path.join(trace_dir, fname)) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if isinstance(rec, dict) and "name" in rec and "ts" in rec:
                        rec.setdefault("dur", 0.0)
                        rec.setdefault("attrs", {})
                        spans.append(rec)
        except OSError:
            continue
    spans.sort(key=lambda s: (s.get("ts", 0.0), s.get("name", "")))
    return spans


def chrome_trace(spans: List[Dict]) -> Dict:
    """Chrome trace-event JSON (load in Perfetto / chrome://tracing):
    one complete ("X") event per span, microsecond timestamps, plus "M"
    metadata naming the pid (trace id / job) and tid (service) rows."""
    events: List[Dict] = []
    pids: Dict[str, int] = {}
    tids: Dict[tuple, int] = {}
    for s in spans:
        pkey = s["attrs"].get("job") or s.get("trace_id") or "trace"
        pid = pids.get(pkey)
        if pid is None:
            pid = pids[pkey] = len(pids) + 1
            events.append({"name": "process_name", "ph": "M", "pid": pid,
                           "tid": 0, "args": {"name": str(pkey)}})
        tkey = (pid, s.get("service", ""))
        tid = tids.get(tkey)
        if tid is None:
            tid = tids[tkey] = len(tids) + 1
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid, "args": {"name": s.get("service", "") or "?"}})
        events.append({
            "name": s.get("name", ""),
            "cat": s.get("service", "") or "span",
            "ph": "X",
            "ts": float(s.get("ts", 0.0)) * 1e6,
            "dur": max(float(s.get("dur", 0.0)), 0.0) * 1e6,
            "pid": pid,
            "tid": tid,
            "args": {k: v for k, v in s.get("attrs", {}).items()},
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}
