"""Compiles as JAX reports them — the flight recorder's ``CompileLog``.

``jax.monitoring`` sends, for every jitted function, a scalar event when
its tracing, its lowering (the jaxpr's conversion to an MLIR module) or
the backend's compile *starts* and a time span when it *ends*, each with
``fun_name``; between the start and the end of a backend compile, on the
same thread, the persistent cache's request, hit or miss and its read
time. Listeners run on compile paths only: a cached dispatch sends
nothing, so a step that does not compile pays nothing.

One ``CompileLog`` a process (``install()``) turns those events into

* three spans of the process's tracer for every function compiled on its
  own (a *top-level* phase: nothing open above it on its thread):
  ``jax.trace``, ``jax.lower``, ``jax.compile``. Each is open as a
  ``TraceAnnotation`` while JAX works, so a profile window that holds a
  recompile names the gap; the record is written once the function is
  done, with JAX's own start time and duration, and only for a function
  whose three phases took ``MIN_RECORD_S`` or more in all (every eager
  ``convert_element_type`` is a compile of a few milliseconds too,
  hundreds a set-up);
* one record a compiled function (``records()``, the newest
  ``MAX_RECORDS``): ``fun``, ``trace_s``, ``lower_s``, ``executable_s``
  (the backend's compile, or the persistent cache's read where it hit),
  ``cache`` (``hit``, ``miss``, or ``off`` where no request was made),
  ``cache_read_s``, ``ts``;
* a table by function name (``nested()``, at most ``MAX_NAMES`` names and
  the rest under ``other``): calls, summed seconds and own seconds (less
  its children's) of every trace, the inner ``jit``s and ``custom_vjp``s
  that make no span among them; a function too small for a record, and
  an eager primitive compiled while another function is traced, add all
  three of their phases there;
* a count of the functions compiled so far (``count()``), by name or of
  the calling thread: the recompile counter the trainers read across a
  dispatch (``since()`` hands back what compiled).

This module imports the standard library and ``jax.monitoring`` alone,
and the latter only inside ``install()``: ``kubedl_tpu.obs`` stays
importable by the operator, which keeps off JAX.
"""
from __future__ import annotations

import functools
import threading
import traceback
from collections import deque
from typing import Dict, List, Optional

from kubedl_tpu.obs.trace import Tracer, tracer_from_env

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
# event -> (span name, the record's field), in the order JAX runs them
PHASES = {TRACE: ("jax.trace", "trace_s"), LOWER: ("jax.lower", "lower_s"),
          COMPILE: ("jax.compile", "executable_s")}
ORDER = {event: i for i, event in enumerate(PHASES)}
CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"

MIN_RECORD_S = 0.1   # a compiled function under this makes no record or span
MAX_RECORDS = 512
MAX_NAMES = 256
OTHER = "other"
TOP_NESTED = 5       # inner functions a jax.trace span names
RECENT = 16          # compiled functions a thread remembers for since()


def fun_of(fun_name: str) -> str:
    """JAX's ``fun_name`` with the ``jit(...)`` of the lowering and
    compile events stripped, so that a function's three phases agree."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def summed(records: List[Dict]) -> Dict:
    """What a step's record says of the functions that compiled inside
    it: the names of those worth a record (of the largest where none
    is: a stage's eager `add`s are compiles too, of milliseconds), the
    three times summed over all, and the cache's worst answer (a miss
    among hits is a miss)."""
    def total(r):
        return r["trace_s"] + r["lower_s"] + r["executable_s"]

    caches = {r["cache"] for r in records}
    named = ([r for r in records if total(r) >= MIN_RECORD_S]
             or [max(records, key=total)])
    return {
        "fun": "+".join(r["fun"] for r in named),
        "trace_s": round(sum(r["trace_s"] for r in records), 6),
        "lower_s": round(sum(r["lower_s"] for r in records), 6),
        "executable_s": round(sum(r["executable_s"] for r in records), 6),
        "cache": next((c for c in ("miss", "hit") if c in caches), "off"),
    }


def _add(table: Dict[str, List], name: str, calls: int, total_s: float,
         own_s: float) -> None:
    row = table.get(name)
    if row is None:
        if len(table) >= MAX_NAMES and name != OTHER:
            return _add(table, OTHER, calls, total_s, own_s)
        row = table[name] = [0, 0.0, 0.0]
    row[0] += calls
    row[1] += total_s
    row[2] += own_s


class _Phase:
    __slots__ = ("event", "fun", "span", "child_s")

    def __init__(self, event: str, fun: str, span) -> None:
        self.event, self.fun, self.span = event, fun, span
        self.child_s = 0.0  # summed seconds of the phases nested in it


class _Function:
    """One function on its way through trace, lowering and compile."""

    __slots__ = ("fun", "ts", "seconds", "spans", "cache", "cache_read_s",
                 "last", "trace_child_s", "nested")

    def __init__(self, fun: str, ts: float) -> None:
        self.fun, self.ts = fun, ts
        self.seconds = {"trace_s": 0.0, "lower_s": 0.0, "executable_s": 0.0}
        self.spans: List[tuple] = []  # (name, JAX's start, duration)
        self.cache, self.cache_read_s = "off", 0.0
        self.last = -1  # ORDER of the newest phase it holds
        self.trace_child_s = 0.0
        self.nested: Dict[str, List] = {}


class _Thread:
    __slots__ = ("stack", "function", "count", "recent")

    def __init__(self) -> None:
        self.stack: List[_Phase] = []
        self.function: Optional[_Function] = None
        self.count = 0
        self.recent: deque = deque(maxlen=RECENT)  # (count, record)


def _listener(method):
    """A fault in here must not fail the compile it watches: it is
    counted, its traceback kept, and the event dropped."""
    @functools.wraps(method)
    def guarded(self, *args, **kwargs):
        try:
            method(self, *args, **kwargs)
        except Exception:  # noqa: BLE001 — the boundary JAX calls across
            self.errors += 1
            self.last_error = traceback.format_exc()
    return guarded


class CompileLog:
    def __init__(self, tracer: Optional[Tracer] = None) -> None:
        self._ring_only = Tracer(service="compiles")
        self.tracer = tracer or self._ring_only
        self.errors = 0
        self.last_error = ""
        self._local = threading.local()
        self._lock = threading.Lock()
        self._records: deque = deque(maxlen=MAX_RECORDS)
        self._counts: Dict[str, List] = {}  # by name, bounded as the table is
        self._table: Dict[str, List] = {}

    def release(self, tracer: Tracer) -> None:
        """A tracer about to be closed stops receiving spans."""
        if self.tracer is tracer:
            self.tracer = self._ring_only

    def _thread(self) -> _Thread:
        th = getattr(self._local, "th", None)
        if th is None:
            th = self._local.th = _Thread()
        return th

    # -- listeners (jax.monitoring calls them on the compiling thread) -----

    @_listener
    def on_scalar(self, event: str, value, **kwargs) -> None:
        if event not in PHASES:
            return
        th = self._thread()
        fun = fun_of(str(kwargs.get("fun_name", "")))
        span = None
        if not th.stack:
            self._begin(th, event, fun, float(value))
            span = self.tracer.span(PHASES[event][0], export=False, fun=fun)
            span.__enter__()
        th.stack.append(_Phase(event, fun, span))

    @_listener
    def on_span(self, event: str, start: float, end: float, **kwargs) -> None:
        if event not in PHASES:
            return
        th = self._thread()
        fun = fun_of(str(kwargs.get("fun_name", "")))
        phase = th.stack[-1] if th.stack else None
        if phase is not None and phase.event == event and phase.fun == fun:
            th.stack.pop()
        else:
            phase = self._unwind(th, event, fun)
            if phase is None:
                return  # it began before install()
        if phase.span is not None:
            phase.span.cancel()
        dur = max(end - start, 0.0)
        fn = th.function
        if fn is None:
            return
        if th.stack:
            # an inner jit's trace; or an eager primitive run while the
            # outer function is traced, whose three phases add to one row
            th.stack[-1].child_s += dur
            _add(fn.nested, fun, int(event == TRACE), dur, dur - phase.child_s)
            return
        name, field = PHASES[event]
        fn.seconds[field] = dur
        fn.spans.append((name, start, dur))
        if event == TRACE:
            fn.trace_child_s = phase.child_s
        if event == COMPILE:
            self._commit(th)

    def _unwind(self, th: _Thread, event: str, fun: str) -> Optional[_Phase]:
        """The open phase an end event belongs to where it is not the
        innermost: whatever is open above it never got its end (JAX sends
        none once the interpreter is exiting) and is dropped."""
        at = next((i for i in range(len(th.stack) - 1, -1, -1)
                   if th.stack[i].event == event and th.stack[i].fun == fun),
                  None)
        if at is None:
            return None
        for ph in th.stack[at + 1:]:
            if ph.span is not None:
                ph.span.cancel()
        phase = th.stack[at]
        del th.stack[at:]
        return phase

    def _compiling(self) -> Optional[_Function]:
        """The function whose backend compile is the one phase open on
        this thread (a compile nested in a trace is an eager primitive's
        and keeps its cache events to itself)."""
        th = self._thread()
        if len(th.stack) == 1 and th.stack[0].event == COMPILE:
            return th.function
        return None

    @_listener
    def on_event(self, event: str, **kwargs) -> None:
        if event not in (CACHE_REQUEST, CACHE_HIT, CACHE_MISS):
            return
        fn = self._compiling()
        if fn is not None:
            fn.cache = "hit" if event == CACHE_HIT else "miss"

    @_listener
    def on_duration(self, event: str, duration_secs: float, **kwargs) -> None:
        if event != CACHE_READ:
            return
        fn = self._compiling()
        if fn is not None:
            fn.cache_read_s = float(duration_secs)

    # -- a function's life ---------------------------------------------------

    def _begin(self, th: _Thread, event: str, fun: str, ts: float) -> None:
        """A top-level phase opens: the next phase of the function this
        thread is compiling, or the first of a new one (a trace always;
        a lowering or a compile that no earlier phase of the same
        function preceded, as after `.lower()` or under new shardings)."""
        fn = th.function
        if fn is None or fn.fun != fun or ORDER[event] <= fn.last:
            self._commit(th)
            fn = th.function = _Function(fun, ts)
            th.count += 1
            with self._lock:
                _add(self._counts, fun, 1, 0.0, 0.0)
        fn.last = ORDER[event]

    def _commit(self, th: _Thread) -> None:
        fn, th.function = th.function, None
        if fn is None:
            return
        rec = {"fun": fn.fun, **{k: round(v, 6) for k, v in fn.seconds.items()},
               "cache": fn.cache, "cache_read_s": round(fn.cache_read_s, 6),
               "ts": fn.ts}
        th.recent.append((th.count, rec))
        total = sum(fn.seconds.values())
        kept = total >= MIN_RECORD_S
        with self._lock:
            seconds = fn.seconds["trace_s"] if kept else total
            _add(self._table, fn.fun, 1, seconds, seconds - fn.trace_child_s)
            for name, (calls, total_s, own_s) in fn.nested.items():
                _add(self._table, name, calls, total_s, own_s)
            if kept:
                self._records.append(rec)
        if not kept:
            return
        top = sorted(fn.nested.items(), key=lambda kv: -kv[1][2])[:TOP_NESTED]
        attrs = {
            "jax.trace": {"nested": [
                {"fun": name, "calls": calls, "total_s": round(total_s, 6),
                 "own_s": round(own_s, 6)} for name, (calls, total_s, own_s) in top]},
            "jax.lower": {},
            "jax.compile": {"trace_s": rec["trace_s"], "lower_s": rec["lower_s"],
                            "cache": fn.cache, "cache_read_s": rec["cache_read_s"]},
        }
        for name, start, dur in fn.spans:
            self.tracer.record(name, duration_s=dur, end_ts=start + dur,
                               fun=fn.fun, **attrs[name])

    # -- readers -------------------------------------------------------------

    def _settle(self) -> _Thread:
        """The calling thread's function, if it is between phases (traced
        or lowered and not compiled: `.lower()`, `eval_shape`), is done
        as far as a reader can tell."""
        th = self._thread()
        if not th.stack:
            self._commit(th)
        return th

    def records(self, fun: Optional[str] = None) -> List[Dict]:
        """The kept records, oldest first; of one function where named."""
        self._settle()
        with self._lock:
            return [dict(r) for r in self._records
                    if fun is None or r["fun"] == fun]

    def count(self, fun: Optional[str] = None, thread: bool = False) -> int:
        """Functions compiled on their own so far (top-level traces, and
        lowerings or compiles that no trace of theirs preceded), small
        ones included: of one name, of the calling thread, or all."""
        if thread:
            if fun is not None:
                raise ValueError("a thread's count is of every function")
            return self._thread().count
        with self._lock:
            if fun is not None:
                return self._counts.get(fun, [0])[0]
            return sum(row[0] for row in self._counts.values())

    def since(self, count: int) -> List[Dict]:
        """What the calling thread compiled after its `count(thread=True)`
        read `count` (its newest `RECENT`), small functions too."""
        th = self._settle()
        return [dict(rec) for n, rec in th.recent if n > count]

    def nested(self) -> Dict[str, Dict]:
        """The table by function name: calls, summed and own seconds."""
        self._settle()
        with self._lock:
            return {name: {"calls": calls, "total_s": total_s, "own_s": own_s}
                    for name, (calls, total_s, own_s) in self._table.items()}


_LOG: Optional[CompileLog] = None
_INSTALL = threading.Lock()


def install(tracer: Optional[Tracer] = None) -> CompileLog:
    """The process's log, registered with `jax.monitoring` the first time
    (one scalar, one time-span, one duration and one plain listener); a
    tracer, where given, is where its spans go from now on. A process
    that hands none in (the benchmark's runner, a probe) gets the
    injected trace env's: a file under `KUBEDL_TRACE_DIR` where that is
    set, a ring alone where it is not."""
    global _LOG
    with _INSTALL:
        if _LOG is None:
            from jax import monitoring

            log = CompileLog(tracer or tracer_from_env())
            monitoring.register_scalar_listener(log.on_scalar)
            monitoring.register_event_time_span_listener(log.on_span)
            monitoring.register_event_duration_secs_listener(log.on_duration)
            monitoring.register_event_listener(log.on_event)
            _LOG = log
        if tracer is not None:
            _LOG.tracer = tracer
    return _LOG
