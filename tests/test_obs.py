"""Flight recorder (kubedl_tpu/obs/, docs/observability.md): span
nesting/bounds, JSONL + Chrome-trace export round-trip, goodput math on a
synthetic timeline, straggler thresholds, the profiler window's
idempotent shutdown, and an e2e on the local executor asserting a job's
spans cover admission -> steps -> completion under ONE trace id."""
import json
import os
import sys
import time
import urllib.request

import pytest

from kubedl_tpu.obs import (
    GoodputReporter,
    StepAggregator,
    StepStream,
    Tracer,
    chrome_trace,
    goodput,
    job_trace_dir,
    load_spans,
    load_step_records,
    trace_id_for,
    tracer_from_env,
)
from kubedl_tpu.obs.goodput import BUCKETS, OTHER, classify


# ---------------------------------------------------------------------------
# span API
# ---------------------------------------------------------------------------


def test_span_nesting_attrs_and_trace_inheritance():
    t = Tracer(service="svc", trace_id="tid0")
    with t.span("outer", job="j", namespace="ns", a=1) as outer:
        with t.span("inner") as inner:
            assert inner.parent_id == outer.span_id
            assert inner.trace_id == outer.trace_id == "tid0"
            # routing attrs inherit so nested spans land in the job file
            assert inner.attrs["job"] == "j"
            inner.set(b=2)
    spans = t.spans()
    assert [s["name"] for s in spans] == ["inner", "outer"]  # finish order
    assert spans[0]["attrs"]["b"] == 2
    assert spans[1]["attrs"]["a"] == 1
    assert all(s["service"] == "svc" for s in spans)
    # explicit trace id beats the tracer default
    rec = t.record("r", duration_s=0.1, trace_id="other")
    assert rec["trace_id"] == "other"


def test_span_exception_stamps_error_and_still_closes():
    t = Tracer()
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("nope")
    (span,) = t.spans()
    assert span["name"] == "boom"
    assert "ValueError" in span["attrs"]["error"]


def test_ring_and_export_bounds(tmp_path):
    path = str(tmp_path / "x.jsonl")
    t = Tracer(ring_size=4, max_export_spans=3, export_path=path)
    for i in range(10):
        t.record("s", duration_s=0.01, i=i)
    assert len(t.spans()) == 4  # ring keeps rotating
    assert [s["attrs"]["i"] for s in t.spans()] == [6, 7, 8, 9]
    assert t.dropped == 7
    with open(path) as f:
        assert len(f.readlines()) == 3  # file footprint stays bounded


def test_export_cap_is_per_job_file(tmp_path):
    """A long-lived operator's reconcile churn on one job must never
    silence a NEW job's queue-wait evidence: the export budget binds per
    file, not fleet-wide."""
    t = Tracer(service="operator", export_root=str(tmp_path),
               max_export_spans=2)
    for i in range(5):
        t.record("operator.reconcile", duration_s=0.001,
                 job="old", namespace="ns")
    t.record("gang.queue_wait", duration_s=0.5, job="new", namespace="ns")
    old = load_spans(job_trace_dir(str(tmp_path), "ns", "old"))
    new = load_spans(job_trace_dir(str(tmp_path), "ns", "new"))
    assert len(old) == 2 and t.dropped == 3
    assert [s["name"] for s in new] == ["gang.queue_wait"]


def test_goodput_window_ignores_uncategorized_tail():
    """Post-completion reconcile spans keep landing in a Succeeded job's
    dir until its TTL — they must not stretch the wall window, or the
    committed goodput ratio would decay depending on WHEN you scrape."""
    done = [
        _mk("train.step", 0.0, 1.0, step=1),
        _mk("ckpt.save", 1.0, 0.5),
    ]
    gp0 = goodput(done)
    gp1 = goodput(done + [_mk("operator.reconcile", 100.0, 0.01)])
    assert gp1["wall_s"] == gp0["wall_s"] == pytest.approx(1.5)
    assert gp1["ratio"] == gp0["ratio"]


def test_step_aggregator_prunes_stale_jobs():
    agg = StepAggregator(k=2.0, min_pods=2, max_age_s=0.05)
    agg.observe({"job": "dead", "namespace": "ns", "pod": "p", "step": 1,
                 "step_s": 0.1, "t": time.time() - 1.0})
    agg.observe({"job": "live", "namespace": "ns", "pod": "p", "step": 1,
                 "step_s": 0.1, "t": time.time()})
    jobs = agg.snapshot()["jobs"]
    assert "ns/live" in jobs and "ns/dead" not in jobs


def test_goodput_reporter_bounds_snapshot_to_recent_jobs(tmp_path):
    t = Tracer(service="op", export_root=str(tmp_path))
    for i, name in enumerate(["a", "b", "c"]):
        t.record("train.step", duration_s=0.1, job=name, namespace="ns")
        os.utime(job_trace_dir(str(tmp_path), "ns", name), (i, i))
    rep = GoodputReporter(str(tmp_path), max_jobs=2)
    jobs = rep.snapshot()["jobs"]
    assert set(jobs) == {"ns/b", "ns/c"}  # two most recently modified


def test_record_backdates_ts():
    t = Tracer()
    end = time.time()
    rec = t.record("wait", duration_s=2.5, end_ts=end)
    assert rec["ts"] == pytest.approx(end - 2.5)
    assert rec["dur"] == 2.5


def test_trace_id_deterministic_and_job_dir():
    assert trace_id_for("ns", "job") == trace_id_for("ns", "job")
    assert trace_id_for("ns", "job") != trace_id_for("ns", "job2")
    assert len(trace_id_for("a", "b")) == 32
    assert job_trace_dir("/r", "ns", "j") == "/r/ns_j"


def test_tracer_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("KUBEDL_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("KUBEDL_TRACE_ID", "abc123")
    monkeypatch.setenv("POD_NAME", "pod-0")
    t = tracer_from_env()
    assert t.exporting
    t.record("x", duration_s=0.1)
    spans = load_spans(str(tmp_path))
    assert spans and spans[0]["trace_id"] == "abc123"
    assert spans[0]["service"] == "pod-0"
    # without the env: ring-only, no export
    monkeypatch.delenv("KUBEDL_TRACE_DIR")
    t2 = tracer_from_env()
    assert not t2.exporting


def test_load_spans_skips_step_streams_and_garbage(tmp_path):
    t = Tracer(export_path=str(tmp_path / "a.jsonl"))
    t.record("real", duration_s=0.1)
    with open(tmp_path / "pod.steps.jsonl", "w") as f:
        f.write(json.dumps({"step": 1, "step_s": 0.1}) + "\n")
    with open(tmp_path / "a.jsonl", "a") as f:
        f.write("{half-written")  # torn tail line
    spans = load_spans(str(tmp_path))
    assert [s["name"] for s in spans] == ["real"]


# ---------------------------------------------------------------------------
# chrome trace export
# ---------------------------------------------------------------------------


def _assert_chrome_schema(ct):
    """The schema contract Perfetto/chrome://tracing relies on."""
    assert isinstance(ct, dict) and isinstance(ct["traceEvents"], list)
    for e in ct["traceEvents"]:
        assert e["ph"] in ("X", "M")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert isinstance(e["name"], str)
        if e["ph"] == "X":
            assert isinstance(e["ts"], float) and e["ts"] >= 0
            assert isinstance(e["dur"], float) and e["dur"] >= 0
        else:
            assert e["name"] in ("process_name", "thread_name")
            assert "name" in e["args"]


def test_chrome_trace_roundtrip(tmp_path):
    t = Tracer(service="op", trace_id="t1",
               export_path=str(tmp_path / "op.jsonl"))
    t.record("gang.queue_wait", duration_s=0.5, job="j", namespace="ns")
    with t.span("operator.reconcile", trace_id="t1", job="j", namespace="ns"):
        pass
    spans = load_spans(str(tmp_path))
    ct = chrome_trace(spans)
    ct = json.loads(json.dumps(ct))  # must survive JSON round-trip
    _assert_chrome_schema(ct)
    xs = [e for e in ct["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"gang.queue_wait", "operator.reconcile"}
    # all spans of one job share a pid; µs timestamps preserve order
    assert len({e["pid"] for e in xs}) == 1
    wait = next(e for e in xs if e["name"] == "gang.queue_wait")
    assert wait["dur"] == pytest.approx(0.5e6)


# ---------------------------------------------------------------------------
# goodput accounting
# ---------------------------------------------------------------------------


def _mk(name, ts, dur, **attrs):
    return {"name": name, "trace_id": "t", "span_id": "s", "parent_id": "",
            "service": "x", "ts": ts, "dur": dur, "attrs": attrs}


def test_goodput_synthetic_timeline():
    """queue -> compile -> steps -> reshard -> steps, gap-free."""
    spans = [
        _mk("gang.queue_wait", 0.0, 2.0, cause="initial"),
        _mk("trainer.init", 2.0, 0.5),
        _mk("train.compile", 2.5, 1.5, step=1),
        _mk("train.step", 4.0, 1.0, step=2),
        _mk("train.step", 5.0, 1.0, step=3),
        _mk("reshard.live", 6.0, 0.5, outcome="ok"),
        _mk("train.step", 6.5, 1.0, step=4),
        _mk("train.step", 7.5, 1.0, step=5),
        _mk("ckpt.save", 8.5, 0.5, final=True),
    ]
    gp = goodput(spans)
    b = gp["buckets"]
    assert gp["wall_s"] == pytest.approx(9.0)
    assert b["queue_wait"] == pytest.approx(2.0)
    assert b["init_compile"] == pytest.approx(2.0)  # init + compile
    assert b["steps"] == pytest.approx(4.0)
    assert b["reshard"] == pytest.approx(0.5)
    assert b["checkpoint"] == pytest.approx(0.5)
    assert b["eviction"] == 0.0 and b[OTHER] == pytest.approx(0.0)
    assert gp["ratio"] == pytest.approx(4.0 / 9.0)
    # acceptance: the breakdown partitions wall time (well inside 1%)
    assert abs(sum(b.values()) - gp["wall_s"]) <= 0.01 * gp["wall_s"]


def test_goodput_overlap_precedence_no_double_count():
    # an async checkpoint save overlapping a step: the overlap books as
    # checkpoint, never twice
    spans = [
        _mk("train.step", 0.0, 2.0, step=1),
        _mk("ckpt.save", 1.0, 2.0),
    ]
    gp = goodput(spans)
    b = gp["buckets"]
    assert gp["wall_s"] == pytest.approx(3.0)
    assert b["checkpoint"] == pytest.approx(2.0)
    assert b["steps"] == pytest.approx(1.0)
    assert abs(sum(b.values()) - gp["wall_s"]) < 1e-9


def test_goodput_uncovered_time_is_other_and_requeue_is_eviction():
    spans = [
        _mk("train.step", 0.0, 1.0, step=1),
        # 2s hole (pod dead after preemption), then the re-admission wait
        _mk("gang.queue_wait", 3.0, 1.5, cause="requeue", preemptions=1),
        _mk("train.step", 4.5, 1.0, step=2),
    ]
    gp = goodput(spans)
    b = gp["buckets"]
    assert b["eviction"] == pytest.approx(1.5)
    assert b[OTHER] == pytest.approx(2.0)
    assert b["steps"] == pytest.approx(2.0)
    assert abs(sum(b.values()) - gp["wall_s"]) < 1e-9


def test_goodput_empty_and_classify_table():
    gp = goodput([])
    assert gp["wall_s"] == 0.0 and gp["ratio"] == 0.0
    assert set(gp["buckets"]) == set(BUCKETS) | {OTHER}
    assert classify(_mk("gang.queue_wait", 0, 1)) == "queue_wait"
    assert classify(_mk("gang.queue_wait", 0, 1, cause="requeue")) == "eviction"
    for n in ("reshard.live", "reshard.staged", "reshard.fallback",
              "sched.reshard"):
        assert classify(_mk(n, 0, 1)) == "reshard"
    assert classify(_mk("ckpt.restore", 0, 1)) == "checkpoint"
    assert classify(_mk("trainer.init", 0, 1)) == "init_compile"
    assert classify(_mk("pipeline.step", 0, 1)) == "steps"
    assert classify(_mk("operator.reconcile", 0, 1)) is None


# ---------------------------------------------------------------------------
# step stream + straggler detection
# ---------------------------------------------------------------------------


def test_step_stream_jsonl_heartbeat_and_bounds(tmp_path):
    jsonl = str(tmp_path / "p.steps.jsonl")
    hb = str(tmp_path / "heartbeat.json")
    st = StepStream(jsonl_path=jsonl, heartbeat_path=hb, job="j",
                    namespace="ns", pod="p", max_records=3)
    for i in range(5):
        st.record(i + 1, 0.1 * (i + 1), data_s=0.01, loss=2.0,
                  compile=i == 0)
    recs = load_step_records(jsonl)
    assert len(recs) == 3 and st.dropped == 2  # bounded stream
    assert recs[0]["compile"] is True and recs[0]["compiles"] == 1
    # heartbeat always carries the LATEST record, past the jsonl cap
    with open(hb) as f:
        last = json.load(f)
    assert last["step"] == 5 and last["step_s"] == pytest.approx(0.5)
    assert last["job"] == "j" and last["pod"] == "p"
    st.close()


def test_step_stream_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("KUBEDL_TRACE_DIR", str(tmp_path / "t"))
    monkeypatch.setenv("KUBEDL_CONTROL_DIR", str(tmp_path))
    monkeypatch.setenv("POD_NAME", "w-0")
    monkeypatch.setenv("POD_NAMESPACE", "ns")
    monkeypatch.setenv("KUBEDL_LABEL_JOB_NAME", "jobx")
    st = StepStream.from_env()
    st.record(1, 0.2)
    assert os.path.exists(tmp_path / "t" / "w-0.steps.jsonl")
    with open(tmp_path / "heartbeat.json") as f:
        rec = json.load(f)
    assert rec["job"] == "jobx" and rec["namespace"] == "ns"
    monkeypatch.delenv("KUBEDL_TRACE_DIR")
    monkeypatch.delenv("KUBEDL_CONTROL_DIR")
    assert StepStream.from_env() is None


@pytest.mark.parametrize(
    "k,times,expected",
    [
        # pod c at 5x median -> straggler at k=2 and k=4
        (2.0, {"a": 0.1, "b": 0.1, "c": 0.5}, ["c"]),
        (4.0, {"a": 0.1, "b": 0.1, "c": 0.5}, ["c"]),
        # at k=6 a 5x-median pod is within threshold
        (6.0, {"a": 0.1, "b": 0.1, "c": 0.5}, []),
        # uniform pods: nobody straggles
        (2.0, {"a": 0.1, "b": 0.1, "c": 0.1}, []),
        # exactly k x median is NOT a straggler (strict >)
        (2.0, {"a": 0.1, "b": 0.1, "c": 0.2}, []),
        # two stragglers, sorted
        (2.0, {"a": 0.1, "b": 0.1, "d": 0.9, "c": 0.5, "e": 0.1}, ["c", "d"]),
    ],
)
def test_straggler_threshold_matrix(k, times, expected):
    agg = StepAggregator(k=k, min_pods=2)
    for pod, s in times.items():
        agg.observe({"job": "j", "namespace": "ns", "pod": pod, "step": 7,
                     "step_s": s, "t": time.time(), "compiles": 1})
    rec = agg.snapshot()["jobs"]["ns/j"]
    assert rec["stragglers"] == expected
    assert rec["compile_events"] == len(times)


def test_straggler_needs_min_pods_and_keeps_latest():
    now = time.time()
    agg = StepAggregator(k=2.0, min_pods=3)
    agg.observe({"job": "j", "namespace": "ns", "pod": "a", "step": 1,
                 "step_s": 0.1, "t": now})
    agg.observe({"job": "j", "namespace": "ns", "pod": "b", "step": 1,
                 "step_s": 9.9, "t": now})
    # only 2 pods < min_pods: no peer baseline, nobody flagged
    assert agg.snapshot()["jobs"]["ns/j"]["stragglers"] == []
    # a stale heartbeat must not regress a newer observation
    agg.observe({"job": "j", "namespace": "ns", "pod": "b", "step": 5,
                 "step_s": 0.1, "t": now + 2.0})
    agg.observe({"job": "j", "namespace": "ns", "pod": "b", "step": 1,
                 "step_s": 9.9, "t": now + 1.5})
    assert agg.snapshot()["jobs"]["ns/j"]["pods"]["b"]["step"] == 5


# ---------------------------------------------------------------------------
# profiler window (satellite: idempotent stop on SIGTERM mid-window)
# ---------------------------------------------------------------------------


class _FakeProfiler:
    def __init__(self, fail_stop=False):
        self.starts = 0
        self.stops = 0
        self.fail_stop = fail_stop

    def start_trace(self, d):
        self.starts += 1

    def stop_trace(self):
        self.stops += 1
        if self.fail_stop:
            raise RuntimeError("profiler already torn down")


def test_profile_window_covers_post_compile_steps_and_stop_idempotent():
    from kubedl_tpu.train.profile_window import ProfileWindow

    fp = _FakeProfiler()
    w = ProfileWindow("/tmp/prof", start_step=10, n_steps=2, profiler=fp)
    w.maybe_start(10)          # the first step (compile or cache read): not traced
    assert fp.starts == 0
    w.maybe_start(11)
    assert fp.starts == 1 and w.tracing
    assert not w.should_stop(11)
    assert w.should_stop(12)
    w.stop()
    # preemption path + finally backstop both re-stop: must be a no-op
    w.stop()
    w.stop()
    assert fp.stops == 1 and not w.tracing


def test_profile_window_stop_swallows_profiler_errors():
    from kubedl_tpu.train.profile_window import ProfileWindow

    fp = _FakeProfiler(fail_stop=True)
    w = ProfileWindow("/tmp/prof", start_step=0, n_steps=1, profiler=fp)
    w.maybe_start(1)
    w.stop()  # must not raise — SIGTERM exit path depends on it
    assert not w.tracing
    w.stop()
    assert fp.stops == 1


def test_pipeline_trainer_has_profiler_flags():
    """The MPMD stage trainer previously had NO profiler hook at all."""
    from kubedl_tpu.train.pipeline_trainer import parse_args

    args = parse_args(["--profile-dir", "/tmp/p", "--profile-steps", "3"])
    assert args.profile_dir == "/tmp/p" and args.profile_steps == 3


def test_pipeline_stage_says_which_steps_compiled_and_where_init_went(
        tmp_path, monkeypatch, capsys):
    """The MPMD stage trainer, one stage in this process, under the trace
    env: the step in which the stage's programs compiled is train.compile
    with what compiled (asked of the compile log, not of the step's
    index), the rest pipeline.step, and trainer.init has its children."""
    trace_dir = str(tmp_path / "trace")
    monkeypatch.setenv("KUBEDL_TRACE_DIR", trace_dir)
    monkeypatch.setenv("KUBEDL_TRACE_ID", "1" * 32)
    monkeypatch.setenv("POD_NAME", "pp-stage-0")
    monkeypatch.setenv("KUBEDL_PP_STAGES", "1")
    monkeypatch.setenv("KUBEDL_PP_STAGE", "0")
    monkeypatch.setenv("KUBEDL_PP_MICROBATCHES", "2")
    monkeypatch.setenv("KUBEDL_PP_BOUNDARY_DIR", str(tmp_path / "pp"))
    monkeypatch.delenv("KUBEDL_CONTROL_DIR", raising=False)
    from kubedl_tpu.train import pipeline_trainer

    assert pipeline_trainer.main([
        "--model", "tiny", "--batch", "8", "--seq-len", "17", "--steps", "3"]) == 0
    spans = load_spans(trace_dir)
    steps = [s for s in spans if s["name"] in ("train.compile", "pipeline.step")]
    assert [(s["name"], s["attrs"]["step"]) for s in steps] == [
        ("train.compile", 1), ("pipeline.step", 2), ("pipeline.step", 3)]
    first = steps[0]["attrs"]
    assert "loss_body" in first["fun"].split("+") and first["cache"] in ("hit", "miss", "off")
    assert first["trace_s"] > 0 and first["lower_s"] > 0 and first["executable_s"] > 0
    assert first["trace_s"] + first["lower_s"] + first["executable_s"] <= steps[0]["dur"]
    assert "fun" not in steps[1]["attrs"]
    # the stage's programs, each with its three spans inside that step
    for name in ("jax.trace", "jax.lower", "jax.compile"):
        (mine,) = [s for s in spans if s["name"] == name
                   and s["attrs"].get("fun") == "loss_body"]
        assert steps[0]["ts"] <= mine["ts"]
        assert mine["ts"] + mine["dur"] <= steps[0]["ts"] + steps[0]["dur"] + 1e-3
    init = next(s for s in spans if s["name"] == "trainer.init")
    children = [s for s in spans if s["name"].startswith("init.")]
    assert [s["name"] for s in children] == ["init.imports", "init.backend", "init.state"]
    for s in children:
        assert init["ts"] - 1e-2 <= s["ts"]
        assert s["ts"] + s["dur"] <= init["ts"] + init["dur"] + 1e-2
    recs = load_step_records(os.path.join(trace_dir, "pp-stage-0.steps.jsonl"))
    assert [r["compile"] for r in recs] == [True, False, False]
    gp = goodput(spans)
    assert gp["buckets"]["init_compile"] > 0 and gp["buckets"]["steps"] > 0


# ---------------------------------------------------------------------------
# metrics surface (shared escaping + new families)
# ---------------------------------------------------------------------------


def test_prom_escaping_shared_helper():
    from kubedl_tpu.metrics.prom import (
        escape_label_value, format_labels, sample)

    assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    assert format_labels({"job": 'x"y'}) == '{job="x\\"y"}'
    assert sample("m", 1, {"a": "b"}) == 'm{a="b"} 1'
    # the runtime renderer formats through the same discipline
    from kubedl_tpu.metrics import runtime_metrics as rmmod

    assert rmmod._label is escape_label_value


def test_runtime_metrics_render_goodput_and_step_series():
    from kubedl_tpu.metrics.runtime_metrics import RuntimeMetrics

    rm = RuntimeMetrics()
    rm.register_goodput(lambda: {"jobs": {'ns/j"1': {
        "ratio": 0.75, "wall_s": 10.0,
        "buckets": {"steps": 7.5, "queue_wait": 2.5},
    }}})
    rm.register_steps(lambda: {"jobs": {"ns/j": {
        "pods": {"p0": {"step_s": 0.25}, "p1": {"step_s": 1.0}},
        "median_step_s": 0.625, "stragglers": ["p1"], "compile_events": 2,
    }}})
    text = rm.render()
    assert 'kubedl_goodput_ratio{job="ns/j\\"1"} 0.7500' in text
    assert 'kubedl_goodput_seconds{job="ns/j\\"1",bucket="steps"} 7.500000' in text
    assert 'kubedl_step_time_seconds{job="ns/j",pod="p1"} 1.000000' in text
    assert 'kubedl_straggler_pods{job="ns/j"} 1' in text
    assert 'kubedl_compile_events_total{job="ns/j"} 2' in text
    dv = rm.debug_vars()
    assert dv["goodput"]["jobs"] and dv["steps"]["jobs"]


def test_debug_vars_has_every_newer_family():
    """Every register_* family must be on the debug surface (a family
    silently missing from /debug/vars is invisible to `kubedl-tpu top`).

    The family list is DERIVED from the RuntimeMetrics AST by the
    debug-vars-family analyzer pass (docs/static_analysis.md) — the
    hand-maintained assert list this test used to carry could go stale
    the moment a new register_* landed; the machine-derived one cannot."""
    import os

    from kubedl_tpu.analysis.passes import runtime_metric_families
    from kubedl_tpu.operator import Operator, OperatorConfig

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    families = runtime_metric_families(root=repo)
    assert {"slice_pool", "capacity", "pipeline", "steps", "goodput",
            "transport", "rl"} <= set(families)
    op = Operator(OperatorConfig(
        tpu_slices=["v5e-8"], scheduler_policy="priority",
        run_executor=True))
    try:
        dv = op.runtime_metrics.debug_vars()
        for family in families:
            if family == "queue":
                # per-controller queue depth renders under "controllers"
                # (per registration; the analyzer pass pins the surface)
                continue
            assert family in dv, f"register_{family} missing from /debug/vars"
        assert "reshards_total" in dv["capacity"]
        assert "reconnects_total" in dv["transport"]
        assert "jobs" in dv["rl"]
    finally:
        op.stop()


# ---------------------------------------------------------------------------
# chaos paths: preemption + reshard downtime attribution
# ---------------------------------------------------------------------------


def test_preemption_requeue_wait_books_as_eviction(tmp_path):
    """Chaos path: evict a granted gang, re-grant it — the admitter's
    retroactive queue_wait span carries cause=requeue and the goodput
    accountant attributes that downtime to the eviction bucket."""
    from kubedl_tpu.core.store import ObjectStore
    from kubedl_tpu.gang.slice_admitter import TPUSliceAdmitter
    from test_sched_drain import _job

    store = ObjectStore()
    adm = TPUSliceAdmitter.with_pool(store, ["v5e-8"])
    tracer = Tracer(service="operator", export_root=str(tmp_path))
    adm.tracer = tracer
    job = _job("victim", chips=8)
    adm.create_gang(job, job.spec.replica_specs)
    d = job_trace_dir(str(tmp_path), "default", "victim")
    spans = load_spans(d)
    assert [s["name"] for s in spans] == ["gang.queue_wait"]
    assert spans[0]["attrs"]["cause"] == "initial"
    assert spans[0]["trace_id"] == trace_id_for("default", "victim")

    adm.evict_gang("default", "victim", hold_seconds=0.05)
    time.sleep(0.12)  # downtime the requeue span must cover
    adm.kick()
    spans = load_spans(d)
    assert [s["name"] for s in spans] == ["gang.queue_wait"] * 2
    requeue = spans[-1]
    assert requeue["attrs"]["cause"] == "requeue"
    assert requeue["attrs"]["preemptions"] == 1
    assert requeue["dur"] >= 0.1
    gp = goodput(spans)
    assert gp["buckets"]["eviction"] == pytest.approx(requeue["dur"], abs=1e-5)
    assert abs(sum(gp["buckets"].values()) - gp["wall_s"]) <= 1e-4


def test_capacity_reshard_ladder_records_sched_span(tmp_path):
    """A RESIZE that never gets replies fails closed at the deadline —
    and the ladder rung lands as a sched.reshard span with the failure
    outcome, booked to the reshard goodput bucket."""
    from kubedl_tpu.core.store import ObjectStore
    from kubedl_tpu.gang.slice_admitter import TPUSliceAdmitter
    from kubedl_tpu.sched.capacity import CapacityScheduler, CapacityConfig
    from test_sched_drain import _job, _pod

    store = ObjectStore()
    adm = TPUSliceAdmitter.with_pool(store, ["v5e-8", "v5e-4"])
    sched = CapacityScheduler(adm, store, CapacityConfig(
        policy="priority", reshard_reply_timeout=0.05, quiesce_timeout=0.0))
    tracer = Tracer(service="operator", export_root=str(tmp_path))
    sched.tracer = tracer
    replies = []
    sched.attach_control(lambda ns, pod, msg: (
        replies.append((pod, msg)) or str(tmp_path / f"reply-{pod}.json")))

    job = _job("elastic", chips=8)
    job.spec.elastic = type("E", (), {"live_reshard": True,
                                      "quiesce_timeout_s": 0.0})()
    sched_pol = job.spec.run_policy.scheduling_policy
    sched_pol.tpu_slice = "v5e-8"
    sched_pol.tpu_slice_fallbacks = ["v5e-4"]
    adm.create_gang(job, job.spec.replica_specs)
    _pod(store, job, "elastic-w0", chips=8)
    g = next(s for s in adm.gang_snapshots() if s.key == "default/elastic")
    assert g.slice_names  # granted
    assert sched._post_resize(g, "shrink")
    assert replies  # RESIZE reached the pod
    time.sleep(0.1)
    sched._reshard_pass()  # deadline passed, no replies -> failed
    spans = load_spans(job_trace_dir(str(tmp_path), "default", "elastic"))
    ladder = [s for s in spans if s["name"] == "sched.reshard"]
    assert len(ladder) == 1
    assert ladder[0]["attrs"]["outcome"] == "failed"
    assert ladder[0]["attrs"]["direction"] == "shrink"
    assert ladder[0]["dur"] >= 0.05
    assert classify(ladder[0]) == "reshard"


# ---------------------------------------------------------------------------
# e2e: local executor, one trace id from admission to completion
# ---------------------------------------------------------------------------

# a mini-trainer exercising the injected flight-recorder env end to end:
# spans + step stream + heartbeat, with worker index 1 as the artificial
# straggler (10x step time in its telemetry)
_E2E_SCRIPT = r"""
import os, time
from kubedl_tpu.obs import StepStream, tracer_from_env

tr = tracer_from_env()
st = StepStream.from_env()
assert tr.exporting and st is not None, "trace env not injected"
slow = os.environ.get("POD_NAME", "").endswith("-1")
tr.record("trainer.init", duration_s=0.01, step=0)
tr.record("train.compile", duration_s=0.03, step=1, loss=3.0)
st.record(1, 0.03, data_s=0.001, loss=3.0, compile=True)
for i in range(2, 5):
    step_s = 0.5 if slow else 0.05
    time.sleep(0.02)
    tr.record("train.step", duration_s=step_s, step=i, loss=2.0)
    st.record(i, step_s, data_s=0.001, loss=2.0)
tr.record("ckpt.save", duration_s=0.01, step=4, final=True)
tr.record("trainer.done", step=4)
st.close(); tr.close()
"""


@pytest.fixture()
def obs_e2e_op():
    from kubedl_tpu.operator import Operator, OperatorConfig
    from fake_workload import TestJobController

    op = Operator(OperatorConfig(
        enable_gang_scheduling=True, tpu_slices=["v5e-8"]))
    op.register(TestJobController())
    op.start()
    yield op
    op.stop()


def _e2e_manifest(name, workers=2):
    container = {
        "name": "test-container",
        "image": "none",
        "command": [sys.executable, "-c", _E2E_SCRIPT],
        "resources": {"limits": {"google.com/tpu": 4}},
    }
    return {
        "kind": "TestJob",
        "metadata": {"name": name},
        "spec": {"replicaSpecs": {"Worker": {
            "replicas": workers,
            "restartPolicy": "Never",
            "template": {"spec": {"containers": [container]}},
        }}},
    }


def test_e2e_flight_recorder_single_trace_id(obs_e2e_op, tmp_path, capsys):
    op = obs_e2e_op
    job = op.apply(_e2e_manifest("rec-job"))
    assert op.wait_for_condition(job, "Succeeded", timeout=30)

    d = job_trace_dir(op.trace_root, "default", "rec-job")
    spans = load_spans(d)
    names = {s["name"] for s in spans}
    # the timeline covers queue wait -> admission -> compile -> steps ->
    # completion, across BOTH planes
    assert {"gang.queue_wait", "operator.reconcile", "trainer.init",
            "train.compile", "train.step", "trainer.done"} <= names
    # ... under ONE gang-level trace id
    tids = {s["trace_id"] for s in spans if s["trace_id"]}
    assert tids == {trace_id_for("default", "rec-job")}
    # both worker pods reported their own span files
    services = {s["service"] for s in spans if s["name"] == "train.step"}
    assert len(services) == 2

    # goodput from the SAME spans: productive, and the breakdown
    # partitions wall time within 1%
    gp = op.goodput.job("default", "rec-job")
    assert gp["ratio"] > 0
    assert gp["buckets"]["steps"] > 0
    assert gp["buckets"]["queue_wait"] > 0  # admission wait was recorded
    assert abs(sum(gp["buckets"].values()) - gp["wall_s"]) \
        <= 0.01 * gp["wall_s"]

    # exposition: goodput + step/straggler series render
    text = op.runtime_metrics.render()
    assert 'kubedl_goodput_ratio{job="default/rec-job"}' in text
    assert "kubedl_step_time_seconds" in text
    snap = op.step_aggregator.snapshot()
    rec = snap["jobs"]["default/rec-job"]
    assert len(rec["pods"]) == 2
    # the artificially-delayed pod (worker index 1) is flagged
    assert rec["stragglers"] == ["rec-job-worker-1"]
    assert "kubedl_straggler_pods{job=\"default/rec-job\"} 1" in text

    # CLI: timeline + goodput table straight off the trace dir, and
    # Chrome-trace export that passes the schema check
    from kubedl_tpu import cli

    out_json = str(tmp_path / "chrome.json")
    rc = cli.main(["trace", "rec-job", "--dir", d,
                   "--chrome-trace", out_json])
    assert rc == 0
    printed = capsys.readouterr().out
    assert "gang.queue_wait" in printed and "train.step" in printed
    assert "goodput:" in printed and "queue_wait" in printed
    with open(out_json) as f:
        _assert_chrome_schema(json.load(f))


def test_e2e_trace_endpoint_and_top(obs_e2e_op, capsys):
    from kubedl_tpu.server import OperatorHTTPServer
    from kubedl_tpu import cli

    op = obs_e2e_op
    job = op.apply(_e2e_manifest("srv-job", workers=1))
    assert op.wait_for_condition(job, "Succeeded", timeout=30)
    server = OperatorHTTPServer(op, port=0)
    port = server.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/trace/default/srv-job") as r:
            body = json.loads(r.read())
        assert body["trace_id"] == trace_id_for("default", "srv-job")
        assert {s["name"] for s in body["spans"]} >= {
            "gang.queue_wait", "train.step", "trainer.done"}
        assert body["goodput"]["ratio"] > 0
        # unknown job -> 404, not an empty 200
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/trace/default/nope")
        assert ei.value.code == 404
        # the CLI renders the server-side trace and top shows GOODPUT
        rc = cli.main(["trace", "srv-job",
                       "--server", f"http://127.0.0.1:{port}"])
        assert rc == 0
        assert "train.step" in capsys.readouterr().out
        rc = cli.main(["top", "--server", f"http://127.0.0.1:{port}"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "GOODPUT" in out and "default/srv-job" in out
        assert "STRAGGLERS" in out
    finally:
        server.stop()


@pytest.mark.slow
def test_real_trainer_emits_flight_recorder_timeline(tmp_path, monkeypatch):
    """The ACTUAL SPMD trainer under the injected trace env: compile +
    steps + checkpoint save land as spans, a resume adds ckpt.restore,
    the step stream records compile=True exactly on post-(re)build steps,
    and goodput computed from the run is productive."""
    trace_dir = str(tmp_path / "trace")
    ctl_dir = str(tmp_path / "ctl")
    os.makedirs(ctl_dir)
    ckpt = str(tmp_path / "ckpt")
    monkeypatch.setenv("KUBEDL_MESH", "data=-1")
    monkeypatch.setenv("KUBEDL_TRACE_DIR", trace_dir)
    monkeypatch.setenv("KUBEDL_TRACE_ID", trace_id_for("default", "tj"))
    monkeypatch.setenv("KUBEDL_CONTROL_DIR", ctl_dir)
    monkeypatch.setenv("POD_NAME", "tj-worker-0")
    monkeypatch.setenv("POD_NAMESPACE", "default")
    monkeypatch.setenv("KUBEDL_LABEL_JOB_NAME", "tj")
    from kubedl_tpu.train import trainer

    common = ["--model", "tiny", "--batch", "8", "--seq-len", "17",
              "--checkpoint-path", ckpt, "--checkpoint-interval", "2"]
    assert trainer.main(common + ["--steps", "2"]) == 0
    spans = load_spans(trace_dir)
    names = [s["name"] for s in spans]
    assert "trainer.init" in names and "train.compile" in names
    assert "ckpt.save" in names and "trainer.done" in names
    assert {s["trace_id"] for s in spans} == {trace_id_for("default", "tj")}
    # step stream + heartbeat landed, compile flagged on step 1 only
    recs = load_step_records(
        os.path.join(trace_dir, "tj-worker-0.steps.jsonl"))
    assert [r["compile"] for r in recs] == [True, False]
    assert os.path.exists(os.path.join(ctl_dir, "heartbeat.json"))
    # resume: restore span + more steps on the SAME timeline
    assert trainer.main(common + ["--steps", "4"]) == 0
    spans = load_spans(trace_dir)
    names = [s["name"] for s in spans]
    assert "ckpt.restore" in names and "train.step" in names
    gp = goodput(spans)
    assert gp["buckets"]["steps"] > 0 and gp["buckets"]["checkpoint"] > 0
    assert gp["ratio"] > 0
    assert abs(sum(gp["buckets"].values()) - gp["wall_s"]) \
        <= 0.01 * gp["wall_s"] + 1e-4


def test_goodput_reporter_snapshot_and_cache(tmp_path):
    t = Tracer(service="op", export_root=str(tmp_path))
    t.record("train.step", duration_s=1.0,
             trace_id=trace_id_for("ns", "j"), job="j", namespace="ns")
    rep = GoodputReporter(str(tmp_path))
    snap = rep.snapshot()
    assert snap["jobs"]["ns/j"]["ratio"] == pytest.approx(1.0)
    # unchanged dir -> cached object comes back
    assert rep.snapshot()["jobs"]["ns/j"] is snap["jobs"]["ns/j"]
    # new spans invalidate the fingerprint
    t.record("gang.queue_wait", duration_s=1.0,
             trace_id=trace_id_for("ns", "j"), job="j", namespace="ns")
    snap2 = rep.snapshot()
    assert snap2["jobs"]["ns/j"]["buckets"]["queue_wait"] > 0
