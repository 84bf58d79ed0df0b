"""The compile log (kubedl_tpu/obs/compiles.py): what jax.monitoring
sends for a jitted function lands on the right record, inner functions
make no span and are counted once, a cached call sends nothing, a new
shape is a new compile, the persistent cache's answer is read, threads
keep their phases apart, and every table is bounded.

Real compiles run on the CPU with a temporary cache directory; the
bounds and the threads are fed by hand, event for event as JAX sends
them, so that they take no compile at all."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubedl_tpu.obs import Tracer, compiles, load_spans
from kubedl_tpu.obs.compiles import COMPILE, LOWER, TRACE, CompileLog


@pytest.fixture
def log(tmp_path, monkeypatch):
    """The process's log, its spans exported to a file of this test, and
    every function worth a record however fast this machine compiles."""
    monkeypatch.setattr(compiles, "MIN_RECORD_S", 0.0)
    tracer = Tracer(service="t", export_path=str(tmp_path / "t.jsonl"))
    the_log = compiles.install(tracer)
    yield the_log
    the_log.release(tracer)
    tracer.close()
    assert the_log.errors == 0, the_log.last_error


@pytest.fixture
def cache_dir(tmp_path):
    """A persistent compile cache of this test alone, every program in."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    jax.config.update(names[0], str(tmp_path / "cache"))
    jax.config.update(names[1], 0)
    jax.config.update(names[2], -1)
    cc.reset_cache()
    yield str(tmp_path / "cache")
    for n, v in before.items():
        jax.config.update(n, v)
    cc.reset_cache()


def _step_with_an_inner_jit():
    @jax.jit
    def inner_block(x):
        for _ in range(40):
            x = jnp.tanh(x) @ x
        return x

    def train_step(x):
        for _ in range(6):
            x = inner_block(x) + 1.0
        return x.sum()

    return jax.jit(train_step)


def test_three_phases_land_on_the_function_and_inner_jits_make_no_span(log, tmp_path):
    step = _step_with_an_inner_jit()
    n0 = log.count("train_step")
    # the table is the process's: other tests of this worker add to it
    zero = {"calls": 0, "total_s": 0.0, "own_s": 0.0}
    before = {name: log.nested().get(name, zero) for name in ("inner_block", "train_step")}
    step(np.ones((8, 8), np.float32)).block_until_ready()
    recs = log.records("train_step")
    assert log.count("train_step") == n0 + 1
    rec = recs[-1]
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0 and rec["executable_s"] > 0
    assert rec["cache"] in ("off", "miss", "hit") and rec["ts"] > 0
    # the inner function: no record, no span; in the table once a trace,
    # its own time less than the step's trace, which holds it
    assert not log.records("inner_block")
    inner, own = ({k: log.nested()[name][k] - before[name][k] for k in zero}
                  for name in ("inner_block", "train_step"))
    assert inner["calls"] == 6 and inner["own_s"] <= inner["total_s"] < rec["trace_s"]
    assert own["calls"] == 1 and own["total_s"] == pytest.approx(rec["trace_s"], abs=1e-5)
    assert own["own_s"] <= own["total_s"] - inner["total_s"] + 1e-5
    spans = [s for s in load_spans(str(tmp_path)) if s["attrs"].get("fun") == "train_step"]
    assert [s["name"] for s in spans] == ["jax.trace", "jax.lower", "jax.compile"]
    trace, lower, compiled = spans
    assert trace["ts"] == rec["ts"] and trace["dur"] == pytest.approx(rec["trace_s"], abs=1e-5)
    assert trace["ts"] + trace["dur"] <= lower["ts"] <= compiled["ts"]
    assert {n["fun"] for n in trace["attrs"]["nested"]} >= {"inner_block"}
    assert len(trace["attrs"]["nested"]) <= compiles.TOP_NESTED
    assert compiled["attrs"]["cache"] == rec["cache"]
    assert compiled["attrs"]["trace_s"] == rec["trace_s"]
    assert compiled["dur"] == pytest.approx(rec["executable_s"], abs=1e-5)
    assert not [s for s in load_spans(str(tmp_path))
                if s["attrs"].get("fun") == "inner_block"]


def test_a_second_call_sends_nothing_and_a_new_shape_is_a_new_compile(log):
    step = _step_with_an_inner_jit()
    base = log.count("train_step")
    step(np.ones((8, 8), np.float32)).block_until_ready()
    mine = log.count(thread=True)
    everything = log.count()
    step(np.ones((8, 8), np.float32)).block_until_ready()
    assert (log.count(thread=True), log.count()) == (mine, everything)
    assert log.since(mine) == []
    step(np.ones((16, 16), np.float32)).block_until_ready()
    assert log.count("train_step") == base + 2
    again = log.since(mine)
    assert [r["fun"] for r in again][-1] == "train_step"
    assert log.count(thread=True) == mine + len(again)


def test_small_functions_make_no_record_but_count(log, tmp_path, monkeypatch):
    monkeypatch.setattr(compiles, "MIN_RECORD_S", 60.0)
    n0, t0 = len(log.records()), log.count(thread=True)
    jax.jit(lambda x: x + 1, inline=False)(np.float32(1)).block_until_ready()
    assert log.count(thread=True) == t0 + 1
    (small,) = log.since(t0)
    assert small["fun"] == "<lambda>"
    assert small["trace_s"] + small["lower_s"] + small["executable_s"] < compiles.MIN_RECORD_S
    assert len(log.records()) == n0
    assert not [s for s in load_spans(str(tmp_path)) if s["attrs"].get("fun") == "<lambda>"]
    assert log.nested()["<lambda>"]["calls"] >= 1  # other tests' lambdas too


def test_the_persistent_cache_says_miss_then_hit(log, cache_dir):
    def make():
        def cached_step(x):
            for _ in range(60):
                x = jnp.sin(x) @ x
            return x
        return jax.jit(cached_step)

    x = np.ones((8, 8), np.float32)
    make()(x).block_until_ready()
    first = log.records("cached_step")[-1]
    assert first["cache"] == "miss" and first["cache_read_s"] == 0.0
    # as a second process would find it: nothing in memory, the cache filled
    jax.clear_caches()
    make()(x).block_until_ready()
    second = log.records("cached_step")[-1]
    assert second["ts"] > first["ts"]
    assert second["cache"] == "hit" and second["cache_read_s"] > 0
    assert second["cache_read_s"] <= second["executable_s"]
    assert second["trace_s"] > 0 and second["lower_s"] > 0  # no cache removes these


def test_lowering_alone_is_a_record_without_an_executable(log):
    def lowered_only(x):
        for _ in range(120):
            x = jnp.cos(x) @ x
        return x

    n0 = log.count("lowered_only")
    lowered = jax.jit(lowered_only).lower(jax.ShapeDtypeStruct((8, 8), jnp.float32))
    rec = log.records("lowered_only")[-1]
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0
    assert rec["executable_s"] == 0.0 and rec["cache"] == "off"
    lowered.compile()
    # a compile that no trace of its own preceded is a function begun too
    assert log.count("lowered_only") == n0 + 2
    assert log.since(log.count(thread=True) - 1)[0]["executable_s"] > 0


def test_install_twice_registers_once():
    from jax._src import monitoring

    log = compiles.install()
    assert compiles.install() is log
    tracer = Tracer(service="other")
    assert compiles.install(tracer) is log and log.tracer is tracer
    log.release(tracer)
    assert log.tracer is not tracer
    for listeners, mine in (
            (monitoring.get_scalar_listeners(), log.on_scalar),
            (monitoring.get_event_time_span_listeners(), log.on_span),
            (monitoring.get_event_duration_listeners(), log.on_duration),
            (monitoring.get_event_listeners(), log.on_event)):
        assert sum(1 for cb in listeners if cb == mine) == 1


# -- fed by hand ---------------------------------------------------------------


def _compile(log, fun, trace_s=0.2, lower_s=0.1, executable_s=0.3, inner=(),
             cache=None, t0=1000.0, between=None):
    """One function's events as JAX sends them."""
    log.on_scalar(TRACE, t0, fun_name=fun)
    t = t0
    for name, dur in inner:
        log.on_scalar(TRACE, t, fun_name=name)
        log.on_span(TRACE, t, t + dur, fun_name=name)
        t += dur
    if between is not None:
        between()
    log.on_span(TRACE, t0, t0 + trace_s, fun_name=fun)
    t = t0 + trace_s
    log.on_scalar(LOWER, t, fun_name=f"jit({fun})")
    log.on_span(LOWER, t, t + lower_s, fun_name=f"jit({fun})")
    t += lower_s
    log.on_scalar(COMPILE, t, fun_name=f"jit({fun})")
    if cache is not None:
        log.on_event(compiles.CACHE_REQUEST)
        log.on_event(compiles.CACHE_HIT if cache == "hit" else compiles.CACHE_MISS)
        if cache == "hit":
            log.on_duration(compiles.CACHE_READ, 0.25)
    log.on_span(COMPILE, t, t + executable_s, fun_name=f"jit({fun})")


def test_by_hand_own_time_is_apart_from_the_childrens():
    log = CompileLog()
    _compile(log, "train_step", trace_s=1.0, inner=[("gmm", 0.2), ("gmm", 0.2), ("attn", 0.1)],
             cache="hit")
    (rec,) = log.records()
    assert rec == {"fun": "train_step", "trace_s": 1.0, "lower_s": 0.1, "executable_s": 0.3,
                   "cache": "hit", "cache_read_s": 0.25, "ts": 1000.0}
    table = log.nested()
    assert table["gmm"] == {"calls": 2, "total_s": pytest.approx(0.4), "own_s": pytest.approx(0.4)}
    assert table["train_step"] == {"calls": 1, "total_s": 1.0, "own_s": pytest.approx(0.5)}
    names = [s["name"] for s in log.tracer.spans()]
    assert names == ["jax.trace", "jax.lower", "jax.compile"]
    nested = log.tracer.spans()[0]["attrs"]["nested"]
    assert [n["fun"] for n in nested] == ["gmm", "attn"]
    assert log.count() == log.count("train_step") == log.count(thread=True) == 1
    assert log.errors == 0


def test_by_hand_an_eager_compile_inside_a_trace_keeps_its_cache_events():
    log = CompileLog()

    def eager_primitive():
        # jnp.asarray of a constant while train_step is traced: a whole
        # compile, cache miss and all, nested in the outer trace
        log.on_scalar(TRACE, 1000.5, fun_name="convert_element_type")
        log.on_span(TRACE, 1000.5, 1000.51, fun_name="convert_element_type")
        log.on_scalar(COMPILE, 1000.52, fun_name="jit(convert_element_type)")
        log.on_event(compiles.CACHE_REQUEST)
        log.on_event(compiles.CACHE_MISS)
        log.on_span(COMPILE, 1000.52, 1000.55, fun_name="jit(convert_element_type)")

    _compile(log, "train_step", trace_s=1.0, between=eager_primitive)
    (rec,) = log.records()
    assert rec["fun"] == "train_step" and rec["cache"] == "off"
    assert log.count() == 1
    row = log.nested()["convert_element_type"]
    assert row["calls"] == 1 and row["total_s"] == pytest.approx(0.04)
    assert log.nested()["train_step"]["own_s"] == pytest.approx(0.96)


def test_by_hand_the_bounds_hold():
    log = CompileLog()
    for i in range(compiles.MAX_RECORDS + 40):
        _compile(log, f"f{i}", t0=1000.0 + i)
    recs = log.records()
    assert len(recs) == compiles.MAX_RECORDS
    assert recs[0]["fun"] == "f40" and recs[-1]["fun"] == f"f{compiles.MAX_RECORDS + 39}"
    table = log.nested()
    assert len(table) == compiles.MAX_NAMES + 1 and table["other"]["calls"] == (
        compiles.MAX_RECORDS + 40 - compiles.MAX_NAMES)
    assert log.count() == compiles.MAX_RECORDS + 40
    assert log.count("f0") == 1 and log.count("other") > 0
    assert len(log.since(0)) == compiles.RECENT
    # a function's own inner names are bounded the same way
    log = CompileLog()
    _compile(log, "wide", trace_s=5.0,
             inner=[(f"g{i}", 0.001) for i in range(compiles.MAX_NAMES + 30)])
    assert len(log.nested()) <= compiles.MAX_NAMES + 1
    assert log.nested()["other"]["calls"] >= 30


def test_by_hand_two_threads_keep_their_phases_apart():
    log = CompileLog()
    gate = threading.Barrier(2, timeout=30)
    seen = {}

    def worker(name, trace_s):
        before = log.count(thread=True)
        log.on_scalar(TRACE, 1000.0, fun_name=name)
        gate.wait()  # both traces are open at once
        log.on_scalar(TRACE, 1000.1, fun_name=f"inner_{name}")
        gate.wait()
        log.on_span(TRACE, 1000.1, 1000.2, fun_name=f"inner_{name}")
        log.on_span(TRACE, 1000.0, 1000.0 + trace_s, fun_name=name)
        gate.wait()
        log.on_scalar(COMPILE, 1002.0, fun_name=f"jit({name})")
        log.on_event(compiles.CACHE_REQUEST)
        if name == "a":
            log.on_event(compiles.CACHE_HIT)
        gate.wait()
        log.on_span(COMPILE, 1002.0, 1002.5, fun_name=f"jit({name})")
        seen[name] = (log.count(thread=True) - before, log.since(before))

    threads = [threading.Thread(target=worker, args=("a", 0.5)),
               threading.Thread(target=worker, args=("b", 0.7))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    assert log.errors == 0, log.last_error
    by_fun = {r["fun"]: r for r in log.records()}
    assert by_fun["a"]["trace_s"] == 0.5 and by_fun["b"]["trace_s"] == pytest.approx(0.7)
    assert by_fun["a"]["cache"] == "hit" and by_fun["b"]["cache"] == "miss"
    assert seen["a"][0] == seen["b"][0] == 1
    assert [r["fun"] for r in seen["a"][1]] == ["a"] and [r["fun"] for r in seen["b"][1]] == ["b"]
    table = log.nested()
    assert table["inner_a"]["calls"] == table["inner_b"]["calls"] == 1
    assert log.count(thread=True) == 0  # this thread compiled nothing


def test_two_real_threads_compile_at_once(log):
    """Lost updates would show as a record short or a phase on the wrong
    function; more workers than the two functions need, a short switch
    interval."""
    import sys

    def make(i):
        def threaded_step(x):
            for _ in range(40 + i):
                x = jnp.tanh(x) @ x
            return x
        threaded_step.__name__ = f"threaded_step_{i}"
        return jax.jit(threaded_step)

    steps = [make(i) for i in range(6)]
    counts = {}

    def worker(i):
        before = log.count(thread=True)
        steps[i](np.ones((8, 8), np.float32)).block_until_ready()
        counts[i] = [r["fun"] for r in log.since(before)]

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    for i in range(6):
        assert counts[i] == [f"threaded_step_{i}"]
        (rec,) = log.records(f"threaded_step_{i}")
        assert rec["trace_s"] > 0 and rec["lower_s"] > 0 and rec["executable_s"] > 0


def test_a_fault_in_a_listener_is_counted_and_never_raised():
    log = CompileLog()
    log.on_scalar(TRACE, "not a time", fun_name="f")     # float() raises inside
    assert log.errors == 1 and "ValueError" in log.last_error
    log.on_span(TRACE, 1.0, 2.0, fun_name="never_opened")  # began before install
    log.on_span("/some/other/event", 1.0, 2.0)
    log.on_event(compiles.CACHE_HIT)                       # no compile open
    log.on_duration(compiles.CACHE_READ, 0.5)
    assert log.errors == 1 and log.records() == []


def test_summed_names_what_was_worth_a_record():
    recs = [{"fun": "add", "trace_s": 0.001, "lower_s": 0.002, "executable_s": 0.003,
             "cache": "hit"},
            {"fun": "loss_body", "trace_s": 0.3, "lower_s": 0.4, "executable_s": 1.1,
             "cache": "miss"},
            {"fun": "update_body", "trace_s": 0.1, "lower_s": 0.1, "executable_s": 0.2,
             "cache": "hit"}]
    assert compiles.summed(recs) == {
        "fun": "loss_body+update_body", "trace_s": 0.401, "lower_s": 0.502,
        "executable_s": 1.303, "cache": "miss"}
    assert compiles.summed(recs[:1])["fun"] == "add"
    assert compiles.summed([dict(recs[0], cache="off")])["cache"] == "off"


def test_by_hand_an_end_that_skips_open_phases_drops_them():
    """JAX sends no end event once the interpreter is exiting; an end that
    does come closes its own phase and whatever was left open above it."""
    log = CompileLog()
    log.on_scalar(TRACE, 1000.0, fun_name="outer")
    log.on_scalar(TRACE, 1000.1, fun_name="left_open")
    log.on_span(TRACE, 1000.0, 1000.4, fun_name="outer")
    log.on_scalar(COMPILE, 1000.5, fun_name="jit(outer)")
    log.on_span(COMPILE, 1000.5, 1000.9, fun_name="jit(outer)")
    (rec,) = log.records()
    assert (rec["fun"], rec["trace_s"], rec["executable_s"]) == (
        "outer", pytest.approx(0.4), pytest.approx(0.4))
    assert "left_open" not in log.nested() and log.errors == 0
    assert log.tracer.current() is None  # no span left on the tracer's stack
