"""Names a device trace can be read by (PERF.md section 3): `name=` on
every Pallas kernel, the jitted step's own name, `jax.named_scope`s in
the model and the step, `obs` spans mirrored into a profiler window, and
a recorder that reads a step's loss two steps late.

All on the CPU: names are compile-time metadata, so the lowered text
holds them whatever the backend; what the TPU's compiler makes of a
kernel's name is in tests/test_tpu_compile.py.
"""
import ast
import glob
import json
import os
import re
import signal
import types

import jax
import jax.numpy as jnp
import optax
import pytest

from kubedl_tpu.models import llama
from kubedl_tpu.obs import Tracer, load_spans, load_step_records
from kubedl_tpu.parallel.mesh import ShardingRules, build_mesh
from kubedl_tpu.parallel.train_step import make_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPS = os.path.join(ROOT, "kubedl_tpu", "ops")


# -- (a) every pallas_call carries a fixed name ------------------------------


def _pallas_calls():
    """(id, name= node or None) for every `pallas_call(...)` under
    kubedl_tpu/ops/, read from the source: a new unnamed kernel is a new
    failing case."""
    found = []
    for path in sorted(glob.glob(os.path.join(OPS, "*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        for fn in ast.walk(tree):
            if not isinstance(fn, ast.FunctionDef):
                continue
            for node in ast.walk(fn):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "pallas_call"):
                    name = next((kw.value for kw in node.keywords
                                 if kw.arg == "name"), None)
                    found.append(pytest.param(
                        name, id=f"{os.path.basename(path)}:{fn.name}:"
                                 f"{node.lineno}"))
    return found


def _literal_names(node):
    """The strings a `name=` expression can take, if every one of them is
    a literal: "x", or "x" if cond else "y". None otherwise (a name built
    from shapes, an f-string, a variable)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, ast.IfExp):
        a, b = _literal_names(node.body), _literal_names(node.orelse)
        return a + b if a and b else None
    return None


PALLAS_CALLS = _pallas_calls()


def test_the_scan_finds_the_kernels():
    assert len(PALLAS_CALLS) >= 7


@pytest.mark.parametrize("name_node", PALLAS_CALLS)
def test_every_pallas_call_has_a_literal_name(name_node):
    assert name_node is not None, "pl.pallas_call(...) without name="
    names = _literal_names(name_node)
    assert names, "name= is not a fixed string"
    for n in names:
        assert n.replace("_", "").isalnum() and n[0].isalpha(), n


def test_kernel_names_are_the_ones_the_metrics_read():
    names = sorted(n for p in PALLAS_CALLS for n in _literal_names(p.values[0]))
    assert names == ["flash_bwd_dqkv", "flash_fwd",
                     "flash_fwd_streamed", "gmm", "gmm_drhs", "gmm_scaled",
                     "gmm_swiglu", "hc_post_bwd", "hc_post_fwd", "hc_pre_bwd",
                     "hc_pre_fwd", "moe_gather", "short_conv_bwd",
                     "short_conv_fwd", "ssm_conv_bwd", "ssm_conv_fwd",
                     "ssm_scan_bwd", "ssm_scan_fwd"]


# the instruction each flash kernel defines, as a trace's event names it
FLASH_EVENTS = {"flash_fwd": "%flash_fwd.3 = (bf16[64,8192,128]{2,1,0}, ",
                "flash_bwd_dqkv": "%flash_bwd_dqkv.7 = (bf16[64,8192,128]{2,1,0}, "}
NAMED_FLASH_METRICS = sorted(
    glob.glob(os.path.join(ROOT, "benchmarks", "metrics", "flash_time_share_*.train.json"))
    + glob.glob(os.path.join(ROOT, "benchmarks", "metrics", "flash_bwd_roofline*.train.json")))


@pytest.mark.parametrize("path", NAMED_FLASH_METRICS, ids=os.path.basename)
def test_the_by_name_metrics_find_the_one_backward_kernel(path):
    """Every flash metric that reads kernels by name finds
    `flash_bwd_dqkv`: the five time shares the forward beside it, so they
    still read the whole attention core, and the five backward rooflines
    it alone (which count two events a call, so read half its share)."""
    with open(path) as f:
        params = json.load(f)["params"]
    if "pattern" in params:
        wanted, pattern = FLASH_EVENTS, params["pattern"]
    else:
        wanted, pattern = ("flash_bwd_dqkv",), params["patterns"]["bwd"]
    for kernel in FLASH_EVENTS:
        assert bool(re.search(pattern, FLASH_EVENTS[kernel])) == (kernel in wanted), kernel


# -- (b) scopes and names in the lowered step --------------------------------


def _lowered_step(**config_kw):
    config = llama.LlamaConfig.tiny(**config_kw)
    mesh = build_mesh({"fsdp": 1}, devices=jax.devices()[:1])
    rules = ShardingRules()
    init_state, train_step = make_train_step(
        lambda p, t: llama.loss_fn(p, t, config, mesh=mesh, rules=rules),
        optax.adamw(3e-4), mesh, llama.param_specs(config, rules),
        rules.spec("batch", None), rules)
    params = jax.eval_shape(
        lambda k: llama.init(config, k), jax.random.PRNGKey(0))
    state = jax.eval_shape(init_state.jit, params)
    tokens = jax.ShapeDtypeStruct((2, 33), jnp.int32)
    return init_state, train_step, train_step.lower(state, tokens)


@pytest.fixture(scope="module")
def lowered_text():
    return {
        (use_flash, remat): _lowered_step(use_flash=use_flash, remat=remat)[2]
        .as_text(debug_info=True)
        for use_flash, remat in ((True, True), (False, True), (False, False))}


def test_step_and_init_are_named_for_what_they_are():
    init_state, train_step, lowered = _lowered_step()
    assert "module @jit_train_step" in lowered.as_text()
    assert train_step.__name__ == "train_step"
    assert init_state.jit.__name__ == "init_state"
    # still the jitted callable itself, no Python wrapper around it
    assert hasattr(train_step, "lower") and hasattr(init_state.jit, "lower")


@pytest.mark.parametrize("scope", [
    "jvp(embed)", "jvp(attn)", "attn/attn_core", "jvp(mlp)",
    "jvp(head_loss)", "transpose(jvp(head_loss))", "/optimizer/",
    "/grad_norm/"])
@pytest.mark.parametrize("use_flash", [True, False])
def test_lowered_step_holds_each_scope(lowered_text, scope, use_flash):
    """The same scope names whether flash or plain XLA does attention."""
    assert scope in lowered_text[(use_flash, True)]


def test_remat_recompute_is_told_apart(lowered_text):
    with_remat, without = lowered_text[(False, True)], lowered_text[(False, False)]
    for scope in ("rematted_computation/mlp", "rematted_computation/attn/attn_core",
                  "checkpoint/mlp"):
        assert scope in with_remat
        assert scope not in without
    assert "transpose(jvp(mlp))" in without


def test_kernel_names_reach_the_lowered_text(lowered_text):
    text = lowered_text[(True, True)]
    # the kernel's own name is the innermost entry of the stack, which
    # is what its HLO instruction is called after
    for name in ("attn_core/flash_attention/flash_fwd/",
                 "attn_core/flash_attention/flash_bwd_dqkv/"):
        assert name in text
    # remat keeps the forward kernel's out and lse (llama._remat_policy):
    # the backward recomputes the projections around it, not the kernel
    assert "rematted_computation/attn/" in text
    assert ("rematted_computation/attn/attn_core/flash_attention/flash_fwd/"
            not in text)
    assert "flash_fwd" not in lowered_text[(False, True)]


@pytest.mark.parametrize("devices", [1, 2])
def test_convolution_kernels_keep_their_names_under_the_scope(monkeypatch, devices):
    """A convolution layer's gates and taps as their kernels (steered to
    the form a TPU takes; interpreted here) in a lowered train step, on
    one device and inside a shard_map over two: the call sits under the
    scope `short_conv` in the forward, under `jax.grad`'s transpose and
    in the remat copy, inside it `short_conv_kernel`, and each kernel's
    operations under its own name, which its HLO instruction takes on a
    TPU (tests/test_tpu_compile.py)."""
    from kubedl_tpu.models import short_conv

    monkeypatch.setattr(short_conv, "interpret", lambda: False)
    config = llama.LlamaConfig.tiny(n_layers=2, layer_types=("conv", "attention"),
                                    use_flash=False)
    mesh = build_mesh({"fsdp": devices}, devices=jax.devices()[:devices])
    rules = ShardingRules()
    init_state, train_step = make_train_step(
        lambda p, t: llama.loss_fn(p, t, config, mesh=mesh, rules=rules),
        optax.adamw(3e-4), mesh, llama.param_specs(config, rules),
        rules.spec("batch", None), rules)
    params = jax.eval_shape(lambda k: llama.init(config, k), jax.random.PRNGKey(0))
    state = jax.eval_shape(init_state.jit, params)
    tokens = jax.ShapeDtypeStruct((2, 129), jnp.int32)
    text = train_step.lower(state, tokens).as_text(debug_info=True)
    # inside a shard_map the body's own names start again at its scope
    call = "shard_map" if devices > 1 else "short_conv_kernel/"
    for path in (r"jvp\(short_conv\)/",  # the forward
                 r'transpose\([^"]*/checkpoint/rematted_computation/short_conv/',
                 r'transpose\([^"]*/checkpoint/short_conv/'):  # the backward
        assert re.search(r'"jit\(train_step\)/' + path + call, text), path
    assert ('"short_conv_kernel/jit' in text) == (devices > 1)
    for kernel in ("short_conv_fwd", "short_conv_bwd"):
        assert f'"{kernel}/pallas_call"' in text, kernel


# -- (c) spans on the profiler's clock ---------------------------------------


def _trace_env(monkeypatch, tmp_path, pod="tn-worker-0"):
    trace_dir = str(tmp_path / "trace")
    monkeypatch.setenv("KUBEDL_MESH", "data=-1")
    monkeypatch.setenv("KUBEDL_TRACE_DIR", trace_dir)
    monkeypatch.setenv("KUBEDL_TRACE_ID", "0" * 32)
    monkeypatch.setenv("POD_NAME", pod)
    return trace_dir


def _host_event_names(profile_dir):
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb"))
    assert len(paths) == 1, paths
    data = ProfileData.from_file(paths[0])
    return {ev.name for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events}


def test_span_is_a_trace_annotation_while_open(tmp_path):
    tracer = Tracer(service="t", export_path=str(tmp_path / "t.jsonl"))
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        with tracer.span("outer.span", step=1) as outer:
            with tracer.span("inner.span", export=False):
                jnp.ones(8).block_until_ready()
        tracer.record("after.the.fact", duration_s=0.01)
    finally:
        jax.profiler.stop_trace()
    tracer.close()
    names = _host_event_names(str(tmp_path / "prof"))
    assert {"outer.span", "inner.span"} <= names
    assert "after.the.fact" not in names  # an interval already over
    assert outer.dur > 0
    # the ring holds all three, the file only what is exported
    assert [s["name"] for s in tracer.spans()] == [
        "inner.span", "outer.span", "after.the.fact"]
    assert [s["name"] for s in load_spans(str(tmp_path))] == [
        "outer.span", "after.the.fact"]


def test_trainer_profile_window_holds_the_loop_spans(tmp_path, monkeypatch, capsys):
    trace_dir = _trace_env(monkeypatch, tmp_path)
    profile_dir = str(tmp_path / "prof")
    from kubedl_tpu.train import trainer

    steps = 6
    assert trainer.main([
        "--model", "tiny", "--batch", "8", "--seq-len", "17",
        "--steps", str(steps), "--log-every", "3",
        "--profile-dir", profile_dir, "--profile-steps", "3"]) == 0
    names = _host_event_names(profile_dir)
    assert {"train.data", "train.dispatch", "train.wait", "train",
            "PjitFunction(train_step)"} <= names
    spans = load_spans(trace_dir)
    step_spans = [s for s in spans if s["name"] in ("train.compile", "train.step")]
    assert [s["name"] for s in step_spans] == ["train.compile"] + ["train.step"] * (steps - 1)
    assert [s["attrs"]["step"] for s in step_spans] == list(range(1, steps + 1))
    for s in step_spans:
        assert isinstance(s["attrs"]["loss"], float)
        assert {"data_wait_s", "dispatch_s", "wait_s"} <= set(s["attrs"])
        assert "synced" not in s["attrs"]
    # the sub-step spans ride on the step's record, not in the file
    assert not {"train.data", "train.dispatch", "train.wait"} & {
        s["name"] for s in spans}
    recs = load_step_records(os.path.join(trace_dir, "tn-worker-0.steps.jsonl"))
    assert [r["loss"] for r in recs] == [s["attrs"]["loss"] for s in step_spans]
    # each step's own loss: the log line reads steps 3 and 6 itself
    logged = dict(
        (int(line.split(":")[0].split()[1]), float(line.split("loss=")[1].split()[0]))
        for line in capsys.readouterr().out.splitlines()
        if line.startswith("step "))
    assert set(logged) == {3, 6}
    for n, loss in logged.items():
        assert step_spans[n - 1]["attrs"]["loss"] == pytest.approx(loss, abs=1e-4)


# -- (d) the recorder does not drain the device ------------------------------


class _FakeLoss:
    """A step's result that says when it was read."""

    def __init__(self, step, events):
        self.step, self.events = step, events

    def __float__(self):
        self.events.append(("read", self.step))
        return float(self.step)

    def block_until_ready(self):
        return self


def _fake_make_train_step(events, on_dispatch=None):
    def make(*args, **kwargs):
        count = {"n": 0}

        def init_state(params):
            return types.SimpleNamespace(params=None, step=jnp.zeros(()))

        def train_step(state, batch):
            count["n"] += 1
            events.append(("dispatch", count["n"]))
            if on_dispatch is not None:
                on_dispatch(count["n"])
            return state, {"loss": _FakeLoss(count["n"], events)}

        return init_state, train_step
    return make


def _run_fake(monkeypatch, argv, events, on_dispatch=None):
    import kubedl_tpu.parallel.train_step as ts
    from kubedl_tpu.train import trainer

    monkeypatch.setattr(
        ts, "make_train_step", _fake_make_train_step(events, on_dispatch))
    return trainer.main(["--model", "tiny", "--batch", "4", "--seq-len", "9",
                         "--log-every", "1000"] + argv)


def test_recorder_reads_a_loss_two_dispatches_late_and_save_flushes(
        tmp_path, monkeypatch):
    trace_dir = _trace_env(monkeypatch, tmp_path)
    events = []
    assert _run_fake(monkeypatch, ["--steps", "10",
                                   "--checkpoint-interval", "4"], events) == 0
    d, r = (lambda n: ("dispatch", n)), (lambda n: ("read", n))
    assert events == [
        d(1), d(2), d(3), r(1), d(4), r(2),
        r(3), r(4),                      # the interval save at step 4 flushes
        d(5), d(6), d(7), r(5), d(8), r(6),
        r(7), r(8),                      # and at step 8
        d(9), d(10), r(9), r(10)]        # the end of the run flushes
    # no step's loss is read before two later steps were dispatched,
    # but where a flush was due
    dispatched = 0
    for kind, n in events:
        if kind == "dispatch":
            dispatched = n
        else:
            assert dispatched >= min(n + 2, 4 * ((n - 1) // 4 + 1), 10)
    spans = [s for s in load_spans(trace_dir)
             if s["name"] in ("train.compile", "train.step")]
    assert [s["attrs"]["loss"] for s in spans] == [float(n) for n in range(1, 11)]
    # consecutive awaited completions: the records tile, none overlaps
    for a, b in zip(spans, spans[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + b["dur"]


def test_a_compile_lies_in_its_own_steps_record_and_in_no_other():
    """Two steps are in flight when step 3's dispatch stalls on a compile:
    their records end where that dispatch began, and step 3's holds it."""
    import time

    from kubedl_tpu.train.trainer import StepRecorder

    tracer, events = Tracer(service="t"), []
    rec = StepRecorder(tracer, None)
    what = [{"fun": "train_step", "trace_s": 0.02, "lower_s": 0.01,
             "executable_s": 0.03, "cache": "miss"}]
    starts = {}
    for step in (1, 2, 3, 4, 5):
        starts[step] = t0 = time.perf_counter()
        if step == 3:
            time.sleep(0.06)  # the compile, inside the dispatch
        rec.dispatched(step, _FakeLoss(step, events), t0, 0.0,
                       time.perf_counter() - t0, what if step == 3 else [])
    rec.flush()
    spans = {s["attrs"]["step"]: s for s in tracer.spans()
             if s["name"] in ("train.compile", "train.step")}
    assert [spans[n]["name"] for n in (1, 2, 3, 4, 5)] == [
        "train.step", "train.step", "train.compile", "train.step", "train.step"]
    assert spans[3]["attrs"]["fun"] == "train_step" and spans[3]["attrs"]["cache"] == "miss"
    assert spans[3]["dur"] >= 0.06 > spans[1]["dur"] + spans[2]["dur"]
    # the records still tile: each ends where the next begins
    for a, b in zip((1, 2, 3, 4), (2, 3, 4, 5)):
        assert spans[a]["ts"] + spans[a]["dur"] <= spans[b]["ts"] + 1e-4


def test_plain_loop_reads_no_loss_and_makes_no_span(tmp_path, monkeypatch):
    """Neither trace env nor profile window: dispatch only."""
    monkeypatch.setenv("KUBEDL_MESH", "data=-1")
    for var in ("KUBEDL_TRACE_DIR", "KUBEDL_CONTROL_DIR"):
        monkeypatch.delenv(var, raising=False)
    from kubedl_tpu.obs import trace as obs_trace

    made = []
    real = obs_trace.Tracer.span
    monkeypatch.setattr(
        obs_trace.Tracer, "span",
        lambda self, name, *a, **kw: made.append(name) or real(self, name, *a, **kw))
    events = []
    assert _run_fake(monkeypatch, ["--steps", "5"], events) == 0
    assert events == [("dispatch", n) for n in range(1, 6)]
    assert not [n for n in made if n.startswith("train.")]


def test_preemption_flushes_the_pending_steps(tmp_path, monkeypatch):
    trace_dir = _trace_env(monkeypatch, tmp_path)
    events = []

    class Exited(Exception):
        pass

    def fake_exit(code):
        raise Exited(code)

    monkeypatch.setattr(os, "_exit", fake_exit)
    before = signal.getsignal(signal.SIGTERM)
    try:
        with pytest.raises(Exited) as exited:
            _run_fake(monkeypatch, ["--steps", "10"], events,
                      on_dispatch=lambda n: n == 3 and os.kill(
                          os.getpid(), signal.SIGTERM))
    finally:
        signal.signal(signal.SIGTERM, before)
    from kubedl_tpu.utils.exit_codes import EXIT_TPU_PREEMPTED

    assert exited.value.args == (EXIT_TPU_PREEMPTED,)
    assert events == [("dispatch", 1), ("dispatch", 2), ("dispatch", 3),
                      ("read", 1), ("read", 2), ("read", 3)]
    names = [s["name"] for s in load_spans(trace_dir)]
    # the fake step compiles nothing, and the compile log says so: no
    # step is train.compile for being the first
    assert names.count("train.step") == 3 and "train.compile" not in names
    assert "trainer.preempted" in names


# -- (e) a compile is what JAX says it was -----------------------------------


class _OddBatchLoader:
    """The trainer's token loader, handing one batch of another length:
    the step that takes it traces, lowers and compiles anew."""

    ODD_AT = 3  # the fourth batch, inside the profile window [1, 4)
    n_windows, is_native = 1 << 20, False

    def __init__(self, paths, batch, seq_len, seed=0, n_threads=0):
        self.batch, self.seq_len = batch, seq_len

    def batch_at(self, i):
        import numpy as np

        seq = self.seq_len + (4 if i == self.ODD_AT else 0)
        return np.random.default_rng(i).integers(
            0, 256, (self.batch, seq), dtype=np.int32)


@pytest.fixture(scope="module")
def recompile_run(tmp_path_factory):
    """One tiny trainer run under the trace env and a profile window,
    its fourth step made to trace anew."""
    import io
    from contextlib import redirect_stdout

    from kubedl_tpu.native import loader
    from kubedl_tpu.train import trainer

    steps = 6

    def run_once(tmp):
        shard = tmp / "shard-0.bin"
        shard.write_bytes(b"\0" * 64)
        with pytest.MonkeyPatch.context() as mp:
            trace_dir = _trace_env(mp, tmp)
            mp.setattr(loader, "TokenLoader", _OddBatchLoader)
            out = io.StringIO()
            with redirect_stdout(out):
                rc = trainer.main([
                    "--model", "tiny", "--batch", "8", "--seq-len", "17",
                    "--steps", str(steps), "--log-every", "1000",
                    "--data-path", str(shard),
                    "--profile-dir", str(tmp / "prof"), "--profile-steps", "3"])
        assert rc == 0, out.getvalue()
        return trace_dir

    # The same run once before, its records thrown away: JAX's own helpers
    # for an odd batch (`_multi_slice`) are compiled by whichever call of
    # this process meets them first, and a dispatch's compiles are joined
    # into its record's `fun`; after this run they are this worker's
    # already, whatever tests it ran before (PERF.md Open question 30).
    run_once(tmp_path_factory.mktemp("recompile_warm"))
    tmp = tmp_path_factory.mktemp("recompile")
    trace_dir = run_once(tmp)
    return {"trace_dir": trace_dir, "profile_dir": str(tmp / "prof"),
            "spans": load_spans(trace_dir), "steps": steps, "tmp": tmp}


def test_train_compile_is_the_steps_that_compiled_and_no_other(recompile_run):
    spans = recompile_run["spans"]
    step_spans = [s for s in spans if s["name"] in ("train.compile", "train.step")]
    odd = _OddBatchLoader.ODD_AT + 1
    assert [(s["name"], s["attrs"]["step"]) for s in step_spans] == [
        ("train.compile" if n in (1, odd) else "train.step", n)
        for n in range(1, recompile_run["steps"] + 1)]
    for s in step_spans:
        if s["name"] == "train.compile":
            a = s["attrs"]
            assert a["fun"] == "train_step" and a["cache"] in ("hit", "miss", "off")
            assert a["trace_s"] > 0 and a["lower_s"] > 0 and a["executable_s"] > 0
            # the dispatch held the compile, and the record says how much of it
            assert a["trace_s"] + a["lower_s"] + a["executable_s"] <= a["dispatch_s"]
        else:
            assert not {"fun", "trace_s", "cache"} & set(s["attrs"])
    recs = load_step_records(os.path.join(
        recompile_run["trace_dir"], "tn-worker-0.steps.jsonl"))
    assert [r["compile"] for r in recs] == [n in (1, odd) for n in range(1, 7)]
    assert recs[-1]["compiles"] == 2  # what kubedl_compile_events_total sums


def test_each_compile_of_the_step_has_its_three_spans_inside_its_record(recompile_run):
    spans = recompile_run["spans"]
    compiled = [s for s in spans if s["name"] == "train.compile"]
    for name in ("jax.trace", "jax.lower", "jax.compile"):
        mine = [s for s in spans if s["name"] == name
                and s["attrs"].get("fun") == "train_step"]
        assert len(mine) == 2
        for rec, span in zip(compiled, mine):
            assert rec["ts"] - 1e-3 <= span["ts"]
            assert span["ts"] + span["dur"] <= rec["ts"] + rec["dur"] + 1e-3
            assert span["trace_id"] == rec["trace_id"]
    first = next(s for s in spans if s["name"] == "jax.trace"
                 and s["attrs"].get("fun") == "train_step")
    assert first["attrs"]["nested"] and len(first["attrs"]["nested"]) <= 5
    # the step's record sums what compiled inside its dispatch: the step,
    # and whatever small eager primitive a new shape brought with it
    last = [s for s in spans if s["name"] == "jax.compile"
            and s["attrs"].get("fun") == "train_step"][-1]
    whole = compiled[-1]["attrs"]["executable_s"]
    assert 0.5 * whole <= last["dur"] <= whole + 1e-5


def test_trainer_init_has_its_children_where_the_work_is(recompile_run):
    spans = recompile_run["spans"]
    init = next(s for s in spans if s["name"] == "trainer.init")
    children = [s for s in spans if s["name"].startswith("init.")]
    assert [s["name"] for s in children] == [
        "init.imports", "init.backend", "init.mesh", "init.state"]
    for a, b in zip(children, children[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-3
    for s in children:
        assert init["ts"] - 1e-2 <= s["ts"]
        assert s["ts"] + s["dur"] <= init["ts"] + init["dur"] + 1e-2
    assert sum(s["dur"] for s in children) <= init["dur"] + 1e-2
    # the state's own compiles lie inside init.state
    state = children[-1]
    inside = [s for s in spans if s["name"] == "jax.compile"
              and state["ts"] <= s["ts"] <= state["ts"] + state["dur"]]
    assert inside and "train_step" not in {s["attrs"]["fun"] for s in inside}


def test_profile_window_names_the_gap_under_a_recompile(recompile_run):
    """Read as the benchmark reads a trace (benchmarks/trace.py:load): the
    three phases lie on the host plane inside the dispatch that held them."""
    from benchmarks import trace as tr

    trace = tr.load(tr.find_xplane(recompile_run["profile_dir"]))
    events = [ev for plane in trace["planes"] if plane["name"] == "/host:CPU"
              for line in plane["lines"] for ev in line["events"]]
    by_name = {}
    for name, start, dur in events:
        by_name.setdefault(name, []).append((start, start + dur))
    assert {"jax.trace", "jax.lower", "jax.compile", "train.dispatch"} <= set(by_name)
    dispatches = by_name["train.dispatch"]
    for name in ("jax.trace", "jax.lower", "jax.compile"):
        # the longest of a name is the step's (eager primitives are annotations too)
        start, end = max(by_name[name], key=lambda iv: iv[1] - iv[0])
        assert any(d0 <= start and end <= d1 for d0, d1 in dispatches), name


def test_trace_cli_prints_a_row_a_compile_and_the_inner_functions(recompile_run, capsys):
    from kubedl_tpu import cli

    assert cli.main(["trace", "tn", "--dir", recompile_run["trace_dir"]]) == 0
    out = capsys.readouterr().out
    table = out[out.index("compiles (as jax.monitoring reported them):"):]
    rows = [line.split() for line in table.splitlines() if line.startswith("train_step")]
    assert [row[-1] for row in rows] == ["1", str(_OddBatchLoader.ODD_AT + 1)]
    for row in rows:
        assert row[5] in ("hit", "miss", "off") and float(row[4]) > 0
    assert "inner functions of train_step's trace" in table
    assert out.index("goodput:") < out.index("compiles (as")


def test_compile_pending_is_gone():
    for rel in ("train/trainer.py", "train/pipeline_trainer.py"):
        with open(os.path.join(ROOT, "kubedl_tpu", rel)) as f:
            text = f.read()
        assert "compile_pending" not in text
        assert "step == start_step" not in text
