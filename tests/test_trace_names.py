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
    assert names == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd",
                     "flash_fwd_streamed", "gmm", "gmm_drhs", "gmm_scaled",
                     "gmm_swiglu", "moe_gather", "ssm_scan_bwd",
                     "ssm_scan_fwd"]
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           "flash_bwd_roofline.train.json")) as f:
        assert "flash_bwd_(dq|dkv)" in json.load(f)["params"]["patterns"]["bwd"]


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
                 "attn_core/flash_attention/flash_bwd_dq/",
                 "attn_core/flash_attention/flash_bwd_dkv/"):
        assert name in text
    # remat keeps the forward kernel's out and lse (llama._remat_policy):
    # the backward recomputes the projections around it, not the kernel
    assert "rematted_computation/attn/" in text
    assert ("rematted_computation/attn/attn_core/flash_attention/flash_fwd/"
            not in text)
    assert "flash_fwd" not in lowered_text[(False, True)]


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
    assert names.count("train.step") == 2 and "trainer.preempted" in names
