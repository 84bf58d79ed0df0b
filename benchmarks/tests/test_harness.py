"""The harness end to end on the CPU at a tiny size: everything behind
the look for a chip. A CPU run proves control flow and `correct`, and
must never print a device metric."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmarks import check, run as R
from benchmarks.reference import llama_ref
from benchmarks.runners import train

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
SEED = 2**31 + 12345  # seeds reach a little over 2**31


def load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.fixture
def cell():
    return load("tiny-cell.json")


@pytest.fixture
def cfg():
    return load("tiny-config.json")


def one_device():
    return jax.devices()[:1]


# -- a sound run ------------------------------------------------------------


def test_run_is_correct_and_reports_no_device_metric(cell, cfg):
    for trace in (False, True):
        res = R.execute(cell, cfg, SEED, 0.5, trace, one_device(), None)
        assert res["correct"] is True
        assert res["attempted"] == res["window"]["steps"] >= 1
        assert res["failed"] == 0
        assert list(res)[-1] == "compared"
        assert res["compared"]["grad_gap"]["value"] < res["compared"]["grad_gap"]["limit"]
        assert res["compared"]["loss_gap"]["limit"] is None
        if trace:
            # the CPU has no peak: every per-layer metric is refused
            assert res["metrics"] == {}
            assert "busy_s" not in res["device"] and "breakdown" not in res
        else:
            assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
            assert res["metrics"]["train_tokens_per_s"]["value"] > 0
        assert res["device"]["platform"] == "cpu"


def test_same_seed_same_inputs(cell, cfg):
    a = train.Run(cell, cfg, SEED, one_device())
    b = train.Run(cell, cfg, SEED, one_device())
    c = train.Run(cell, cfg, SEED + 1, one_device())
    assert (a.next_batch() == b.next_batch()).all()
    assert not (a.next_batch() == c.next_batch()).all()
    a, b = (train.Run(cell, cfg, SEED, one_device()) for _ in range(2))
    a.setup(), b.setup()
    assert a.readings["loss"] == b.readings["loss"]
    rows = a.first_batches[0]
    assert len({r.tobytes() for r in rows}) == len(rows)  # rows all differ


def test_command_line_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "mistral7b-d4-train-8k", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_unknown_device_kind_is_an_error(monkeypatch):
    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    class FakeJax:
        @staticmethod
        def devices():
            return [Dev()]

    with pytest.raises(R.Refused, match="peaks.json"):
        R.find_devices(FakeJax, 1)


def test_reference_is_the_program_in_float32(cell, cfg, monkeypatch):
    """Both sides state the same mathematics: the program run in float32
    on the reference's weights agrees with it to rounding."""
    from benchmarks import weights

    real = weights.maker

    def in_float32(c, shardings=None):
        make = real(c, shardings)
        return lambda seed: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), make(seed))

    monkeypatch.setattr(weights, "maker", in_float32)
    run = train.Run(cell, dict(cfg, torch_dtype="float32"), SEED, one_device())
    run.setup()
    monkeypatch.setattr(weights, "maker", real)
    ref = llama_ref.Reference(cfg, cell, SEED, one_device()).run(run.first_batches, 2)
    values = check.numbers(run.readings, ref)
    assert values["loss_gap"] < 1e-6
    assert values["grad_gap"] < 1e-5
    assert values["change_gap"] < 1e-4


def test_four_virtual_devices_under_fsdp(cell, cfg):
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    cell = dict(cell, chips=4, mesh={"fsdp": 4})
    res = R.execute(cell, cfg, SEED, 0.3, False, jax.devices()[:4], None)
    assert res["correct"] is True
    assert res["device"]["count"] == 4


# -- the control and the faults ------------------------------------------------


def test_lower_precision_control_is_not_correct(cell, cfg):
    """The reference computed in float8 e4m3 and put in the program's
    place fails grad_gap. (At this width the int8 control reads within
    twice the bf16 program's own rounding, 0.8e-3..1.7e-3 against
    0.4e-3..0.6e-3, so the tiny size is held with fp8; at the cells' own
    width int8 reads 5-10 times the program on the chip: PERF.md.)"""
    dev = one_device()
    batches = train.Run(cell, cfg, SEED, dev)
    batches = [batches.next_batch() for _ in range(2)]
    ref = llama_ref.Reference(cfg, cell, SEED, dev).run(batches, 2)
    ctl = llama_ref.Reference(cfg, cell, SEED, dev, mode="fp8").run(batches, 2)
    values = check.numbers(ctl, ref)
    ok, compared = check.decide(values, cell["limits"])
    assert not ok
    assert values["grad_gap"] > cell["limits"]["grad_gap"]
    int8 = llama_ref.Reference(cfg, cell, SEED, dev, mode="int8").run(batches, 2)
    assert check.numbers(int8, ref)["grad_gap"] > 5e-4


def _broken(kind):
    """make_train_step with the step broken underneath the harness."""
    real_make = train.make_train_step

    def make(*args, **kwargs):
        init_state, step = real_make(*args, **kwargs)

        def unchanged(state, batch):
            old = jax.tree_util.tree_map(jnp.copy, state)
            _, metrics = step(state, batch)
            return old, metrics

        def half_batch(state, batch):
            return step(state, batch[: batch.shape[0] // 2])

        def no_exchange(state, batch):
            # every chip keeps the gradient of chip 0's rows alone
            n = batch.shape[0] // 4
            own = jnp.tile(jax.device_get(batch)[:n], (4, 1))
            return step(state, jax.device_put(own, batch.sharding))

        return init_state, {"unchanged": unchanged, "half_batch": half_batch,
                            "no_exchange": no_exchange}[kind]

    return make


@pytest.mark.parametrize("kind,chips", [
    ("unchanged", 1), ("half_batch", 1), ("no_exchange", 4)])
def test_a_broken_step_is_not_correct(cell, cfg, monkeypatch, kind, chips):
    if len(jax.devices()) < chips:
        pytest.skip("needs four (virtual) devices")
    if chips == 4:
        cell = dict(cell, chips=4, mesh={"fsdp": 4})
    monkeypatch.setattr(train, "make_train_step", _broken(kind))
    res = R.execute(cell, cfg, SEED, 0.2, False, jax.devices()[:chips], None)
    assert res["correct"] is False
    c = res["compared"]
    assert (c["grad_gap"]["value"] > c["grad_gap"]["limit"]
            or c["change_gap"]["value"] > c["change_gap"]["limit"])
    if kind == "unchanged":
        assert c["change_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("fault", ["half_batch", "no_exchange"])
def test_a_fault_planted_in_the_reference_is_not_correct(cell, cfg, fault):
    dev = one_device()
    cell4 = dict(cell, chips=4)  # the gradient is cut into four shards
    feed = train.Run(cell, cfg, SEED, dev)
    batches = [feed.next_batch() for _ in range(2)]
    ref = llama_ref.Reference(cfg, cell4, SEED, dev).run(batches, 2)
    bad = llama_ref.Reference(cfg, cell4, SEED, dev, fault=fault).run(batches, 2)
    ok, _ = check.decide(check.numbers(bad, ref), cell["limits"])
    assert not ok


def test_no_limit_proves_nothing():
    ok, compared = check.decide({"grad_gap": 0.0}, {})
    assert not ok and compared["grad_gap"]["limit"] is None
    assert not check.decide({"grad_gap": float("nan")}, {"grad_gap": 1.0})[0]
    assert check.decide({"grad_gap": 0.5, "loss_gap": 9.0}, {"grad_gap": 1.0})[0]


# -- BENCHMARK.json against the files it names -----------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_names_what_is_there():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks"] and 1 <= b["run_seconds"] <= 51
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        with open(os.path.join(ROOT, c["file"])) as f:
            on_disk = json.load(f)
        assert on_disk["reduced"] == c["reduced"] and on_disk["source"] == c["source"]
        assert all(not k.endswith(("_dim", "_rank", "_size")) for k in c["reduced"])
    cells = {}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
        on_disk = R.load_json("workloads", f"{w['name']}.json")
        for k in ("config", "traffic", "chips", "why"):
            assert on_disk[k] == w[k], (w["name"], k)
        assert w["config"] in configs
        assert os.path.isfile(os.path.join(BENCH, "runners", f"{on_disk['runner']}.py"))
        cells[w["name"]] = w
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) == len(cells)
    assert {w["config"] for w in b["workloads"]} == set(configs)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, len(cells) // 4)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    files = {}
    for path in sorted(os.listdir(os.path.join(BENCH, "metrics"))):
        with open(os.path.join(BENCH, "metrics", path)) as f:
            on_disk = json.load(f)
        assert path == on_disk["name"] + ".json"
        files[on_disk["name"]] = on_disk
    assert set(files) == {m["name"] for m in b["per_layer"]}
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        on_disk = files[m["name"]]
        for k in m:
            assert on_disk[k] == m[k], (m["name"], k)
        assert m["moves"] in e2e and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert set(m.get("workloads", [])) <= set(cells)
        assert os.path.isfile(os.path.join(BENCH, "reducers", f"{on_disk['reducer']}.py"))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
