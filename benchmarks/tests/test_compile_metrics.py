"""The four metrics that read the program's compile log
(`reducers/compile_log.py`), on a hand-made log: what each file sums,
what it leaves out, what it says where there is nothing to read, and
each file against its entry in `BENCHMARK.json`. One run of a real jitted
`train_step` through the process's own log closes the loop on the CPU."""
import json
import os

import pytest

from benchmarks import run as R
from benchmarks.reducers import compile_log

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = {"step_trace_s.train": ("trace_s", "s", "program_span", "pod start-up", "setup_s"),
       "step_lower_s.train": ("lower_s", "s", "program_span", "pod start-up", "setup_s"),
       "step_executable_s.train": ("executable_s", "s", "program_span", "pod start-up",
                                   "setup_s"),
       "step_compiles.train": ("count", "count", "program_counter", "trainer step",
                               "train_tokens_per_s")}
CELLS = ("mistral7b-d4-train-8k", "mistral7b-d4-train-512", "mistral7b-d8x4-train-8k",
         "lfm2-8b-a1b-d9e8-train-8k", "ouro-2.6b-d8-train-8k",
         "granite-4.0-h-micro-d10-train-8k")


def rec(fun, trace_s, lower_s, executable_s, cache="hit"):
    return {"fun": fun, "trace_s": trace_s, "lower_s": lower_s,
            "executable_s": executable_s, "cache": cache, "cache_read_s": 0.0, "ts": 0.0}


# set-up's compile of the step, a second trace of it in the window, and
# what else a run compiles: the state, the readings, the reference
HAND = [rec("init_state", 0.4, 0.3, 1.1), rec("train_step", 3.7, 3.4, 3.6),
        rec("<lambda>", 0.1, 0.1, 0.2), rec("train_step", 0.25, 0.125, 0.5, "miss"),
        rec("reference_train_step_f32", 9.0, 9.0, 9.0), rec("_train_step", 7.0, 7.0, 7.0)]


def reduce(name, records):
    m = R.load_json("metrics", f"{name}.json")
    assert m["reducer"] == "compile_log"
    return compile_log.reduce({"compile_log": records}, m["params"])


@pytest.mark.parametrize("name,want", [
    ("step_trace_s.train", 3.95), ("step_lower_s.train", 3.525),
    ("step_executable_s.train", 4.1), ("step_compiles.train", 2)])
def test_each_metric_sums_its_field_over_the_step_alone(name, want):
    assert reduce(name, HAND) == want
    # one trace of the step: its own numbers, and the floor of the count
    assert reduce(name, HAND[:2]) == HAND[1].get(NEW[name][0], 1)


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_where_no_record_is_the_steps(name):
    assert reduce(name, []) is None
    assert reduce(name, [HAND[0], HAND[2], HAND[4], HAND[5]]) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_file_and_entry_agree_and_every_cell_is_given_it(name):
    field, unit, source, layer, moves = NEW[name]
    on_disk = R.load_json("metrics", f"{name}.json")
    assert on_disk["params"] == {"fun": "^train_step$", "field": field}
    assert (on_disk["unit"], on_disk["better"], on_disk["source"], on_disk["layer"],
            on_disk["moves"]) == (unit, "lower", source, layer, moves)
    assert "workloads" not in on_disk and on_disk["what"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry == {k: on_disk[k] for k in
                     ("name", "unit", "better", "source", "layer", "moves")}
    assert moves in {m["name"] for m in bench["end_to_end"]}
    for cell in CELLS:
        assert name in {m["name"] for m in R.metric_files(
            cell, {"train_tokens_per_s", "setup_s"})}


def test_a_program_without_the_log_reads_nothing(monkeypatch):
    """The parent of the PR that added the log: the import fails, the
    reducer returns nothing and does not raise."""
    import sys

    import kubedl_tpu.obs

    monkeypatch.setitem(sys.modules, "kubedl_tpu.obs.compiles", None)
    monkeypatch.delattr(kubedl_tpu.obs, "compiles", raising=False)
    for name in NEW:
        m = R.load_json("metrics", f"{name}.json")
        assert compile_log.reduce({}, m["params"]) is None


def test_the_process_log_is_read_where_no_log_is_handed_in():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubedl_tpu.obs import compiles

    log = compiles.install()
    before = len(log.records("train_step"))

    def train_step(x):
        for _ in range(200):  # enough equations to pass the log's 0.1 s
            x = jnp.tanh(x) @ x
        return x

    jax.jit(train_step)(np.ones((8, 8), np.float32)).block_until_ready()
    assert len(log.records("train_step")) == before + 1
    m = R.load_json("metrics", "step_compiles.train.json")
    assert compile_log.reduce({}, m["params"]) == before + 1
    m = R.load_json("metrics", "step_trace_s.train.json")
    assert compile_log.reduce({}, m["params"]) > 0
