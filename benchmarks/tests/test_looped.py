"""The looped cell's own pieces on the CPU: the FLOP count against a hand
count of the cut, the configuration file against the published widths,
the new metric files on hand-made records, the metric files each cell is
given, and the runner end to end at a tiny size with its controls."""
import json
import os

import jax
import pytest

from benchmarks import check, flops, flops_looped, run as R
from benchmarks.reducers import flash_roofline, mfu_counted, op_time_share
from benchmarks.runners import train_looped

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "ouro-2.6b-d8-train-8k"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 5
NEW = {"mfu_step_looped.train", "flash_fwd_roofline_looped.train",
       "flash_bwd_roofline_looped.train", "flash_time_share_looped.train"}


def load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


def load_metric(name):
    return R.load_json("metrics", f"{name}.json")


@pytest.fixture(scope="module")
def cfg():
    return R.load_json("configs", "ouro-2.6b-d8.json")


def test_cut_is_612m_parameters_and_15_5_gflop_a_token(cfg):
    d, v, ff, layers, passes = 2048, 49152, 5632, 8, 4
    attn, ffn = 4 * d * d, 3 * d * ff  # 16.78M, 34.60M
    assert round((attn + ffn) / 1e6, 2) == 51.38
    hand = layers * (attn + ffn) + 2 * v * d  # embedding and untied head 201.3M
    small = flops_looped.total_params(cfg) - hand  # 33 norms, the gate and its bias
    assert small == (4 * layers + 1) * d + d + 1 and round(small / 1e6, 2) == 0.07
    assert round(flops_looped.total_params(cfg) / 1e6, 1) == 612.4
    assert round(8 * flops_looped.total_params(cfg) / 1e9, 2) == 4.90  # GB of state
    assert round(flops_looped.total_params(dict(cfg, num_hidden_layers=48)) / 1e9, 2) == 2.67
    tokens = 2 * 8192
    f = flops_looped.step_flops(cfg, 2, 8192, passes)
    per_token = {k: x / tokens / 1e9 for k, x in f.items()}
    scores = passes * layers * 2 * (6 * 2 * 16 * 128 * (8192 * 8193 // 2)) / tokens / 1e9
    hand_token = 6 * (passes * layers * (attn + ffn) + passes * (v * d + d)) / 1e9 + scores
    assert abs(per_token["total"] - hand_token) < 1e-3
    assert round(per_token["total"], 1) == 15.5
    assert round(f["total"] / 1e12) == 254
    assert round(100 * per_token["attention"] / per_token["total"]) == 21
    assert round(100 * per_token["heads"] / per_token["total"]) == 16
    # the work follows the passes the step counted, not the stored parameters
    assert flops_looped.step_flops(cfg, 2, 8192, 3)["total"] == pytest.approx(0.75 * f["total"])
    # the dense count of the same file (8 layers run once) reads a quarter
    dense = flops.step_flops(cfg, 2, 8192)["total"]
    assert dense / f["total"] == pytest.approx(0.25, abs=1e-3)


def test_every_width_is_the_published_one(cfg):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["name"] == "Ouro-2.6B")
    assert cfg["source"] == published["source_url"]
    differs = sorted(k for k, v in published["config"].items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == ["layer_types", "num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 8 and cfg["published"]["num_hidden_layers"] == 48
    assert cfg["layer_types"] == published["config"]["layer_types"][:8]
    widths = {"hidden_size": 2048, "intermediate_size": 5632, "num_attention_heads": 16,
              "num_key_value_heads": 16, "head_dim": 128, "vocab_size": 49152,
              "total_ut_steps": 4, "early_exit_threshold": 1, "rms_norm_eps": 1e-6,
              "rope_theta": 1000000, "max_position_embeddings": 65536}
    assert {k: cfg[k] for k in widths} == widths
    for key in ("sandwich_norms", "final_norm_in_loop", "exit_gate", "loss",
                "exit_entropy_beta", "remat", "weights", "optimizer_state"):
        assert cfg["assumed"][key]
    assert "head_dim" not in cfg["assumed"]  # the config has it
    assert cfg["deployment"] and cfg["remat"] == "full"


def hand_trace(durations_us):
    """One device, one operations line; names as a TPU trace gives them."""
    events, t = [], 0
    for name, us in durations_us:
        events.append([f"%{name} = bf16[32,8192,128]{{2,1,0}} custom-call(...)", t, us * 1000])
        t += us * 1000 + 500
    return {"planes": [{"name": "/device:TPU:0",
                        "lines": [{"name": "XLA Ops", "events": events}]}]}


def test_flash_metrics_cost_16_heads_of_128_and_no_window(cfg):
    fmt = {"device_plane": r"^/device:TPU:\d+$", "op_lines": ["XLA Ops"]}
    cell = R.load_json("workloads", f"{CELL}.json")
    fwd_cost = flops.flash_call_cost(cfg, 2, 8192, "fwd")
    assert fwd_cost["flops"] == 2 * 2 * 2 * 16 * 128 * (8192 * 8193 // 2)
    fwd_least = fwd_cost["flops"] / PEAK["bf16_flops_per_s"]  # FLOP-bound
    assert fwd_least > fwd_cost["bytes"] / PEAK["hbm_bytes_per_s"]
    bwd_least = flops.flash_call_cost(cfg, 2, 8192, "bwd")["flops"] / PEAK["bf16_flops_per_s"]
    # one instruction in the loop's body, an event an execution: two forward
    # calls at twice their least time, one backward pair
    ops = [("flash_fwd.2", 2 * fwd_least * 1e6), ("flash_fwd.2", 2 * fwd_least * 1e6),
           ("flash_bwd_dq.2", 9000), ("flash_bwd_dkv.2", 12000), ("fusion.7", 1000)]
    ctx = {"trace": hand_trace(ops), "fmt": fmt, "cfg": cfg, "cell": cell, "peak": PEAK,
           "traced": {"steps": 1, "window_s": 0.1}}
    fwd = flash_roofline.reduce(ctx, load_metric("flash_fwd_roofline_looped.train")["params"])
    assert fwd == pytest.approx(50.0, rel=1e-6)
    bwd = flash_roofline.reduce(ctx, load_metric("flash_bwd_roofline_looped.train")["params"])
    assert bwd == pytest.approx(100 * bwd_least / 0.021, rel=1e-6)
    share = op_time_share.reduce(ctx, load_metric("flash_time_share_looped.train")["params"])
    assert share == pytest.approx(100 * (4 * fwd_least + 0.021) / 0.1, rel=1e-3)
    # a trace without the kernels, or no trace: nothing, not an error
    none = dict(ctx, trace=hand_trace([("fusion.1", 10)]))
    for name in NEW - {"mfu_step_looped.train"}:
        m = load_metric(name)
        reducer = flash_roofline if m["reducer"] == "flash_roofline" else op_time_share
        assert reducer.reduce(none, m["params"]) is None
        assert reducer.reduce(dict(ctx, trace=None), m["params"]) is None


def test_counted_mfu_reads_the_runners_record(cfg):
    per_step = flops_looped.step_flops(cfg, 2, 8192, 4)["total"]
    ctx = {"cell": {"chips": 1}, "peak": PEAK,
           "window": {"required_flops": 5 * per_step, "elapsed_s": 11.5}}
    assert load_metric("mfu_step_looped.train")["reducer"] == "mfu_counted"
    assert mfu_counted.reduce(ctx, {}) == pytest.approx(
        100 * 5 * 253.99e12 / 11.5 / 197e12, rel=1e-4)
    # the parent's record holds no count: the metric is left out, not raised
    assert mfu_counted.reduce(dict(ctx, window={"elapsed_s": 11.5}), {}) is None


def test_each_cell_is_given_its_own_metrics():
    reported = {"train_tokens_per_s", "setup_s"}
    shared = {"step_ms_median.train", "step_device_ms.train", "device_idle_share.train",
              "peak_hbm_gib.train",
              # lists no cells, so every training cell is handed to it; its dense
              # count of 8 layers run once reads this cell at a quarter of its
              # share (PERF.md, Open question 22)
              "mfu_step.train"}
    assert {m["name"] for m in R.metric_files(CELL, reported)} == NEW | shared
    for cell in ("mistral7b-d4-train-8k", "mistral7b-d4-train-512",
                 "mistral7b-d8x4-train-8k", "lfm2-8b-a1b-d9e8-train-8k"):
        assert not {m["name"] for m in R.metric_files(cell, reported)} & NEW
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        on_disk = load_metric(name)
        assert {k: on_disk[k] for k in by_name[name]} == by_name[name]
    assert [m["name"] for m in bench["per_layer"]][-4:] == [
        "mfu_step_looped.train", "flash_fwd_roofline_looped.train",
        "flash_bwd_roofline_looped.train", "flash_time_share_looped.train"]
    assert bench["configs"][-1]["name"] == "ouro-2.6b-d8"
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "ouro-2.6b-d8", "traffic": "train-8k", "chips": 1,
        "why": R.load_json("workloads", f"{CELL}.json")["why"]}


def test_cell_file_is_the_cell_the_issue_names():
    cell = R.load_json("workloads", f"{CELL}.json")
    assert (cell["runner"], cell["batch"], cell["seen_len"], cell["mesh"]) == (
        "train_looped", 2, 8192, {"fsdp": 1})
    assert cell["optimizer"]["learning_rate"] == 3e-4 and "restore_every" not in cell
    assert cell["reference"] == {"steps": 2, "row_block": 1}
    assert set(cell["limits"]) == {"grad_gap", "grad_gap_median", "change_gap", "exit_mass_gap"}
    assert len(cell["why"]) <= 200 and cell["limits_from"]


# -- the runner end to end at a tiny size ------------------------------------------


def test_looped_runner_end_to_end_at_a_tiny_size():
    cell, cfg = load("tiny-looped-cell.json"), load("tiny-looped-config.json")
    res = R.execute(cell, cfg, SEED, 0.3, False, jax.devices()[:1], None)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["compared"]["loss_gap"]["limit"] is None
    for name in cell["limits"]:
        assert res["compared"][name]["value"] <= res["compared"][name]["limit"]
    win = res["window"]
    steps, c = win["steps"], win["counters"]
    assert c["loop_passes"] == 4 * steps and c["loop_layer_applications"] == 8 * steps
    assert sum(c[f"loop_exit_mass_{t}"] for t in range(1, 5)) == pytest.approx(1.0, abs=1e-4)
    assert 0 < c["loop_exit_entropy"] < 1.3863 and c["loop_ce_4"] > 0
    want = flops_looped.step_flops(cfg, cell["batch"], cell["seen_len"], 4)["total"] * steps
    assert win["required_flops"] == pytest.approx(want)


@pytest.fixture(scope="module")
def tiny_run():
    """The tiny cell after set-up, with its float32 reference's readings."""
    cell, cfg = load("tiny-looped-cell.json"), load("tiny-looped-config.json")
    run = train_looped.Run(cell, cfg, SEED, jax.devices()[:1])
    run.setup()
    return run, run.reference()


@pytest.mark.parametrize("control,fails_by", [
    ({"mode": "fp8"}, ("grad_gap", "grad_gap_median")),
    ({"fault": "passes=3"}, ("grad_gap", "exit_mass_gap")),
    ({"fault": "half_batch"}, ("grad_gap", "change_gap"))])
def test_a_control_in_the_programs_place_is_not_correct(tiny_run, control, fails_by):
    run, ref = tiny_run
    limits = run.cell["limits"]
    sound, _ = check.decide(train_looped.numbers(run.readings, ref), limits)
    assert sound
    ok, compared = check.decide(
        train_looped.numbers(run.reference(**control), ref), limits)
    assert not ok
    for name in fails_by:
        assert compared[name]["value"] > limits[name], name
    # what Run.verify(mode=...) does: the control in the program's place
    if "mode" in control:
        ok, compared_v = run.verify(**control)
        assert not ok and compared_v["grad_gap"] == compared["grad_gap"]


def test_exit_mass_gap_sees_a_pass_the_program_did_not_run(tiny_run):
    run, ref = tiny_run
    assert len(run.readings["exit_mass"]) == 2 and len(run.readings["exit_mass"][0]) == 4
    sound = train_looped.numbers(run.readings, ref)["exit_mass_gap"]
    short = dict(run.readings, exit_mass=[m[:3] for m in run.readings["exit_mass"]])
    gap = train_looped.numbers(short, ref)["exit_mass_gap"]
    assert sound < 0.005 < 0.1 < gap == pytest.approx(ref["exit_mass"][0][3], abs=0.02)


def test_what_the_program_lacks_is_refused(monkeypatch):
    cfg = load("tiny-looped-config.json")
    with pytest.raises(ValueError, match="early_exit_threshold"):
        train_looped.looped_config(dict(cfg, early_exit_threshold=0.5), 128)
    with pytest.raises(ValueError, match="window"):
        train_looped.looped_config(dict(cfg, sliding_window=64), 128)
    # a program with no looped stack (the parent commit): at once, by name
    monkeypatch.delattr(train_looped.llama.LlamaConfig, "total_ut_steps")
    with pytest.raises(SystemExit, match="no looped stack"):
        train_looped.looped_config(cfg, 128)
