"""The Trinity cell's own pieces on the CPU: the FLOP count against a hand
count of the cut, every key against the catalog's row, the new reducer
and metric files on hand-made records, and the runner end to end at a
tiny size with its controls and faults."""
import json
import os

import jax
import pytest

from benchmarks import check, flops, flops_afmoe, flops_hybrid, run as R
from benchmarks.reducers import (counter_ratio, flash_roofline_mixed, gmm_roofline, mfu_counted,
                                 op_time_share)
from benchmarks.reference import trinity_ref
from benchmarks.runners import train_afmoe

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "trinity-large-preview-d5e8-train-8k"
CONFIG = "trinity-large-preview-d5e8"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = {"mfu_step_afmoe.train", "flash_fwd_roofline_mixed.train",
               "flash_bwd_roofline_mixed.train", "flash_time_share_mixed.train",
               "gmm_time_share_afmoe.train", "gmm_roofline_afmoe.train",
               "moe_live_tile_share_afmoe.train"}
SEED = 2**31 + 77
S, F = "sliding_attention", "full_attention"


def load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


def load_metric(name):
    return R.load_json("metrics", f"{name}.json")


@pytest.fixture(scope="module")
def cfg():
    return R.load_json("configs", f"{CONFIG}.json")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_cut_is_1604m_parameters_5_02_gflop_a_token_41_tflop_a_step(cfg):
    d, v, hd = 3072, 25088, 128
    attn = 3 * d * 48 * hd + 2 * d * 8 * hd  # q, o and the gate; k, v: 62.9M
    dense = attn + 3 * d * 12288
    shared = expert = 3 * d * 3072  # 28.3M
    fixed = attn + d * 256 + shared  # an expert layer outside its routed experts
    head = d * v
    hand = dense + 4 * (fixed + 8 * expert) + 2 * head
    small = flops_afmoe.total_params(cfg) - hand  # norms and the routers' biases
    assert small == 5 * (2 * hd + 4 * d) + 4 * 256 + d
    assert flops_afmoe.total_params(cfg) == 1_604_388_096
    assert round(8 * flops_afmoe.total_params(cfg) / 1e9, 2) == 12.84  # GB
    whole = dict(cfg, num_hidden_layers=60, num_dense_layers=6, num_experts=256,
                 vocab_size=200192)
    assert flops_afmoe.total_params(whole) == 398_635_286_016
    tokens = 8192
    rows = flops_afmoe.expert_layers(cfg) * flops_afmoe.uniform_rows_held(cfg, tokens)
    assert rows == 4 * tokens * 4 * 8 / 256  # 128 rows an expert
    f = flops_afmoe.step_flops(cfg, 1, 8192, rows)
    weights = dense + 4 * (fixed + expert * 8 * 4 / 256) + head
    assert round(weights / 1e6, 1) == 635.4
    windowed, full = 3072.25, 4096.5  # keys a query attends on the mean
    assert flops.attended_keys(8192, 4096) / 8192 == windowed
    scores = (4 * windowed + full) * 2 * 6 * 48 * hd  # 73,728 FLOPs a key and token
    assert f["attention"] == pytest.approx(scores * tokens, rel=1e-12)
    per_token = f["total"] / tokens / 1e9
    assert per_token == pytest.approx(6 * weights / 1e9 + scores / 1e9, rel=1e-9)
    assert round(per_token, 2) == 5.02 and round(f["total"] / 1e12, 1) == 41.1
    share = lambda flops_a_token: round(flops_a_token * tokens / f["total"], 3)
    assert share(6 * 5 * attn + scores) == 0.617  # attention with projections, gate, scores
    assert share(6 * 5 * d * 48 * hd) == 0.113  # the gate's projection
    assert share(6 * 4 * shared) == share(6 * 3 * d * 12288) == 0.135
    assert share(6 * head) == 0.092 and share(6 * 4 * expert / 8) == 0.017
    # the dense count of mfu_step.train reads this file otherwise (no gate, no
    # experts, every layer at the one window)
    assert flops.step_flops(cfg, 1, 8192)["total"] != pytest.approx(f["total"], rel=0.05)


def test_every_key_is_the_catalog_rows(cfg, bench):
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 3072, "intermediate_size": 12288,
        "layer_types": [F if i % 4 == 3 else S for i in range(60)],
        "load_balance_coeff": 5e-05, "max_position_embeddings": 262144, "model_type": "afmoe",
        "moe_intermediate_size": 3072, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 48, "num_dense_layers": 6, "num_expert_groups": 1,
        "num_experts": 256, "num_experts_per_tok": 4, "num_hidden_layers": 60,
        "num_key_value_heads": 8, "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.448, "score_func": "sigmoid", "sliding_window": 4096,
        "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
        "vocab_size": 200192}
    differs = sorted(k for k, v in published.items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size", "layer_types"])
    assert {k: cfg["published"][k] for k in differs if k != "layer_types"} == {
        k: published[k] for k in differs if k != "layer_types"}
    # the layers kept: published 0 and 6-9
    assert cfg["layer_types"] == [published["layer_types"][i] for i in (0, 6, 7, 8, 9)]
    assert cfg["router_outputs"] == 256 and cfg["first_expert"] == 0
    assert cfg["vocab_size"] == 196 * 128 and cfg["vocab_size"] % (128 * cfg["ce_chunks"]) == 0
    for key in ("assumed", "deployment"):
        assert cfg[key]
    for word in ("NoPE", "elementwise", "sqrt(3072)", "no gradient and no update", "SMEBU",
                 "1e-20", "0.0913", "the only muP multiplier"):
        assert word in json.dumps(cfg["assumed"]), word
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and sorted(entry["reduced"]) == differs
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "train-8k", 1)
    on_disk = R.load_json("workloads", f"{CELL}.json")
    assert on_disk["why"] == cell["why"] and on_disk["runner"] == "train_afmoe"
    assert (on_disk["batch"], on_disk["seen_len"], on_disk["restore_every"]) == (1, 8192, 5)
    for name in on_disk["limits"]:
        assert name in on_disk["limits_from"], name


def hand_trace(durations_us):
    """One device, one operations line; names as a TPU trace gives them."""
    events, t = [], 0
    for name, us in durations_us:
        events.append([f"%{name} = bf16[48,8192,128]{{2,1,0}} custom-call(...)", t, us * 1000])
        t += us * 1000 + 500
    return {"planes": [{"name": "/device:TPU:0",
                        "lines": [{"name": "XLA Ops", "events": events}]}]}


def test_flash_rooflines_cost_each_call_at_the_mean_of_the_layers_windows(cfg):
    fmt = {"device_plane": r"^/device:TPU:\d+$", "op_lines": ["XLA Ops"]}
    cell = R.load_json("workloads", f"{CELL}.json")
    fwd = flops_afmoe.flash_call_cost(cfg, 1, 8192, "fwd")
    bwd = flops_afmoe.flash_call_cost(cfg, 1, 8192, "bwd")
    keys = (4 * flops.attended_keys(8192, 4096) + flops.attended_keys(8192, None)) / 5
    assert fwd["flops"] == pytest.approx(2 * 2 * 48 * 128 * keys, rel=1e-12)
    assert bwd["flops"] == pytest.approx(5 * 2 * 48 * 128 * keys, rel=1e-12)
    lane = 48 * 8192 * 128 * 2
    assert fwd["bytes"] == 4 * lane + 48 * 8192 * 4
    assert bwd["bytes"] == 8 * lane + 2 * 48 * 8192 * 4
    t_fwd, t_bwd = fwd["flops"] / 197e12, bwd["flops"] / 197e12
    assert t_fwd > fwd["bytes"] / 819e9 and t_bwd > bwd["bytes"] / 819e9
    ops = [(f"flash_fwd.{i}", 2 * t_fwd * 1e6) for i in range(5)]
    ops += [("flash_bwd_dq.2", 9000), ("flash_bwd_dkv.2", 13000), ("gmm.7", 1000),
            ("fusion.9", 9000)]
    ctx = {"trace": hand_trace(ops), "fmt": fmt, "cfg": cfg, "cell": cell, "peak": PEAK,
           "traced": {"steps": 1, "window_s": 0.2}}
    params = load_metric("flash_fwd_roofline_mixed.train")["params"]
    assert flash_roofline_mixed.reduce(ctx, params) == pytest.approx(50.0, rel=1e-3)
    got = flash_roofline_mixed.reduce(ctx, load_metric("flash_bwd_roofline_mixed.train")["params"])
    assert got == pytest.approx(100 * t_bwd / 0.022, rel=1e-3) and 20 < got < 60
    share = op_time_share.reduce(ctx, load_metric("flash_time_share_mixed.train")["params"])
    assert share == pytest.approx(100 * (10 * t_fwd + 0.022) / 0.2, rel=1e-3)
    # a configuration of one window (a Mistral cell's) or of other layer
    # kinds (the LFM2 cell's), no trace, or a trace without the kernels:
    # nothing, and no error
    for other in ("mistral7b-d4", "lfm2-8b-a1b-d9e8"):
        assert flash_roofline_mixed.reduce(
            dict(ctx, cfg=R.load_json("configs", f"{other}.json")), params) is None
    assert flash_roofline_mixed.reduce(dict(ctx, trace=None), params) is None
    assert flash_roofline_mixed.reduce(
        dict(ctx, trace=hand_trace([("fusion.1", 10)])), params) is None


def test_grouped_matmul_metrics_read_the_cells_four_expert_layers(cfg):
    """The LFM2 cell's reducers on this configuration: each gmm call costed
    at the mean rows a layer and step, which at 128 rows an expert is
    bound by the held experts' bytes; the live tiles over the grid's."""
    fmt = {"device_plane": r"^/device:TPU:\d+$", "op_lines": ["XLA Ops"]}
    rows = 4 * 1024.0  # rows held over the four expert layers of one step
    cost = flops_hybrid.gmm_call_cost(cfg, "gmm", 1024.0)
    assert cost["flops"] == 2 * 1024 * 3072 * 3072
    assert cost["bytes"] == (1024 * 6144 + 8 * 3072 * 3072) * 2
    least = flops.roofline_seconds(cost["flops"], cost["bytes"], PEAK)["seconds"]
    assert least == pytest.approx(cost["bytes"] / 819e9)
    ops = [(f"gmm.{i}", 2 * least * 1e6) for i in range(7)] + [("fusion.3", 1000)]
    ctx = {"trace": hand_trace(ops), "fmt": fmt, "cfg": cfg, "peak": PEAK,
           "traced": {"steps": 1, "window_s": 0.1,
                      "counters": {"moe_rows_held": rows, "gmm_live_tiles": 640.0,
                                   "gmm_grid_tiles": 5760.0}}}
    assert gmm_roofline.reduce(ctx, load_metric("gmm_roofline_afmoe.train")["params"]) == \
        pytest.approx(50.0, rel=1e-3)
    share = op_time_share.reduce(ctx, load_metric("gmm_time_share_afmoe.train")["params"])
    assert share == pytest.approx(100 * 14 * least / 0.1, rel=1e-3)
    tiles = counter_ratio.reduce(ctx, load_metric("moe_live_tile_share_afmoe.train")["params"])
    assert tiles == pytest.approx(100 * 640 / 5760)


def test_counted_mfu_reads_the_runners_record():
    ctx = {"cell": {"chips": 1}, "peak": PEAK,
           "window": {"required_flops": 197e12 * 3, "elapsed_s": 10.0}}
    assert load_metric("mfu_step_afmoe.train")["reducer"] == "mfu_counted"
    assert mfu_counted.reduce(ctx, {}) == pytest.approx(30.0)


def test_the_cell_is_given_its_own_metrics_and_no_other_cell_is(bench):
    reported = {"train_tokens_per_s", "setup_s"}
    names = {m["name"] for m in R.metric_files(CELL, reported)}
    shared = {"step_ms_median.train", "step_device_ms.train", "device_idle_share.train",
              "peak_hbm_gib.train", "mfu_step.train", "step_trace_s.train",
              "step_lower_s.train", "step_executable_s.train", "step_compiles.train"}
    assert NEW_METRICS <= names and names - NEW_METRICS <= shared
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert load_metric(name)["workloads"] == [CELL]
        assert {k: by_name[name][k] for k in ("unit", "better", "source", "layer", "moves")} == {
            k: load_metric(name)[k] for k in ("unit", "better", "source", "layer", "moves")}
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert not NEW_METRICS & {m["name"] for m in R.metric_files(w["name"], reported)}


def test_afmoe_runner_end_to_end_at_a_tiny_size():
    cell, cfg = load("tiny-afmoe-cell.json"), load("tiny-afmoe-config.json")
    res = R.execute(cell, cfg, SEED, 0.3, False, jax.devices()[:1], None)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for name in cell["limits"]:
        assert res["compared"][name]["value"] <= res["compared"][name]["limit"]
    assert res["compared"]["loss_gap"]["limit"] is None
    win = res["window"]
    steps, c = win["steps"], win["counters"]
    assert c["moe_rows_routed"] == steps * 4 * 2 * cell["batch"] * cell["seen_len"]
    assert 0.3 < c["moe_rows_held"] / c["moe_rows_routed"] < 0.7  # 4 of 8 held
    assert c["attn_windowed_layers"] == 4 and c["attn_nope_layers"] == 1
    assert 0.3 < c["attn_gate_mean"] < 0.7
    want = flops_afmoe.step_flops(cfg, cell["batch"], cell["seen_len"],
                                  c["moe_rows_held"] / steps)["total"] * steps
    assert win["required_flops"] == pytest.approx(want)
    assert win["restores"] == (steps - 1) // cell["restore_every"]


@pytest.fixture(scope="module")
def tiny_run():
    cell, cfg = load("tiny-afmoe-cell.json"), load("tiny-afmoe-config.json")
    run = train_afmoe.Run(cell, cfg, SEED, jax.devices()[:1])
    run.setup()
    return run, run.reference()


@pytest.mark.parametrize("control,by", [
    ({"mode": "fp8"}, "grad_gap_median"), ({"fault": "half_batch"}, "grad_gap_median"),
    ({"fault": "no_gate"}, "attn_gate_gap"), ({"fault": "rope_on_full"}, "grad_gap_steady"),
    ({"fault": "window_on_full"}, "grad_gap_steady")],
    ids=["fp8", "half_batch", "no_gate", "rope_on_full", "window_on_full"])
def test_a_control_or_a_fault_in_the_programs_place_is_not_correct(tiny_run, control, by):
    run, ref = tiny_run
    limits = run.cell["limits"]
    assert check.decide(train_afmoe.numbers(run.readings, ref), limits)[0]
    ok, compared = check.decide(train_afmoe.numbers(run.reference(**control), ref), limits)
    assert not ok
    assert compared[by]["value"] > limits[by]
    if control.get("fault") == "no_gate":  # a gradient the fault zeroes
        assert compared["change_gap"]["value"] > limits["change_gap"]


def test_what_the_program_lacks_is_refused():
    cfg = load("tiny-afmoe-config.json")
    for key, value in (("score_func", "softmax"), ("n_group", 2), ("hidden_act", "gelu"),
                       ("route_norm", False), ("tie_word_embeddings", True),
                       ("rope_scaling", {"type": "yarn"}), ("mup_enabled", False)):
        with pytest.raises(ValueError, match=key):
            train_afmoe.afmoe_config(dict(cfg, **{key: value}), 128)
    with pytest.raises(ValueError, match="chunked_attention"):
        train_afmoe.afmoe_config(dict(cfg, layer_types=["chunked_attention"] * 5), 128)


def test_half_a_batch_of_one_row_is_half_its_positions():
    """At one sequence a step (the Trinity cell's batch) half a batch is
    planted as the second half of the positions left out of the loss: a
    step that trains on part of its tokens fails by the median leaf."""
    cell, cfg = load("tiny-afmoe-cell.json"), load("tiny-afmoe-config.json")
    cell = dict(cell, batch=1)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 129), 0, cfg["vocab_size"])
    batches = [jax.device_get(tokens)] * 2
    ref = trinity_ref.Reference(cfg, cell, SEED, jax.devices()[:1])
    half = trinity_ref.Reference(cfg, cell, SEED, jax.devices()[:1], fault="half_batch")
    assert half._kept(batches[0]) == 64 and ref._kept(batches[0]) == 128
    sound, wrong = ref.run(batches, 2), half.run(batches, 2)
    values = train_afmoe.numbers(wrong, sound)
    assert values["grad_gap_median"] > 10 * cell["limits"]["grad_gap_median"]
    assert wrong["loss"][0] != sound["loss"][0]
    assert values["attn_gate_gap"] == 0.0  # the forward pass is the sound one
