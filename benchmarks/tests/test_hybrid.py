"""The hybrid cell's own pieces on the CPU: the FLOP count against a hand
count of the cut, the new reducers on hand-made records, the metric files
each cell is given, and the runner end to end at a tiny size."""
import copy
import json
import os

import jax
import pytest

from benchmarks import check, flops, flops_hybrid, run as R
from benchmarks.reducers import (counter_ratio, flash_roofline, gmm_roofline,
                                 mfu_counted, op_time_share)
from benchmarks.runners import train_hybrid

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "lfm2-8b-a1b-d9e8-train-8k"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return R.load_json("configs", "lfm2-8b-a1b-d9e8.json")


def test_cut_is_921m_parameters_and_2_03_gflop_a_token(cfg):
    d, v, ffd, ffe = 2048, 16384, 7168, 1792
    conv = d * 3 * d + d * d  # 16.78M
    attn = 2 * d * d + 2 * d * 512  # 10.49M
    layer0 = conv + 3 * d * ffd  # 60.8M
    mixers = 2 * attn + 6 * conv  # 121.7M
    routers = 8 * d * 32  # 0.5M
    experts = 8 * 8 * 3 * d * ffe  # 704.6M
    embed = v * d  # 33.6M
    hand = layer0 + mixers + routers + experts + embed
    assert round(hand / 1e6) == 921
    small = flops_hybrid.total_params(cfg) - hand  # norms, taps, biases
    assert 0 < small < 0.2e6
    assert round(8 * flops_hybrid.total_params(cfg) / 1e9, 1) == 7.4  # GB
    tokens = 16384
    rows = 8 * flops_hybrid.uniform_rows_held(cfg, tokens)  # 8 layers x k*S/4
    assert rows == 8 * tokens
    f = flops_hybrid.step_flops(cfg, 2, 8192, rows)
    per_token = {k: v / tokens / 1e9 for k, v in f.items()}
    scores = 2 * 6 * 2 * 32 * 64 * (8192 * 8193 // 2) * 2 / tokens / 1e9  # 2 layers, 2 rows
    hand_token = (6 * (layer0 + mixers + routers + embed) + 6 * 8 * 3 * d * ffe) / 1e9 + scores
    assert abs(per_token["total"] - hand_token) < 1e-3
    assert round(per_token["total"], 2) == 2.03
    assert round(per_token["experts"], 2) == 0.53
    assert round(per_token["attention"], 2) == 0.20
    assert round(f["total"] / 1e12) == 33
    # a dense count of the same file reads about twice the true work
    dense = flops.step_flops(cfg, 2, 8192)["total"] / tokens / 1e9
    assert 3.9 < dense < 4.2


def test_every_width_is_the_published_one(cfg):
    published = {"hidden_size": 2048, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "intermediate_size": 7168,
                 "moe_intermediate_size": 1792, "num_experts_per_tok": 4,
                 "router_outputs": 32, "conv_L_cache": 3, "rope_theta": 1000000,
                 "norm_eps": 1e-5, "max_position_embeddings": 128000}
    assert {k: cfg[k] for k in published} == published
    assert flops.head_dim(cfg) == 64
    assert sorted(cfg["reduced"]) == sorted(
        k for k, v in cfg["published"].items() if k in cfg and cfg[k] != v)
    kept = [cfg["published"]["layer_types"][i] for i in (0, 2, 3, 4, 5, 6, 7, 8, 9)]
    assert cfg["layer_types"] == kept
    for key in ("assumed", "deployment"):
        assert cfg[key]


def hand_trace(durations_us):
    """One device, one operations line; names as a TPU trace gives them."""
    events, t = [], 0
    for name, us in durations_us:
        events.append([f"%{name} = bf16[512,2048]{{1,0}} custom-call(...)", t, us * 1000])
        t += us * 1000 + 500
    return {"planes": [{"name": "/device:TPU:0",
                        "lines": [{"name": "XLA Ops", "events": events}]}]}


def test_gmm_roofline_on_a_hand_made_trace(cfg):
    fmt = {"device_plane": r"^/device:TPU:\d+$", "op_lines": ["XLA Ops"]}
    pattern = load_metric("gmm_roofline.train")["params"]["pattern"]
    rows = 16384.0  # a layer's rows at uniform routing
    plain = 2 * rows * 2048 * 1792 / 197e12  # FLOP-bound: 0.61 ms
    byts = (rows * (2048 + 1792) + 8 * 2048 * 1792) * 2 / 819e9
    assert plain > byts
    ops = [("gmm_swiglu.3", 2000), ("gmm.7", 1000), ("gmm.12", 1000),
           ("gmm_drhs.2", 1500), ("fusion.9", 9000), ("flash_fwd.1", 4000),
           ("gmm_swiglu_fusion.4", 7000)]
    ctx = {"trace": hand_trace(ops), "fmt": fmt, "cfg": cfg, "peak": PEAK,
           "traced": {"steps": 1, "counters": {"moe_rows_held": rows * 8}}}
    least = (2 + 1 + 1 + 1) * plain
    spent = (2000 + 1000 + 1000 + 1500) / 1e6
    got = gmm_roofline.reduce(ctx, {"pattern": pattern})
    assert abs(got - 100 * least / spent) < 1e-9
    assert 50 < got < 60
    # half the rows, half the least time: the count is the step's, not k*S
    ctx["traced"]["counters"]["moe_rows_held"] = rows * 4
    assert abs(gmm_roofline.reduce(ctx, {"pattern": pattern}) - got / 2) < 1e-9
    # a program that counts no rows, or a trace without the kernels: nothing
    assert gmm_roofline.reduce(dict(ctx, traced={"steps": 1}), {"pattern": pattern}) is None
    none = dict(ctx, trace=hand_trace([("fusion.1", 10)]),
                traced={"steps": 1, "counters": {"moe_rows_held": 1.0}})
    assert gmm_roofline.reduce(none, {"pattern": pattern}) is None
    assert gmm_roofline.reduce(dict(ctx, trace=None), {"pattern": pattern}) is None


def test_flash_rooflines_cost_the_models_own_head_size(cfg):
    """The kernels run at the padded head size; the least time is that of
    64: a forward that took exactly the padded FLOPs' time reads a half."""
    fmt = {"device_plane": r"^/device:TPU:\d+$", "op_lines": ["XLA Ops"]}
    cell = R.load_json("workloads", f"{CELL}.json")
    padded = dict(cfg, hidden_size=4096)  # 32 heads of 128
    assert flops.head_dim(padded) == 128
    at_128 = flops.flash_call_cost(padded, 2, 8192, "fwd")["flops"] / PEAK["bf16_flops_per_s"]
    ops = [("flash_fwd.2", at_128 * 1e6), ("flash_fwd.3", at_128 * 1e6),
           ("flash_bwd_dq.2", 9000), ("flash_bwd_dkv.2", 12000), ("gmm.7", 1000)]
    ctx = {"trace": hand_trace(ops), "fmt": fmt, "cfg": cfg, "cell": cell, "peak": PEAK,
           "traced": {"steps": 1, "window_s": 0.1}}
    fwd = load_metric("flash_fwd_roofline_hd64.train")
    assert flash_roofline.reduce(ctx, fwd["params"]) == pytest.approx(50.0, rel=1e-3)
    bwd = load_metric("flash_bwd_roofline_hd64.train")
    least = flops.flash_call_cost(cfg, 2, 8192, "bwd")["flops"] / PEAK["bf16_flops_per_s"]
    assert flash_roofline.reduce(ctx, bwd["params"]) == pytest.approx(100 * least / 0.021, rel=1e-3)
    share = load_metric("flash_time_share_hd64.train")
    spent = 2 * at_128 + 0.021
    assert op_time_share.reduce(ctx, share["params"]) == pytest.approx(100 * spent / 0.1, rel=1e-3)


def load_metric(name):
    return R.load_json("metrics", f"{name}.json")


def test_counted_reducers_read_the_runners_records():
    ctx = {"cell": {"chips": 1}, "peak": PEAK,
           "window": {"required_flops": 197e12 * 5, "elapsed_s": 10.0},
           "traced": {"counters": {"gmm_live_tiles": 36.0, "gmm_grid_tiles": 136.0}}}
    assert mfu_counted.reduce(ctx, {}) == pytest.approx(50.0)
    share = counter_ratio.reduce(ctx, load_metric("moe_live_tile_share.train")["params"])
    assert share == pytest.approx(100 * 36 / 136)
    # the parent's records hold neither: the metrics are left out, not raised
    old = {"cell": {"chips": 1}, "peak": PEAK, "window": {"elapsed_s": 10.0},
           "traced": {"step_s": [1.0]}}
    assert mfu_counted.reduce(old, {}) is None
    assert counter_ratio.reduce(old, {"num": "gmm_live_tiles", "den": "gmm_grid_tiles"}) is None


def test_each_cell_is_given_its_own_metrics():
    reported = {"train_tokens_per_s", "setup_s"}
    new = {m["name"] for m in R.metric_files(CELL, reported)}
    assert new == {"mfu_step_hybrid.train", "gmm_roofline.train", "gmm_time_share.train",
                   "moe_live_tile_share.train", "flash_fwd_roofline_hd64.train",
                   "flash_bwd_roofline_hd64.train", "flash_time_share_hd64.train",
                   "step_ms_median.train",
                   "step_device_ms.train", "device_idle_share.train",
                   "peak_hbm_gib.train",
                   # lists no cells, so every training cell is handed to it; its
                   # dense count reads this cell at twice its share (PERF.md,
                   # Open question 22: the list is a `benchmark` PR's to add)
                   "mfu_step.train"}
    shared = {"step_ms_median.train", "step_device_ms.train", "device_idle_share.train",
              "peak_hbm_gib.train", "mfu_step.train"}
    for cell in ("mistral7b-d4-train-8k", "mistral7b-d4-train-512", "mistral7b-d8x4-train-8k"):
        names = {m["name"] for m in R.metric_files(cell, reported)}
        assert "mfu_step.train" in names
        assert not names & new - shared
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in new - shared:
        assert by_name[name]["workloads"] == [CELL]
    for name in shared:
        assert "workloads" not in by_name[name]


def test_hybrid_runner_end_to_end_at_a_tiny_size():
    cell, cfg = load("tiny-hybrid-cell.json"), load("tiny-hybrid-config.json")
    res = R.execute(cell, cfg, SEED, 0.3, False, jax.devices()[:1], None)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["compared"]["route_flip_share"]["limit"] is None
    assert 0 <= res["compared"]["route_flip_share"]["value"] < 0.05
    for name in cell["limits"]:
        assert res["compared"][name]["value"] <= res["compared"][name]["limit"]
    win = res["window"]
    layers, steps = 4, win["steps"]
    c = win["counters"]
    assert c["moe_rows_routed"] == steps * layers * 4 * cell["batch"] * cell["seen_len"]
    assert 0.15 < c["moe_rows_held"] / c["moe_rows_routed"] < 0.35  # 2 of 8 held
    assert c["gmm_live_tiles"] < 0.5 * c["gmm_grid_tiles"]
    assert 1.0 <= c["moe_load_max_over_mean"] < 2.0
    want = flops_hybrid.step_flops(cfg, cell["batch"], cell["seen_len"],
                                   c["moe_rows_held"] / steps)["total"] * steps
    assert win["required_flops"] == pytest.approx(want)
    # the state goes back to the seed every `restore_every` steps, and
    # set-up ends with a restore: the window starts from the seed
    assert len(win["rows_held_by_step"]) == steps
    assert win["restores"] == (steps - 1) // cell["restore_every"]


SEED = 2**31 + 77


@pytest.fixture(scope="module")
def tiny_run():
    """The tiny cell after set-up and a window long enough to go back to
    the seed, with its float32 reference's readings."""
    cell, cfg = load("tiny-hybrid-cell.json"), load("tiny-hybrid-config.json")
    run = train_hybrid.Run(cell, cfg, SEED, jax.devices()[:1])
    run.setup()
    run.window(0.2)
    return run, run.reference()


def test_traced_steps_start_from_the_seed_and_span_no_restore(tiny_run, tmp_path):
    run, _ = tiny_run
    with pytest.raises(ValueError, match="span a restore"):
        run.traced_steps(run.restore_every + 1, str(tmp_path))
    first = run.readings["rows_held_by_step"][0]  # set-up's first step
    traced = run.traced_steps(2, str(tmp_path))
    assert traced["restores"] == 1 and run.since_seed == 2
    # the same weights, another batch: the load of the window's own steps
    assert abs(traced["rows_held_by_step"][0] - first) < 0.2 * first


def test_a_restore_is_set_ups_state_in_the_old_states_buffers(tiny_run):
    run, _ = tiny_run
    old = run.state
    run.state = run._restored(old)
    assert all(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(old))
    fresh = run.init_state(run.make_weights(run.seed))
    same = jax.tree_util.tree_map(lambda a, b: bool((a == b).all()), run.state, fresh)
    assert all(jax.tree_util.tree_leaves(same))
    assert jax.tree_util.tree_structure(run.state) == jax.tree_util.tree_structure(fresh)
    # set-up and the window went through restores: neither the step nor the
    # restore was traced a second time (nothing is made inside the window)
    run.state, _ = run.train_step(run.state, run._put(run.next_batch()))
    assert run.jit_step._cache_size() == 1 and run.reseed._cache_size() == 1


@pytest.mark.parametrize("control", [{"mode": "fp8"}, {"fault": "half_batch"}])
def test_a_control_in_the_programs_place_is_not_correct(tiny_run, control):
    run, ref = tiny_run
    limits = run.cell["limits"]
    sound, _ = check.decide(train_hybrid.numbers(run.readings, ref), limits)
    assert sound
    ok, compared = check.decide(
        train_hybrid.numbers(run.reference(**control), ref), limits)
    assert not ok
    assert compared["grad_gap_steady"]["value"] > limits["grad_gap_steady"]
    # what Run.verify(mode=...) does: the control in the program's place
    if "mode" in control:
        ok, compared_v = run.verify(**control)
        assert not ok and compared_v["grad_gap_steady"] == compared["grad_gap_steady"]


def test_one_wrong_leaf_is_not_correct_and_a_run_off_its_load_neither(tiny_run):
    run, ref = tiny_run
    limits = run.cell["limits"]

    # one leaf's gradient 3% off (a gap is taken over the leaf's norm or
    # the median leaf's, whichever is larger: the embedding's is its own):
    # the median leaf does not feel it
    bad = copy.deepcopy(run.readings)
    bad["grad_norm"]["embed"] *= 1.03
    values = train_hybrid.numbers(bad, ref)
    assert values["grad_gap_median"] <= limits["grad_gap_median"]
    assert values["grad_gap_steady"] > limits["grad_gap_steady"]
    assert not check.decide(values, limits)[0]
    # a router's leaf is not among the steady ones (a flipped choice moves
    # it by itself): grad_gap's wider limit is the one that holds it
    layer = next(i for i, p in enumerate(ref["grad_norm"]["layers"]) if "moe" in p)
    sound = train_hybrid.numbers(run.readings, ref)
    for factor, passes in ((1.005, True), (2.0, False)):
        bad = copy.deepcopy(run.readings)
        bad["grad_norm"]["layers"][layer]["moe"]["router"] *= factor
        values = train_hybrid.numbers(bad, ref)
        assert values["grad_gap_steady"] == sound["grad_gap_steady"]
        assert check.decide(values, limits)[0] is passes
        assert passes or values["grad_gap"] > limits["grad_gap"]
    assert all("router" in k for k in ref["selection_leaves"])
    assert len(ref["selection_leaves"]) == 2 * 4  # matrix and bias, four expert layers
    # a run whose held rows left the load
    off = dict(run.readings, rows_held_by_step=[
        r * (0.4 if i else 1.0) for i, r in enumerate(run.readings["rows_held_by_step"])])
    values = train_hybrid.numbers(off, ref)
    assert values["held_rows_off_uniform"] > limits["held_rows_off_uniform"]
    assert not check.decide(values, limits)[0]


def test_what_the_program_lacks_is_refused():
    cfg = load("tiny-hybrid-config.json")
    for key, value in (("routed_scaling_factor", 2.5), ("conv_bias", True),
                       ("norm_topk_prob", False), ("use_expert_bias", False)):
        with pytest.raises(ValueError, match=key):
            train_hybrid.hybrid_config(dict(cfg, **{key: value}), 128)
