"""The latent cell's own pieces on the CPU: the FLOP count against a hand
count of the cut, every width against the catalog's row, the new metric
files on hand-made records, and the runner end to end at a tiny size with
its controls and faults."""
import json
import os

import jax
import pytest

from benchmarks import check, flops, flops_latent, run as R
from benchmarks.reducers import flash_roofline_split, mfu_counted, op_time_share
from benchmarks.runners import train_latent

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "xing4.0-29b-a4b-d5e8-train-8k"
CONFIG = "xing4.0-29b-a4b-d5e8"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = {"mfu_step_latent.train", "flash_fwd_roofline_mla.train",
               "flash_bwd_roofline_mla.train", "flash_time_share_mla.train",
               "gmm_time_share_latent.train"}
SEED = 2**31 + 77


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


def test_cut_is_913m_parameters_4_8_gflop_a_token_79_tflop_a_step(cfg):
    d, v, h = 3584, 16384, 32
    mla = d * 768 + 768 * h * 192 + d * 576 + 512 * h * 256 + h * 128 * d  # 28.41M
    mapping = 4 * d * 24  # the three projections: 344,064
    dense = mla + 2 * mapping + 3 * d * 9216  # 128.2M
    shared = expert = 3 * d * 1024  # 11.01M
    fixed = mla + 2 * mapping + d * 64 + shared  # an expert block outside its routed experts
    head = d * v
    hand = dense + 5 * (fixed + 8 * expert) + 2 * head + 2 * d * d
    assert round(hand / 1e6, 1) == 913.4
    small = flops_latent.total_params(cfg) - hand  # norms, biases, the mappings' b and a
    assert 0 < small < 0.07e6
    assert flops_latent.total_params(cfg) == 913_473_668
    assert round(8 * flops_latent.total_params(cfg) / 1e9, 2) == 7.31  # GB
    whole = dict(cfg, num_hidden_layers=40, first_k_dense_replace=2, n_routed_experts=64,
                 vocab_size=131072)
    assert round(flops_latent.total_params(whole) / 1e9, 1) == 30.3
    tokens = 16384
    rows = flops_latent.expert_blocks(cfg) * flops_latent.uniform_rows_held(cfg, tokens)
    assert rows == 5 * tokens * 4 / 8
    f = flops_latent.step_flops(cfg, 2, 8192, rows)
    per_token = {k: x / tokens / 1e9 for k, x in f.items()}
    weights = dense + 4 * (fixed + expert / 2) + head + (fixed + expert / 2 + 2 * d * d + head)
    assert round(weights / 1e6, 1) == 500.5
    scores = 6 * 2 * h * (8192 * 8193 // 2) * 2 * 1152 / tokens / 1e9  # six layers, two rows
    assert round(scores, 2) == 1.81
    assert abs(per_token["total"] - (6 * weights / 1e9 + scores)) < 2e-3
    assert round(per_token["total"], 1) == 4.8
    assert round(f["total"] / 1e12) == 79
    assert round(f["attention"] / f["total"], 2) == 0.38
    assert round((f["mtp_fixed"] + f["experts"] / 5 + f["attention"] / 6) / f["total"], 2) == 0.23
    # the dense count of mfu_step.train reads this file at 1.2 times its work
    dense_count = flops.step_flops(cfg, 2, 8192)["total"]
    assert 1.15 < dense_count / f["total"] < 1.25


def test_every_width_is_the_catalog_rows(cfg, bench):
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu",
        "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
        "max_position_embeddings": 262144, "model_type": "xing4_0",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
        "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                         "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
    differs = sorted(k for k, v in published.items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"])
    assert {k: cfg["published"][k] for k in differs} == {k: published[k] for k in differs}
    assert cfg["router_outputs"] == 64 and cfg["first_expert"] == 0
    assert flops_latent.widths(cfg) == {"qk": 192, "v": 128}
    for key in ("assumed", "deployment"):
        assert cfg[key]
    for word in ("replicated", "columns first", "clamp before", "half-split", "embedding's half first",
                 "0.3", "no gradient", "float32", "2 on its diagonal"):
        assert word in json.dumps(cfg["assumed"]), word
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and sorted(entry["reduced"]) == differs
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "train-8k", 1)
    on_disk = R.load_json("workloads", f"{CELL}.json")
    assert on_disk["why"] == cell["why"] and on_disk["runner"] == "train_latent"
    for name, lim in on_disk["limits"].items():
        assert name in on_disk["limits_from"], name


def hand_trace(durations_us):
    """One device, one operations line; names as a TPU trace gives them."""
    events, t = [], 0
    for name, us in durations_us:
        events.append([f"%{name} = bf16[64,8192,256]{{2,1,0}} custom-call(...)", t, us * 1000])
        t += us * 1000 + 500
    return {"planes": [{"name": "/device:TPU:0",
                        "lines": [{"name": "XLA Ops", "events": events}]}]}


def test_flash_rooflines_cost_keys_and_values_at_their_own_widths(cfg):
    fmt = {"device_plane": r"^/device:TPU:\d+$", "op_lines": ["XLA Ops"]}
    cell = R.load_json("workloads", f"{CELL}.json")
    keys = 8192 * 8193 // 2
    fwd = flops_latent.flash_call_cost(cfg, 2, 8192, "fwd")
    bwd = flops_latent.flash_call_cost(cfg, 2, 8192, "bwd")
    assert fwd["flops"] == 2 * 32 * 2 * keys * (192 + 128)
    assert bwd["flops"] == 2 * 32 * 2 * keys * 832
    lane = 2 * 32 * 8192 * 2
    assert fwd["bytes"] == lane * (192 + 192 + 128 + 128) + 2 * 32 * 8192 * 4
    assert bwd["bytes"] == lane * (4 * 192 + 4 * 128) + 2 * 2 * 32 * 8192 * 4
    t_fwd, t_bwd = fwd["flops"] / 197e12, bwd["flops"] / 197e12
    assert t_fwd > fwd["bytes"] / 819e9 and t_bwd > bwd["bytes"] / 819e9
    ops = [("flash_fwd.2", 2 * t_fwd * 1e6), ("flash_fwd.3", 2 * t_fwd * 1e6),
           ("flash_bwd_dq.2", 18000), ("flash_bwd_dkv.2", 23000), ("gmm.7", 1000),
           ("gmm_swiglu.3", 500), ("fusion.9", 9000)]
    ctx = {"trace": hand_trace(ops), "fmt": fmt, "cfg": cfg, "cell": cell, "peak": PEAK,
           "traced": {"steps": 1, "window_s": 0.2}}
    got = flash_roofline_split.reduce(ctx, load_metric("flash_fwd_roofline_mla.train")["params"])
    assert got == pytest.approx(50.0, rel=1e-3)
    got = flash_roofline_split.reduce(ctx, load_metric("flash_bwd_roofline_mla.train")["params"])
    assert got == pytest.approx(100 * t_bwd / 0.041, rel=1e-3) and 30 < got < 45
    share = op_time_share.reduce(ctx, load_metric("flash_time_share_mla.train")["params"])
    assert share == pytest.approx(100 * (4 * t_fwd + 0.041) / 0.2, rel=1e-3)
    share = op_time_share.reduce(ctx, load_metric("gmm_time_share_latent.train")["params"])
    assert share == pytest.approx(100 * 0.0015 / 0.2, rel=1e-3)
    # a configuration with no such widths (an older cell's), no trace, or a
    # trace without the kernels: nothing, and no error
    params = load_metric("flash_fwd_roofline_mla.train")["params"]
    old = R.load_json("configs", "lfm2-8b-a1b-d9e8.json")
    assert flash_roofline_split.reduce(dict(ctx, cfg=old), params) is None
    assert flash_roofline_split.reduce(dict(ctx, trace=None), params) is None
    assert flash_roofline_split.reduce(
        dict(ctx, trace=hand_trace([("fusion.1", 10)])), params) is None


def test_counted_mfu_reads_the_runners_record():
    ctx = {"cell": {"chips": 1}, "peak": PEAK,
           "window": {"required_flops": 197e12 * 3, "elapsed_s": 10.0}}
    assert load_metric("mfu_step_latent.train")["reducer"] == "mfu_counted"
    assert mfu_counted.reduce(ctx, {}) == pytest.approx(30.0)
    assert mfu_counted.reduce(dict(ctx, window={"elapsed_s": 10.0}), {}) is None


def test_the_cell_is_given_its_own_metrics_and_no_other_cell_is(bench):
    reported = {"train_tokens_per_s", "setup_s"}
    names = {m["name"] for m in R.metric_files(CELL, reported)}
    shared = {"step_ms_median.train", "step_device_ms.train", "device_idle_share.train",
              "peak_hbm_gib.train", "mfu_step.train"}
    assert NEW_METRICS <= names and names - NEW_METRICS <= shared | {
        "step_trace_s.train", "step_lower_s.train", "step_executable_s.train",
        "step_compiles.train"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert load_metric(name)["workloads"] == [CELL]
        assert {k: by_name[name][k] for k in ("unit", "better", "source", "layer", "moves")} == {
            k: load_metric(name)[k] for k in ("unit", "better", "source", "layer", "moves")}
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert not NEW_METRICS & {m["name"] for m in R.metric_files(w["name"], reported)}
    assert CELL in [w["name"] for w in bench["workloads"]]
    assert CONFIG in [c["name"] for c in bench["configs"]]


def test_latent_runner_end_to_end_at_a_tiny_size():
    cell, cfg = load("tiny-latent-cell.json"), load("tiny-latent-config.json")
    res = R.execute(cell, cfg, SEED, 0.3, False, jax.devices()[:1], None)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for name in cell["limits"]:
        assert res["compared"][name]["value"] <= res["compared"][name]["limit"]
    assert res["compared"]["loss_gap"]["limit"] is None
    win = res["window"]
    steps, c = win["steps"], win["counters"]
    blocks = 3  # two expert blocks and the module's
    assert c["moe_rows_routed"] == steps * blocks * 2 * cell["batch"] * cell["seen_len"]
    assert 0.35 < c["moe_rows_held"] / c["moe_rows_routed"] < 0.65  # 4 of 8 held
    assert c["hc_mappings"] == 8 and 0 < c["hc_res_offdiag"] < 0.75
    assert c["mtp_ce"] > 0 and c["ce"] > 0
    want = flops_latent.step_flops(cfg, cell["batch"], cell["seen_len"],
                                   c["moe_rows_held"] / steps)["total"] * steps
    assert win["required_flops"] == pytest.approx(want)
    assert win["restores"] == (steps - 1) // cell["restore_every"]


@pytest.fixture(scope="module")
def tiny_run():
    cell, cfg = load("tiny-latent-cell.json"), load("tiny-latent-config.json")
    run = train_latent.Run(cell, cfg, SEED, jax.devices()[:1])
    run.setup()
    return run, run.reference()


@pytest.mark.parametrize("control,by", [
    ({"mode": "fp8"}, "grad_gap_median"), ({"fault": "half_batch"}, "grad_gap_median"),
    ({"fault": "no_mix"}, "hc_res_offdiag_gap"), ({"fault": "no_mtp"}, "mtp_ce_gap"),
    ({"fault": "no_rope_key"}, "grad_gap_median")],
    ids=["fp8", "half_batch", "no_mix", "no_mtp", "no_rope_key"])
def test_a_control_or_a_fault_in_the_programs_place_is_not_correct(tiny_run, control, by):
    run, ref = tiny_run
    limits = run.cell["limits"]
    assert check.decide(train_latent.numbers(run.readings, ref), limits)[0]
    ok, compared = check.decide(
        train_latent.numbers(run.reference(**control), ref), limits)
    assert not ok
    assert compared[by]["value"] > limits[by]
    if control.get("fault") in ("no_mix", "no_mtp"):  # a gradient the fault zeroes
        assert compared["change_gap"]["value"] > limits["change_gap"]


def test_what_the_program_lacks_is_refused():
    cfg = load("tiny-latent-config.json")
    for key, value in (("scoring_func", "softmax"), ("n_group", 2), ("hidden_act", "gelu"),
                       ("norm_topk_prob", False), ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            train_latent.latent_config(dict(cfg, **{key: value}), 128)
