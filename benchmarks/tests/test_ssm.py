"""The state-space cell's own pieces on the CPU: the FLOP count against a
hand count of the cut, the configuration file against the published
widths, the new metric files on hand-made records, the metric files each
cell is given, and the runner end to end at a tiny size with its controls."""
import json
import os

import jax
import pytest

from benchmarks import check, flops, flops_ssm, run as R, weights_ssm
from benchmarks.reducers import mfu_counted, op_time_share
from benchmarks.runners import train_ssm

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "granite-4.0-h-micro-d10-train-8k"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 5
NEW = {"mfu_step_ssm.train", "flash_time_share_ssm.train"}


def load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


def load_metric(name):
    return R.load_json("metrics", f"{name}.json")


@pytest.fixture(scope="module")
def cfg():
    return R.load_json("configs", "granite-4.0-h-micro-d10.json")


def test_cut_is_952m_parameters_and_5_9_gflop_a_token(cfg):
    d, v, ff = 2048, 100352, 8192
    mamba = d * 8512 + (4352 * 4 + 4352) + 192 + 4096 + 4096 * d
    assert mamba == 25_847_232
    attention = 2 * d * d + 2 * d * 512
    assert attention == 10_485_760
    ffn_and_norms = 3 * d * ff + 2 * d
    assert mamba + ffn_and_norms == 76_182_976 and attention + ffn_and_norms == 60_821_504
    hand = 9 * 76_182_976 + 60_821_504 + v * d + d
    assert flops_ssm.total_params(cfg) == hand == 951_991_232
    assert round(8 * hand / 1e9, 2) == 7.62  # GB of state
    whole = dict(cfg, num_hidden_layers=40, layer_types=cfg["layer_types"] * 4)
    assert flops_ssm.total_params(whole) == 3_191_396_096
    # the seeded tree has the same count
    shapes = jax.tree_util.tree_leaves(weights_ssm.leaf_shapes(cfg), is_leaf=weights_ssm.is_shape)
    assert sum(int(__import__("math").prod(s)) for s in shapes) == hand

    tokens = 2 * 8192
    f = flops_ssm.step_flops(cfg, 2, 8192)
    per_token = {k: x / tokens / 1e9 for k, x in f.items()}
    vectors = 9 * (4352 * 5 + 192 + 4096) + 21 * d
    assert 6 * (hand - vectors) / 1e9 == pytest.approx(per_token["matmul"], rel=1e-12)
    assert round(per_token["matmul"], 2) == 5.71
    scores = 6 * 2 * 32 * 64 * (8192 * 8193 // 2) / 8192 / 1e9
    assert per_token["attention"] == pytest.approx(scores) and round(scores, 2) == 0.10
    # a chunk of 256: the causal C B^T once, its product with x a head, the
    # chunk's end state and the carried state's output
    scan = 128.5 * 2 * 128 + 128.5 * 2 * 4096 + 2 * 2 * 4096 * 128
    assert flops_ssm.scan_flops_per_token(cfg) == scan and round(scan / 1e6, 1) == 3.2
    assert per_token["scans"] == pytest.approx(9 * 3 * scan / 1e9)
    assert round(per_token["scans"], 2) == 0.09
    assert round(per_token["total"], 1) == 5.9 and round(f["total"] / 1e12) == 97
    head = 6 * v * d / 1e9
    assert round(100 * head / per_token["total"]) == 21
    deep = flops_ssm.step_flops(whole, 2, 8192)["total"] / tokens / 1e9
    assert round(100 * head / deep, 1) == 6.2
    # the dense count of the same file (ten attention layers) is within a
    # percent of it by accident (PERF.md Open question 22)
    dense = flops.step_flops(cfg, 2, 8192)["total"]
    assert dense / f["total"] == pytest.approx(1.0, abs=0.01)


def test_every_width_is_the_published_one(cfg):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["name"] == "granite-4.0-h-micro")
    assert cfg["source"] == published["source_url"]
    differs = sorted(k for k, v in published["config"].items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == ["layer_types", "num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 10 and cfg["published"]["num_hidden_layers"] == 40
    assert cfg["layer_types"] == published["config"]["layer_types"][:10]
    assert cfg["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert published["config"]["layer_types"] == cfg["layer_types"] * 4
    widths = {"hidden_size": 2048, "intermediate_size": 8192, "num_attention_heads": 32,
              "num_key_value_heads": 8, "vocab_size": 100352, "mamba_n_heads": 64,
              "mamba_d_head": 64, "mamba_d_state": 128, "mamba_d_conv": 4,
              "mamba_expand": 2, "mamba_n_groups": 1, "mamba_chunk_size": 256,
              "attention_multiplier": 0.015625, "embedding_multiplier": 12,
              "residual_multiplier": 0.22, "logits_scaling": 8, "rms_norm_eps": 1e-5,
              "position_embedding_type": "nope", "tie_word_embeddings": True,
              "max_position_embeddings": 131072}
    assert {k: cfg[k] for k in widths} == widths
    for key in ("gate_before_norm", "one_norm_group", "silu_after_conv",
                "residual_multiplier_on_both_branches", "head_dim", "initializer_range",
                "torch_dtype", "weights", "remat", "ce_chunks", "optimizer_state"):
        assert cfg["assumed"][key]
    assert cfg["deployment"] and cfg["remat"] == "full" and cfg["ce_chunks"] == 7
    assert cfg["vocab_size"] % cfg["ce_chunks"] == 0
    assert cfg["vocab_size"] // cfg["ce_chunks"] == 112 * 128
    # the program's config from these keys
    config = train_ssm.ssm_config(cfg, 8192)
    assert config.layer_types == ("ssm",) * 5 + ("attention",) + ("ssm",) * 4
    assert (config.ssm_heads, config.ssm_head_dim, config.ssm_state, config.ssm_chunk,
            config.ssm_conv_kernel) == (64, 64, 128, 256, 4)
    assert config.q_prescale == 0.125 and not config.use_rope and config.ce_chunks == 7
    assert (config.embed_scale, config.residual_multiplier, config.logits_scaling) == (
        12.0, 0.22, 8.0)


def test_seeded_state_space_leaves_keep_their_ranges(cfg):
    small = dict(cfg, hidden_size=256, mamba_n_heads=8, vocab_size=512, intermediate_size=64,
                 shared_intermediate_size=64, num_attention_heads=4, num_key_value_heads=2)
    tree = weights_ssm.maker(small)(SEED)
    layer = tree["layers"][0]
    dt = jax.nn.softplus(layer["ssm_dt_bias"])
    assert 1e-3 * 0.999 <= float(dt.min()) and float(dt.max()) <= 1e-1 * 1.001
    a = jax.numpy.exp(layer["ssm_A_log"])
    assert 1 <= float(a.min()) and float(a.max()) <= 16
    assert float(abs(layer["ssm_conv_w"]).max()) <= 0.5 and layer["ssm_conv_w"].dtype == "float32"
    assert float(layer["ssm_D"].min()) == 1 and layer["ssm_in"].dtype == "bfloat16"
    assert layer["ssm_in"].shape == (256, 2 * 512 + 2 * 128 + 8)
    same = weights_ssm.maker(small)(SEED)["layers"][3]["ssm_A_log"]
    assert (same == tree["layers"][3]["ssm_A_log"]).all()


def hand_trace(durations_us):
    """One device, one operations line; names as a TPU trace gives them."""
    events, t = [], 0
    for name, us in durations_us:
        events.append([f"%{name} = bf16[64,8192,128]{{2,1,0}} custom-call(...)", t, us * 1000])
        t += us * 1000 + 500
    return {"planes": [{"name": "/device:TPU:0",
                        "lines": [{"name": "XLA Ops", "events": events}]}]}


def test_new_metric_files_reduce_a_hand_made_record(cfg):
    fmt = {"device_plane": r"^/device:TPU:\d+$", "op_lines": ["XLA Ops"]}
    per_step = flops_ssm.step_flops(cfg, 2, 8192)["total"]
    ctx = {"cell": {"chips": 1}, "peak": PEAK, "fmt": fmt, "cfg": cfg,
           "window": {"required_flops": 10 * per_step, "elapsed_s": 10.4},
           "traced": {"steps": 1, "window_s": 1.0},
           "trace": hand_trace([("flash_fwd.3", 10400), ("flash_bwd_dq.3", 11100),
                                ("flash_bwd_dkv.3", 16800), ("fusion.9", 90000)])}
    mfu, share = load_metric("mfu_step_ssm.train"), load_metric("flash_time_share_ssm.train")
    assert (mfu["reducer"], share["reducer"]) == ("mfu_counted", "op_time_share")
    assert mfu_counted.reduce(ctx, mfu["params"]) == pytest.approx(
        100 * 10 * per_step / 10.4 / 197e12)
    assert 40 < mfu_counted.reduce(ctx, mfu["params"]) < 50
    assert op_time_share.reduce(ctx, share["params"]) == pytest.approx(3.83, abs=1e-6)
    # a program that counts nothing, a trace without the kernels, no trace:
    # the metric is left out, not raised
    assert mfu_counted.reduce(dict(ctx, window={"elapsed_s": 10.4}), {}) is None
    none = dict(ctx, trace=hand_trace([("fusion.1", 10)]))
    assert op_time_share.reduce(none, share["params"]) is None
    assert op_time_share.reduce(dict(ctx, trace=None), share["params"]) is None


def test_each_cell_is_given_its_own_metrics():
    reported = {"train_tokens_per_s", "setup_s"}
    shared = {"step_ms_median.train", "step_device_ms.train", "device_idle_share.train",
              "peak_hbm_gib.train",
              # lists no cells, so every training cell is handed to it; its dense
              # count of ten attention layers reads this cell within a percent
              # of its share, by accident (PERF.md, Open question 22)
              "mfu_step.train"}
    assert {m["name"] for m in R.metric_files(CELL, reported)} == NEW | shared
    for cell in ("mistral7b-d4-train-8k", "mistral7b-d4-train-512", "mistral7b-d8x4-train-8k",
                 "lfm2-8b-a1b-d9e8-train-8k", "ouro-2.6b-d8-train-8k"):
        assert not {m["name"] for m in R.metric_files(cell, reported)} & NEW
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        on_disk = load_metric(name)
        assert {k: on_disk[k] for k in by_name[name]} == by_name[name]
    # found by name, not by place: a later PR appends its own entries
    config = next(c for c in bench["configs"] if c["name"] == "granite-4.0-h-micro-d10")
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert next(w for w in bench["workloads"] if w["name"] == CELL) == {
        "name": CELL, "config": "granite-4.0-h-micro-d10", "traffic": "train-8k", "chips": 1,
        "why": R.load_json("workloads", f"{CELL}.json")["why"]}


def test_cell_file_is_the_cell_the_issue_names():
    cell = R.load_json("workloads", f"{CELL}.json")
    assert (cell["runner"], cell["batch"], cell["seen_len"], cell["mesh"], cell["chips"]) == (
        "train_ssm", 2, 8192, {"fsdp": 1}, 1)
    assert cell["optimizer"] == R.load_json(
        "workloads", "ouro-2.6b-d8-train-8k.json")["optimizer"]
    assert "restore_every" not in cell
    assert cell["reference"] == {"steps": 2, "row_block": 1}
    assert set(cell["limits"]) == {"grad_gap", "grad_gap_median", "change_gap",
                                   "grad_gap_decay"}
    assert len(cell["why"]) <= 200 and cell["limits_from"]


# -- the runner end to end at a tiny size ------------------------------------------


def test_ssm_runner_end_to_end_at_a_tiny_size():
    cell, cfg = load("tiny-ssm-cell.json"), load("tiny-ssm-config.json")
    res = R.execute(cell, cfg, SEED, 0.3, False, jax.devices()[:1], None)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["compared"]["loss_gap"]["limit"] is None
    for name in cell["limits"]:
        assert res["compared"][name]["value"] <= res["compared"][name]["limit"]
    win = res["window"]
    steps, c = win["steps"], win["counters"]
    # three state-space layers, four sequences of 128 tokens in chunks of 32
    assert c["ssm_layers"] == 3 * steps and c["ssm_chunks"] == 3 * 4 * 4 * steps
    assert 0 < c["ssm_state_carry"] < 1 and 1e-3 < c["ssm_dt_mean"] < 1
    want = flops_ssm.step_flops(cfg, cell["batch"], cell["seen_len"])["total"] * steps
    assert win["required_flops"] == pytest.approx(want)


@pytest.fixture(scope="module")
def tiny_run():
    """The tiny cell after set-up, with its float32 reference's readings."""
    cell, cfg = load("tiny-ssm-cell.json"), load("tiny-ssm-config.json")
    run = train_ssm.Run(cell, cfg, SEED, jax.devices()[:1])
    run.setup()
    return run, run.reference()


@pytest.mark.parametrize("control,fails_by", [
    ({"mode": "fp8"}, ("grad_gap", "grad_gap_median")),
    ({"fault": "no_carry"}, ("grad_gap_decay",)),
    ({"fault": "half_batch"}, ("grad_gap", "grad_gap_median", "grad_gap_decay"))])
def test_a_control_in_the_programs_place_is_not_correct(tiny_run, control, fails_by):
    run, ref = tiny_run
    limits = run.cell["limits"]
    sound, _ = check.decide(train_ssm.numbers(run.readings, ref), limits)
    assert sound
    ok, compared = check.decide(
        train_ssm.numbers(run.reference(**control), ref), limits)
    assert not ok
    for name in fails_by:
        assert compared[name]["value"] > limits[name], name
    # what Run.verify(mode=...) does: the control in the program's place
    if "mode" in control:
        ok, compared_v = run.verify(**control)
        assert not ok and compared_v["grad_gap"] == compared["grad_gap"]


def test_no_carry_is_invisible_to_the_norms_of_the_large_leaves(tiny_run):
    """Why grad_gap_decay is compared: a state dropped between chunks
    leaves the median leaf's gradient norm where it was."""
    run, ref = tiny_run
    values = train_ssm.numbers(run.reference(fault="no_carry"), ref)
    assert values["grad_gap_median"] < run.cell["limits"]["grad_gap_median"]
    assert values["grad_gap_decay"] > 2 * run.cell["limits"]["grad_gap_decay"]


def test_what_the_program_lacks_is_refused(monkeypatch):
    cfg = load("tiny-ssm-config.json")
    for key, value in (("mamba_n_groups", 2), ("mamba_proj_bias", True),
                       ("mamba_conv_bias", False), ("num_local_experts", 8),
                       ("position_embedding_type", "alibi")):
        with pytest.raises(ValueError, match=key):
            train_ssm.ssm_config(dict(cfg, **{key: value}), 128)
    # a program with no state-space layer (the parent commit): at once, by name
    monkeypatch.delattr(train_ssm.llama.LlamaConfig, "ssm_heads")
    with pytest.raises(SystemExit, match="no state-space layer"):
        train_ssm.ssm_config(cfg, 128)
