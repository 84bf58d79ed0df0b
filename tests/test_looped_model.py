"""A looped decoder (a stack applied several times over the same weights,
a head and an exit gate after every pass, the expected loss over the
gate's exit distribution) against the plain reference
`benchmarks/reference/ouro_ref.py`, on seeded weights at a small size,
and the refusals of the paths that cannot run such a model yet."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check, weights_looped
from benchmarks.reference import ouro_ref
from benchmarks.reference.llama_ref import make_mm, rms_norm
from benchmarks.run import load_json
from benchmarks.runners.train_looped import looped_config
from kubedl_tpu.models import llama

SEQ = 48

# hidden 64, 2 layers, 4 passes, heads of 16
CFG = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 128, "hidden_act": "silu", "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "sliding_window": None, "use_sliding_window": False,
    "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
    "exit_entropy_beta": 0.05, "initializer_range": 0.02,
    "torch_dtype": "float32", "remat": "full",
}


def weights_of(cfg, seed, spread=0.2, dtype=jnp.float32):
    # a larger spread than the benchmark's 0.02, so that at hidden 64 the
    # gate's logits differ from token to token and every leaf's gradient
    # is well above zero; the gate's bias off zero, so that it matters
    tree = weights_looped.make_fn(dict(cfg, initializer_range=spread))(
        jax.random.PRNGKey(seed))
    tree["exit_gate"]["b"] = jnp.full((1,), 0.3, jnp.float32)
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if a.ndim == 2 else a, tree)


def tokens_of(cfg, seed, batch=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ + 1), 0,
                              cfg["vocab_size"])


def program(cfg, **kw):
    return dataclasses.replace(looped_config(cfg, SEQ), use_flash=False, **kw)


def value_and_grads(config, params, tokens):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: llama.loss_and_stats(p, tokens, config), has_aux=True))(params)


def leaves(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def assert_close_leaf_by_leaf(got, want, tol):
    got, want = leaves(got), leaves(want)
    assert got.keys() == want.keys()
    for name, w in want.items():
        assert float(jnp.linalg.norm(w)) > 0, name
        gap = float(jnp.linalg.norm(got[name] - w) / jnp.linalg.norm(w))
        assert gap < tol, (name, gap)


# -- against the reference ----------------------------------------------------


@pytest.mark.parametrize("passes", [2, 4])
def test_loss_and_every_gradient_leaf_match_the_reference(passes):
    cfg = dict(CFG, total_ut_steps=passes)
    params, tokens = weights_of(cfg, 3), tokens_of(cfg, 4)
    (loss, stats), grads = value_and_grads(program(cfg), params, tokens)
    (ref_loss, ref_mass), ref_grads = jax.jit(jax.value_and_grad(
        lambda p: ouro_ref.loss(p, tokens, cfg), has_aux=True))(params)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    assert_close_leaf_by_leaf(grads, ref_grads, 2e-4)
    mass = [float(stats[f"loop_exit_mass_{t + 1}"]) for t in range(passes)]
    np.testing.assert_allclose(mass, np.asarray(ref_mass), atol=1e-6)


def test_the_walk_gives_the_whole_models_gradient_norms():
    """The reference's walk (a block of rows and a layer application at a
    time, the head in chunks) computes what its loss in one piece does."""
    cfg = dict(CFG)
    cell = {"optimizer": {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9,
                          "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01},
            "reference": {"steps": 1, "row_block": 1}}
    tokens = np.asarray(tokens_of(cfg, 5))
    ref = ouro_ref.Reference(cfg, cell, 11, jax.devices()[:1])
    start = jax.tree_util.tree_map(jnp.copy, ref.params)
    out = ref.run([tokens], 1)
    (want, mass), grads = jax.jit(jax.value_and_grad(
        lambda p: ouro_ref.loss(p, tokens, cfg), has_aux=True))(start)
    assert out["loss"][0] == pytest.approx(float(want), rel=1e-5)
    np.testing.assert_allclose(out["exit_mass"][0], np.asarray(mass), atol=1e-6)
    norms = jax.tree_util.tree_map(lambda g: float(jnp.linalg.norm(g)), grads)
    assert max(check.leaf_gaps(out["grad_norm"], norms).values()) < 1e-4


def test_bfloat16_stays_within_a_tolerance_that_the_fp8_control_fails():
    """In the parameters' own dtype the program is within 8e-3 of the
    float32 reference on every leaf's gradient norm (rounding of bf16
    matmuls through 8 layer applications); the reference computed in
    float8 e4m3 is not."""
    cfg = dict(CFG, torch_dtype="bfloat16")
    params, tokens = weights_of(cfg, 3, spread=0.02, dtype=jnp.bfloat16), tokens_of(cfg, 4, 8)
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_and_stats(p, tokens, program(cfg)), has_aux=True))(params)
    as_f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    norm = lambda t: jax.tree_util.tree_map(
        lambda g: float(jnp.linalg.norm(g.astype(jnp.float32))), t)
    grad_of = lambda mm: jax.jit(jax.value_and_grad(
        lambda p: ouro_ref.loss(p, tokens, cfg, mm=mm), has_aux=True))(as_f32)
    (ref_loss, _), ref_grads = grad_of(make_mm("f32"))
    (_, _), fp8_grads = grad_of(make_mm("fp8"))
    assert abs(float(loss) - float(ref_loss)) < 1e-3 * float(ref_loss)
    sound = max(check.leaf_gaps(norm(grads), norm(ref_grads)).values())
    control = max(check.leaf_gaps(norm(fp8_grads), norm(ref_grads)).values())
    assert sound < 8e-3 < control, (sound, control)


# -- the loop ----------------------------------------------------------------


def test_one_pass_and_no_gate_is_the_plain_decoder_bit_for_bit():
    """A stack run once holds no gate leaf, draws the weights it always
    drew and takes the plain loss: next-token cross entropy of the
    plain forward's logits."""
    plain = llama.LlamaConfig.tiny(use_flash=False)
    once = dataclasses.replace(plain, total_ut_steps=1, exit_entropy_beta=0.3)
    params = llama.init(once, jax.random.PRNGKey(0))
    assert "exit_gate" not in params and "exit_gate" not in llama.param_specs(once)
    same = jax.tree_util.tree_map(
        lambda a, b: bool((a == b).all()), params, llama.init(plain, jax.random.PRNGKey(0)))
    assert all(jax.tree_util.tree_leaves(same))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, plain.vocab_size)

    def by_hand(p):
        logits = llama.forward(p, tokens[:, :-1], plain)
        return llama._next_token_ce(logits, tokens[:, 1:])

    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, tokens, once)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(by_hand))(params)
    assert float(loss) == float(want)
    assert all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: bool((a == b).all()), grads, want_grads)))
    assert llama.loss_and_stats(params, tokens, once)[1] == {}
    # and the same program: the looped fields leave no trace in it
    text = lambda c: jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, tokens, c))).lower(params).as_text()
    assert text(once) == text(plain)


def test_a_shared_weights_gradient_is_the_sum_over_its_uses():
    """The looped gradient of a layer's weight equals the sum of the T
    gradients of an unrolled model that holds T copies of the stack."""
    cfg, passes = dict(CFG), CFG["total_ut_steps"]
    params, tokens = weights_of(cfg, 7), tokens_of(cfg, 8)
    (_, _), grads = value_and_grads(program(cfg), params, tokens)
    mm = make_mm("f32")

    def unrolled(stacks, rest):
        h, targets = rest["embed"][tokens[:, :-1]], tokens[:, 1:]
        ces, zs = [], []
        for layers in stacks:
            for p in layers:
                h = ouro_ref.layer_fwd(h, p, cfg, mm)
            h = rms_norm(h, rest["final_norm"], cfg["rms_norm_eps"])
            ce, z = ouro_ref.head_and_gate(
                h, rest["lm_head"], rest["exit_gate"], targets, mm)
            ces.append(ce), zs.append(z)
        total, _ = ouro_ref.objective(
            jnp.stack(ces), jnp.stack(zs), cfg["exit_entropy_beta"])
        return total / targets.size

    copies = [jax.tree_util.tree_map(jnp.copy, params["layers"]) for _ in range(passes)]
    by_copy = jax.jit(jax.grad(unrolled))(copies, params)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *by_copy)
    assert_close_leaf_by_leaf(grads["layers"], summed, 2e-4)
    # no one use's gradient is the whole of it
    one = leaves(by_copy[0])
    for name, g in leaves(grads["layers"]).items():
        if name.endswith("['wq']"):
            assert float(jnp.linalg.norm(g - one[name]) / jnp.linalg.norm(g)) > 0.1


def test_remat_on_and_off_and_the_loop_unrolled_agree():
    cfg = dict(CFG)
    params, tokens = weights_of(cfg, 3), tokens_of(cfg, 4)
    (loss, _), grads = value_and_grads(program(cfg), params, tokens)
    (loss_off, _), grads_off = value_and_grads(program(cfg, remat=False), params, tokens)
    assert float(loss) == pytest.approx(float(loss_off), rel=1e-6)
    assert_close_leaf_by_leaf(grads_off, grads, 1e-5)


def test_the_loop_is_a_loop_in_the_program(monkeypatch):
    """The step holds one stack's kernels whatever the number of passes:
    n_layers forward flash kernels in the loop's body (and its backward's
    two a layer, none run a second time), and the unrolled form of the
    same loop computes the same numbers."""
    from tests.test_remat_flash import KERNELS, _equations, _kernel_calls

    config = llama.LlamaConfig.tiny(
        total_ut_steps=3, post_block_norms=True, dtype=jnp.float32)
    params = llama.init(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, config.vocab_size)
    # a new function a trace: JAX keeps a function's trace by its identity
    make_step = lambda: jax.value_and_grad(lambda p: llama.loss_fn(p, tokens, config))
    step = make_step()
    calls = _kernel_calls(list(_equations(jax.make_jaxpr(step)(params).jaxpr)))
    assert calls == dict.fromkeys(KERNELS, config.n_layers)
    loss, grads = jax.jit(step)(params)

    def python_loop(f, carry, xs, length):
        ys = []
        for _ in range(length):
            carry, y = f(carry, None)
            ys.append(y)
        return carry, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)

    monkeypatch.setattr(llama, "_scan_passes", python_loop)
    step = make_step()
    unrolled = _kernel_calls(list(_equations(jax.make_jaxpr(step)(params).jaxpr)))
    assert unrolled == dict.fromkeys(KERNELS, 3 * config.n_layers)
    loss_u, grads_u = jax.jit(step)(params)
    assert float(loss) == pytest.approx(float(loss_u), rel=1e-6)
    assert_close_leaf_by_leaf(grads_u, grads, 1e-4)


def test_exit_masses_sum_to_one_and_the_counters_count_the_loop():
    cfg = dict(CFG)
    params, tokens = weights_of(cfg, 9), tokens_of(cfg, 10)
    (loss, stats), _ = value_and_grads(program(cfg), params, tokens)
    stats = {k: float(v) for k, v in stats.items()}
    passes, layers = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    assert set(stats) == {"loop_passes", "loop_layer_applications", "loop_exit_entropy"} | {
        f"loop_{kind}_{t}" for kind in ("exit_mass", "ce") for t in range(1, passes + 1)}
    mass = [stats[f"loop_exit_mass_{t}"] for t in range(1, passes + 1)]
    assert sum(mass) == pytest.approx(1.0, abs=1e-6) and min(mass) > 0.01
    assert stats["loop_passes"] == passes
    assert stats["loop_layer_applications"] == passes * layers
    assert 0 < stats["loop_exit_entropy"] <= np.log(passes) + 1e-6
    # the loss is the expected cross entropy less beta times the entropy
    # only token by token; its mean lies within the passes' mean losses'
    # range, less at most beta * log(T)
    ces = [stats[f"loop_ce_{t}"] for t in range(1, passes + 1)]
    assert min(ces) - 0.05 * np.log(passes) - 0.5 < float(loss) < max(ces) + 0.5
    # a gate that always leaves at the first pass: the loss is that pass's
    shut = dict(params, exit_gate={"w": jnp.zeros_like(params["exit_gate"]["w"]),
                                   "b": jnp.full((1,), 40.0, jnp.float32)})
    (loss_1, stats_1), _ = value_and_grads(program(cfg), shut, tokens)
    assert float(stats_1["loop_exit_mass_1"]) == pytest.approx(1.0)
    assert float(loss_1) == pytest.approx(float(stats_1["loop_ce_1"]), rel=1e-5)
    # and the forward's logits are the last pass's
    logits = llama.forward(params, tokens[:, :-1], program(cfg))
    assert logits.shape == (2, SEQ, cfg["vocab_size"])
    ce_last = llama._next_token_ce(logits, tokens[:, 1:])
    assert float(ce_last) == pytest.approx(stats[f"loop_ce_{passes}"], rel=1e-4)


# -- the published sizes, and the paths that refuse them ------------------------


@pytest.mark.parametrize("layers,millions", [(48, 2668.0), (8, 612.4)])
def test_published_sizes_count_2_67b_parameters_and_the_cut_612m(layers, millions):
    config = llama.LlamaConfig.config_for("ouro-2.6b")
    assert (config.total_ut_steps, config.n_layers, config.post_block_norms) == (4, 48, True)
    config = dataclasses.replace(config, n_layers=layers)
    shapes = jax.eval_shape(lambda k: llama.init(config, k), jax.random.PRNGKey(0))
    total = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert round(total / 1e6, 1) == millions
    assert shapes["exit_gate"]["w"].shape == (2048, 1)
    assert shapes["exit_gate"]["b"].shape == (1,)
    assert set(shapes["layers"][0]) == {
        "attn_norm", "post_attn_norm", "mlp_norm", "post_mlp_norm",
        "wq", "wk", "wv", "wo", "w1", "w3", "w2"}
    # the sharding contract covers every leaf
    specs = llama.param_specs(config)
    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda s: 0, specs, is_leaf=is_spec)
    ) == jax.tree_util.tree_structure(jax.tree_util.tree_map(lambda s: 0, shapes))
    # the benchmark's weights have the program's layout
    cut = load_json("configs", "ouro-2.6b-d8.json")
    made = jax.eval_shape(weights_looped.make_fn(dict(cut, num_hidden_layers=layers)),
                          jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda a: a.shape, made) == \
        jax.tree_util.tree_map(lambda a: a.shape, shapes)


def small_looped():
    return program(dict(CFG))


def test_cached_decode_refuses_a_looped_stack():
    from kubedl_tpu.models import decode

    with pytest.raises(NotImplementedError, match="total_ut_steps = 4 times"):
        decode.init_kv_cache(small_looped(), 1, 64)


def test_serving_engine_refuses_a_looped_stack():
    from kubedl_tpu.models.serving import ServingEngine

    config = small_looped()
    params = llama.init(config, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="ServingEngine visits each layer once"):
        ServingEngine(params, config, slots=2, max_len=64)


def test_pipelined_forward_refuses_a_looped_stack():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("stage",))
    tokens = jnp.zeros((2, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="the pipelined forward visits"):
        llama.forward_pipelined_and_aux({}, tokens, small_looped(), mesh)


def test_hf_import_refuses_the_family_by_name():
    from types import SimpleNamespace

    from kubedl_tpu.models.import_hf import config_from_hf

    with pytest.raises(ValueError, match="total_ut_steps"):
        config_from_hf(SimpleNamespace(model_type="ouro", total_ut_steps=4))


def test_chunked_loss_and_a_bad_pass_count_are_refused():
    with pytest.raises(ValueError, match="total_ut_steps"):
        llama.LlamaConfig.tiny(total_ut_steps=0)
    config = dataclasses.replace(small_looped(), ce_chunks=4)
    params = llama.init(config, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="ce_chunks"):
        llama.loss_fn(params, tokens_of(CFG, 1), config)


# -- through the trainer --------------------------------------------------------


def test_trainer_main_trains_the_preset_and_records_the_loops_counters(
        tmp_path, monkeypatch):
    from kubedl_tpu.obs import load_spans
    from kubedl_tpu.train import trainer

    published = llama.LlamaConfig.ouro_2_6b
    monkeypatch.setattr(llama.LlamaConfig, "ouro_2_6b", staticmethod(
        lambda **kw: published(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                               n_kv_heads=4, d_ff=128, max_seq_len=64, **kw)))
    trace_dir = str(tmp_path / "trace")
    monkeypatch.setenv("KUBEDL_MESH", "data=-1")
    monkeypatch.setenv("KUBEDL_TRACE_DIR", trace_dir)
    monkeypatch.setenv("KUBEDL_TRACE_ID", "0" * 32)
    monkeypatch.setenv("POD_NAME", "loop-worker-0")
    assert trainer.main(["--model", "ouro-2.6b", "--batch", "8", "--seq-len", "17",
                         "--steps", "4", "--log-every", "2"]) == 0
    steps = [s for s in load_spans(trace_dir)
             if s["name"] in ("train.compile", "train.step")]
    assert len(steps) == 4
    for s in steps:
        a = s["attrs"]
        assert a["loop_passes"] == 4 and a["loop_layer_applications"] == 8
        assert sum(a[f"loop_exit_mass_{t}"] for t in range(1, 5)) == pytest.approx(1.0, abs=1e-3)
        assert a["loop_ce_4"] > 0 and a["loop_exit_entropy"] > 0
    assert steps[-1]["attrs"]["loss"] < steps[0]["attrs"]["loss"] + 0.5
    # and `kubedl-tpu trace` shows the passes and the exit distribution
    from kubedl_tpu.cli import _span_detail

    detail = _span_detail(steps[-1]["attrs"])
    assert detail.startswith("step=4 passes=4 exit=0.")
    assert len(detail.split("exit=")[1].split("/")) == 4
    assert _span_detail({"step": 3}) == "step=3"
