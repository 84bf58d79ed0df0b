"""The main path's Pallas kernels, compiled by the TPU's own compiler.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block shapes off the (8, 128) tiling, kernels GSPMD cannot partition.
The compiler is installed here and compiles for a v5e that is described,
not attached, so these cost no chip time. Nothing runs: a compile that
passes says nothing about results (chip_smoke.py's kernel phase does).

The topology is described inside a module-scoped fixture, never at
import: only one process may load libtpu, and every xdist worker imports
this file. For the same reason all of these live in this one file.
"""

import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from kubedl_tpu.models import llama
from kubedl_tpu.models.moe import _row_tile
from kubedl_tpu.ops.flash_attention import flash_attention
from kubedl_tpu.ops.gmm import gmm, gmm_scaled, gmm_swiglu
from kubedl_tpu.ops.row_gather import gather_rows
from kubedl_tpu.parallel.mesh import ShardingRules, build_mesh
from kubedl_tpu.parallel.train_step import make_train_step


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep these silent
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """The kernels ask jax.default_backend() whether to interpret, and
    here it still says cpu: steer it in the test, not in the program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled.as_text()


def _kernels(text: str) -> int:
    return text.count('custom_call_target="tpu_custom_call"')


# a pallas_call's name= becomes its HLO instruction's name, which the
# profiler's event name starts with and the benchmark's by-name metrics
# match (benchmarks/metrics/flash_{fwd,bwd}_roofline.train.json)
FLASH_INSTRUCTIONS = ("%flash_fwd.", "%flash_bwd_dqkv.")


def _flash_calls(text: str) -> dict:
    """How many instructions each flash kernel's name defines: what
    benchmarks/reducers/flash_roofline.py counts as calls, so a kernel
    split in two under one name would double the least time it credits."""
    return _calls(text, FLASH_INSTRUCTIONS)


def _calls(text: str, names) -> dict:
    return {name: len(re.findall(re.escape(name) + r"\d+ = ", text))
            for name in names}


@pytest.mark.parametrize("shape,window", [
    ((4, 16, 2048, 128), None),
    ((4, 8, 1024, 128), None),
    ((4, 8, 1024, 128), 256),
    # the benchmark's: Mistral's window under its sequence (window-edge,
    # interior and diagonal blocks) and LFM2's padded head size
    ((2, 32, 8192, 128), 4096),
    ((2, 32, 8192, 64), None),
    # the looped cell's: 16 heads of 128, full causal at 8,192
    ((2, 16, 8192, 128), None),
    # the gated-attention cell's: one sequence of 48 heads, its windowed
    # layers' and its full one's
    ((1, 48, 8192, 128), 4096),
    ((1, 48, 8192, 128), None),
])
def test_flash_fwd_bwd_compiles(one_chip, on_tpu, shape, window):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def f(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32))

    text = _compile(jax.value_and_grad(f, argnums=(0, 1, 2)), x, x, x)
    # the forward and the one backward kernel
    assert _kernels(text) == 2, "flash fell back to attention_reference"
    assert _flash_calls(text) == dict.fromkeys(FLASH_INSTRUCTIONS, 1)


def test_flash_with_a_value_width_of_its_own_compiles(one_chip, on_tpu):
    """The latent-attention cell's shape: keys of 192 padded to 256 lanes
    beside values of 128, whole sequences of 8,192 in a scoped VMEM that
    grows by what the wider operands hold, and in the backward by the
    head's whole dQ of 256 lanes."""
    qk = jax.ShapeDtypeStruct((2, 32, 8192, 192), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((2, 32, 8192, 128), jnp.bfloat16, sharding=one_chip)

    def f(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, sm_scale=0.14468).astype(jnp.float32))

    text = _compile(jax.value_and_grad(f, argnums=(0, 1, 2)), qk, qk, v)
    assert _flash_calls(text) == dict.fromkeys(FLASH_INSTRUCTIONS, 1)
    assert "bf16[64,8192,256]" in text and "bf16[64,8192,128]" in text


def test_flash_streamed_fwd_compiles(one_chip, on_tpu):
    """Past STREAM_MIN_SEQ the forward is the K-streaming kernel (serving
    prefill), one block a grid step under `pl.when(live)`."""
    x = jax.ShapeDtypeStruct((1, 8, 16384, 128), jnp.bfloat16,
                             sharding=one_chip)
    text = _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=4096),
        x, x, x)
    assert _kernels(text) == 1 and "%flash_fwd_streamed." in text


def test_flash_unaligned_blocks_raise(one_chip, on_tpu):
    x = jax.ShapeDtypeStruct((1, 8, 1024, 128), jnp.bfloat16,
                             sharding=one_chip)
    with pytest.raises(ValueError, match="multiples of 128"):
        _compile(lambda q: flash_attention(q, q, q, block_q=200), x)


# (rows, d, ffn, experts): the bench MoE cell (batch 8 x 1,024 tokens,
# top-2 of 4) and a 64-expert shape (OLMoE widths, top-8)
GMM_SHAPES = [(16384, 1024, 2816, 4), (65536, 2048, 1024, 64)]


def _gmm_args(one_chip, rows, d, ffn, e):
    tile = _row_tile(rows, e)
    m = rows + e * tile
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    return dict(
        tile=tile,
        x=sds((m, d), jnp.bfloat16),
        w=sds((e, d, ffn), jnp.bfloat16),
        q=sds((e, d, ffn), jnp.int8),
        s=sds((e, ffn), jnp.float32),
        te=sds((m // tile,), jnp.int32),
    )


@pytest.mark.parametrize("rows,d,ffn,e", GMM_SHAPES)
def test_gmm_fwd_bwd_compiles(one_chip, on_tpu, rows, d, ffn, e):
    a = _gmm_args(one_chip, rows, d, ffn, e)

    def f(x, w, te):
        return jnp.sum(gmm(x, w, te, row_tile=a["tile"]).astype(jnp.float32))

    text = _compile(jax.value_and_grad(f, argnums=(0, 1)), a["x"], a["w"], a["te"])
    assert _kernels(text) >= 3  # gmm, dlhs gmm, tgmm
    assert "%gmm." in text and "%gmm_drhs." in text


@pytest.mark.parametrize("rows,d,ffn,e", GMM_SHAPES)
def test_gmm_swiglu_fwd_bwd_compiles(one_chip, on_tpu, rows, d, ffn, e):
    a = _gmm_args(one_chip, rows, d, ffn, e)

    def f(x, w1, w3, te, s1, s3):
        return jnp.sum(gmm_swiglu(
            x, w1, w3, te, s1, s3, row_tile=a["tile"]).astype(jnp.float32))

    text = _compile(jax.value_and_grad(f, argnums=(0, 1, 2)),
                    a["x"], a["w"], a["w"], a["te"], a["s"], a["s"])
    assert _kernels(text) >= 7  # fused fwd + 2 remat + 2 dlhs + 2 tgmm
    assert "%gmm_swiglu." in text and "%gmm_drhs." in text


@pytest.mark.parametrize("rows,d,ffn,e", GMM_SHAPES)
def test_gmm_scaled_int8_fwd_bwd_compiles(one_chip, on_tpu, rows, d, ffn, e):
    a = _gmm_args(one_chip, rows, d, ffn, e)

    def f(x, q, te, s):
        return jnp.sum(gmm_scaled(
            x, q.astype(x.dtype), te, s,
            row_tile=a["tile"]).astype(jnp.float32))

    text = _compile(jax.value_and_grad(f, argnums=(0, 3)),
                    a["x"], a["q"], a["te"], a["s"])
    assert _kernels(text) >= 2  # scaled fwd + dlhs gmm
    assert "%gmm_scaled." in text


# the LFM2 cell's two moves without a bound (combine's forward, permute's
# backward with its sum over k = 4), and float32 rows
@pytest.mark.parametrize("n,c,r,dtype", [
    (69632, 1, 65536, jnp.bfloat16), (69632, 4, 16384, jnp.bfloat16),
    (69632, 4, 16384, jnp.float32)], ids=["combine_fwd", "permute_bwd", "f32"])
def test_row_gather_compiles(one_chip, on_tpu, n, c, r, dtype):
    """Mosaic takes what interpret mode cannot see: copies of whole
    8-row groups, a row picked by a dynamic sublane index, 16-bit rows
    read as words."""
    text = _compile(
        gather_rows,
        jax.ShapeDtypeStruct((n, 2048), dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((c, r), jnp.int32, sharding=one_chip))
    assert _kernels(text) == 1 and "%moe_gather." in text


# loss_fn trains on tokens[:, :-1]: 1,025 gives the model 1,024, the
# shortest sequence flash_attention takes on a TPU (FLASH_MIN_SEQ); at
# 1,024 the model sees 1,023 and the step holds no flash kernel at all
SEQ_LEN = 1025


def _abstract_params(config, sharding_of):
    shapes = jax.eval_shape(
        lambda k: llama.init(config, k), jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, sharding_of(shapes))


def test_moe_loss_default_fused_compiles(one_chip, on_tpu):
    """llama.loss_fn on the bench MoE widths (150M backbone, 4 experts,
    top-2) with moe_fused left at its default; depth cut to two layers."""
    config = llama.LlamaConfig.bench_150m(
        n_layers=2, n_experts=4, expert_top_k=2)
    assert config.moe_fused is None
    params = _abstract_params(
        config, lambda t: jax.tree_util.tree_map(lambda _: one_chip, t))
    tokens = jax.ShapeDtypeStruct((8, SEQ_LEN), jnp.int32, sharding=one_chip)
    text = _compile(
        jax.value_and_grad(lambda p, t: llama.loss_fn(p, t, config)),
        params, tokens)
    assert "gmm_swiglu" in text
    assert _kernels(text) >= 10


def test_looped_loss_holds_one_stacks_kernels_and_a_piece_of_logits(one_chip, on_tpu):
    """A looped stack at the published widths (depth cut to two layers),
    four passes over 4 x 2,048 tokens: the passes are a loop in the
    program, so the gradient holds one stack's flash kernels and not
    four; each pass's head runs in pieces of HEAD_TOKENS tokens inside a
    loop of its own, recomputed in the backward pass, so no array of all
    the step's tokens by the vocabulary exists."""
    config = llama.LlamaConfig.ouro_2_6b(n_layers=2, max_seq_len=2048)
    params = _abstract_params(
        config, lambda t: jax.tree_util.tree_map(lambda _: one_chip, t))
    tokens = jax.ShapeDtypeStruct((4, 2049), jnp.int32, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(
        lambda p, t: llama.loss_fn(p, t, config))).lower(params, tokens).compile()
    text = compiled.as_text()
    assert _flash_calls(text) == dict.fromkeys(FLASH_INSTRUCTIONS, config.n_layers)
    assert text.count(" while(") >= 4  # the passes and the heads, forward and backward
    pieces, vocab = 4 * 2048 // llama.HEAD_TOKENS, config.vocab_size
    assert pieces == 2
    assert f"f32[{4 * 2048},{vocab}]" not in text and f"f32[4,2048,{vocab}]" not in text
    assert f"f32[4,{2048 // pieces},{vocab}]" in text
    # four passes' float32 logits would be 6.4 GB and one pass's 1.6 GB, of
    # which a loss's backward holds three: a piece's three (2.4 GB) and
    # the saved states stay under half of the first
    assert compiled.memory_analysis().temp_size_in_bytes < 3.2e9


# ops/ssm_scan.py's two kernels, by the names a trace is read by
SCAN_INSTRUCTIONS = ("%ssm_scan_fwd.", "%ssm_scan_bwd.")


def _scan_calls(text: str) -> dict:
    return _calls(text, SCAN_INSTRUCTIONS)


# ops/causal_conv.py's two kernels, likewise
CONV_INSTRUCTIONS = ("%ssm_conv_fwd.", "%ssm_conv_bwd.")


def _conv_calls(text: str) -> dict:
    return _calls(text, CONV_INSTRUCTIONS)


def test_state_space_loss_holds_one_piece_of_logits_and_float32_carries(one_chip, on_tpu):
    """A state-space hybrid at the published widths (depth cut to one
    state-space and one attention layer), 2 x 8,192 tokens, the head's
    loss over 7 vocabulary pieces: the chunked scan is its two Pallas
    kernels (the forward again in the remat copy), so no [sequences,
    chunks, heads, 256, 256] array exists in either order and no loop but
    the head's pieces; the state goes from chunk to chunk in float32
    inside the kernels and each chunk's entering state is the forward's
    second output; the convolution is its two kernels too (the forward
    again in the remat copy), reading xBC where it lies in the in
    projection's [2, 8192, 8512] output, so neither a padded nor a float32
    copy of xBC exists; no array of all the step's tokens by the
    vocabulary exists."""
    config = llama.LlamaConfig.granite_4_0_h_micro(
        n_layers=2, layer_types=("ssm", "attention"), max_seq_len=8192, ce_chunks=7)
    params = _abstract_params(
        config, lambda t: jax.tree_util.tree_map(lambda _: one_chip, t))
    tokens = jax.ShapeDtypeStruct((2, 8193), jnp.int32, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(
        lambda p, t: llama.loss_fn(p, t, config))).lower(params, tokens).compile()
    text = compiled.as_text()
    assert _flash_calls(text) == dict.fromkeys(FLASH_INSTRUCTIONS, 1)
    assert _scan_calls(text) == {"%ssm_scan_fwd.": 2, "%ssm_scan_bwd.": 1}
    assert _conv_calls(text) == {"%ssm_conv_fwd.": 2, "%ssm_conv_bwd.": 1}
    assert "[2,8195,4352]" not in text and "f32[2,8192,4352]" not in text
    assert "[2,32,64,256,256]" not in text and "[2,64,32,256,256]" not in text
    assert text.count(" while(") == 2  # the head's pieces, both ways
    assert "f32[32,2,128,4096]" in text  # the states entering each chunk, transposed
    vocab, piece = config.vocab_size, config.vocab_size // 7
    assert f"f32[2,8192,{vocab}]" not in text and f"bf16[2,8192,{vocab}]" not in text
    assert f"f32[2,8192,{piece}]" in text
    # the whole logits in float32 would be 6.6 GB; with the scan's
    # [2, 64, 32, 256, 256] float32 tensors (1.07 GB each) the step held
    # 4.59 GB; now 2.95 GB: a piece's logits and the kernels' operands
    assert compiled.memory_analysis().temp_size_in_bytes < 3.5e9


# ops/causal_conv.py's gated kernels, likewise
SHORT_CONV_INSTRUCTIONS = ("%short_conv_fwd.", "%short_conv_bwd.")


def test_convolution_layer_reads_its_gates_in_place_and_writes_du_whole(one_chip, on_tpu):
    """An LFM2 convolution layer and an attention layer at the published
    widths (dense FFNs, an eighth of the vocabulary), 2 x 8,192 tokens:
    the gates and taps are their two kernels (the forward again in the
    remat copy), reading B, C and z where they lie in the in projection's
    [2, 8192, 6144] output and writing its cotangent whole, so no
    [2, 8192, 2048] third of either is cut out or joined back, and no
    float32 or padded copy of the gates exists."""
    config = llama.LlamaConfig.lfm2_8b_a1b(
        n_layers=2, layer_types=("conv", "attention"), n_dense_layers=2,
        vocab_size=8192, max_seq_len=8192)
    params = _abstract_params(
        config, lambda t: jax.tree_util.tree_map(lambda _: one_chip, t))
    tokens = jax.ShapeDtypeStruct((2, 8193), jnp.int32, sharding=one_chip)
    text = _compile(jax.value_and_grad(lambda p, t: llama.loss_fn(p, t, config)),
                    params, tokens)
    assert _calls(text, SHORT_CONV_INSTRUCTIONS) == {
        "%short_conv_fwd.": 2, "%short_conv_bwd.": 1}
    # the XLA form cuts u in three (bf16[2,8192,2048] slices), pads the
    # gates by the taps' reach ([2,8194,2048]) and joins du's thirds back
    # by padding each to [2,8192,6144]
    assert not re.search(r"\[2,8192,2048\]\{[^}]*\} slice\(", text)
    assert not re.search(r"\[2,8192,6144\]\{[^}]*\} (pad|concatenate)\(", text)
    assert "[2,8194,2048]" not in text


# ops/hyper_mix.py's four kernels, likewise
HC_INSTRUCTIONS = ("%hc_pre_fwd.", "%hc_post_fwd.", "%hc_post_bwd.", "%hc_pre_bwd.")


def test_one_hyper_connection_mapping_compiles_with_no_float32_copy_of_the_streams(
        one_chip, on_tpu):
    """One mapping at the Xing4.0 cell's shape, forward and backward: four
    streams of 3,584 as one [2, 8192, 14336] bf16 array, read and written
    by the four kernels once each; no float32 copy of the streams and no
    [.., 4, 3584] layout of them exists."""
    from kubedl_tpu.models import hyper

    n, d = 4, 3584
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    hc = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: hyper.hc_init(jax.random.PRNGKey(0), d, n)))
    x, y = sds((2, 8192, n * d), jnp.bfloat16), sds((2, 8192, d), jnp.bfloat16)

    def f(x, y, hc, du, dx):
        def mapping(x, y, hc):
            u, onto = hyper.hc_branch(x, hc, n, 20, 1e-6, (-30.0, 30.0))
            return u, hyper.hc_merge(onto, y)
        out, vjp = jax.vjp(mapping, x, y, hc)
        return out, vjp((du, dx))

    compiled = jax.jit(f).lower(x, y, hc, y, x).compile()
    text = compiled.as_text()
    assert _calls(text, HC_INSTRUCTIONS) == dict.fromkeys(HC_INSTRUCTIONS, 1)
    assert "f32[2,8192,14336]" not in text and "[2,8192,4,3584]" not in text
    # the kernels' blocks and the mappings' [tokens, 128] arrays: well
    # under one float32 copy of the streams (0.94 GB)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.9e9


def test_latent_step_holds_each_hyper_connection_kernel_its_times(one_chip, on_tpu):
    """The Xing4.0 preset at its published widths cut to one dense block and
    the module (four mappings), 1,024 tokens: under full remat each
    mapping's `hc_pre_fwd` runs again in the backward pass, and so does
    `hc_post_fwd` of the mappings whose streams the block's recompute
    needs (the mixer's: the FFN reads them), never the FFN's."""
    config = llama.LlamaConfig.xing4_0_29b_a4b(
        n_layers=1, n_dense_layers=1, vocab_size=16384, max_seq_len=1024)
    params = _abstract_params(
        config, lambda t: jax.tree_util.tree_map(lambda _: one_chip, t))
    tokens = jax.ShapeDtypeStruct((1, SEQ_LEN), jnp.int32, sharding=one_chip)
    text = _compile(jax.value_and_grad(lambda p, t: llama.loss_fn(p, t, config)),
                    params, tokens)
    assert _calls(text, HC_INSTRUCTIONS) == {
        "%hc_pre_fwd.": 8, "%hc_post_fwd.": 6, "%hc_post_bwd.": 4, "%hc_pre_bwd.": 4}


def test_state_space_layer_under_fsdp_rides_a_shard_map(topo, on_tpu):
    """One state-space layer at the published widths under fsdp: 4 on the
    described 2x2, a sequence of 1,024 a chip: GSPMD cannot partition a
    Mosaic call, so this compiles only while the scan's and the
    convolution's kernels sit inside a shard_map over `batch`, each chip
    on its own sequence."""
    config = llama.LlamaConfig.granite_4_0_h_micro(
        n_layers=1, layer_types=("ssm",), max_seq_len=1024, ce_chunks=7)
    mesh = build_mesh({"fsdp": 4}, devices=topo.devices)
    rules = ShardingRules()
    params = _abstract_params(
        config, lambda t: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), llama.param_specs(config, rules)))
    tokens = jax.ShapeDtypeStruct(
        (4, 1025), jnp.int32,
        sharding=NamedSharding(mesh, rules.spec("batch", None)))
    text = _compile(jax.value_and_grad(
        lambda p, t: llama.loss_fn(p, t, config, mesh=mesh, rules=rules)),
        params, tokens)
    assert _scan_calls(text) == {"%ssm_scan_fwd.": 2, "%ssm_scan_bwd.": 1}
    assert _conv_calls(text) == {"%ssm_conv_fwd.": 2, "%ssm_conv_bwd.": 1}
    assert "bf16[1,1024,4096]" in text  # a chip's own sequence of x * dt
    assert "all-gather" in text or "all-reduce" in text


def test_sharded_train_step_with_flash_compiles(topo, on_tpu):
    """bench-1b widths under fsdp: 4 on the described 2x2 mesh, flash
    attention on: Mosaic kernels cannot be partitioned by GSPMD, so this
    compiles only while flash sits inside a shard_map. Depth cut to two
    layers; batch 8 x 1,025 as chip_smoke.py --chips 4 runs it."""
    config = llama.LlamaConfig.bench_1b(n_layers=2)
    assert config.use_flash
    mesh = build_mesh({"fsdp": 4}, devices=topo.devices)
    rules = ShardingRules()
    spec_tree = llama.param_specs(config, rules)
    init_state, train_step = make_train_step(
        lambda p, t: llama.loss_fn(p, t, config, mesh=mesh, rules=rules),
        optax.adamw(3e-4), mesh, spec_tree, rules.spec("batch", None), rules)
    params = _abstract_params(
        config, lambda t: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), spec_tree))
    state = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(init_state.jit, params),
        init_state.jit.lower(params).compile().output_shardings)
    tokens = jax.ShapeDtypeStruct(
        (8, SEQ_LEN), jnp.int32,
        sharding=NamedSharding(mesh, rules.spec("batch", None)))
    compiled = train_step.lower(state, tokens).compile()
    text = compiled.as_text()
    assert _kernels(text) >= 2, "flash is not in the sharded step"
    assert text.startswith("HloModule jit_train_step")
    # by its own name inside the shard_map too, one of each a layer
    assert _flash_calls(text) == dict.fromkeys(
        FLASH_INSTRUCTIONS, config.n_layers)
    assert "%shard_map." not in text
    assert "all-gather" in text or "all-reduce" in text
    # the parameters are spread: one device holds about a quarter
    ma = compiled.memory_analysis()
    n_bytes = sum(
        x.size * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(state))
    assert ma.argument_size_in_bytes < 0.3 * n_bytes
