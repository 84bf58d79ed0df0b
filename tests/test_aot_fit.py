"""AOT compile-and-fit check for the v5p-32 north star (SURVEY.md §7
step 10, BASELINE.json): the Llama-7B JAXJob train step must keep
fitting per-device HBM as shardings/remat evolve.

The real config (examples/jax_job_llama7b.yaml) runs data=2 x fsdp=8
over 16 v5p chips with global batch 16, seq 4096. On the 8-device
virtual CPU mesh the data axis is virtualized by scaling the batch:
data=1, fsdp=8, batch 8 gives each device the SAME parameter shard
(1/8th) and the SAME per-device batch rows (8) as the real slice, so
`compiled.memory_analysis()` reports a faithful per-device footprint
without any TPU. jax.eval_shape keeps the 6.7B parameters abstract —
nothing is materialized.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from kubedl_tpu.models import llama
from kubedl_tpu.parallel.mesh import ShardingRules, build_mesh
from kubedl_tpu.parallel.train_step import make_train_step

V5P_HBM_BYTES = 95 * 1024**3  # per-chip HBM budget

# XLA-CPU's buffer assignment is structurally pessimistic vs the real TPU
# compile: no latency-hiding scheduler (all fsdp all-gather temporaries
# counted live at once) and donation aliasing partially fails on CPU, so
# the analyzed footprint overshoots what the chip actually holds. The
# guard threshold is CALIBRATED to the healthy baseline instead:
# 78.9 GiB analyzed with correct shardings+remat (jax 0.9.0, PR 25),
# where remat keeps each layer's flash out and lse; 78.3 GiB at PR 25's
# parent, which kept nothing (the batch axis spans fsdp, so a device
# holds one row of 4,095 tokens: 32 MiB a layer). Round 5's jax read
# 101.1 GiB for the same program, under a 105 GiB guard. Known
# regression signatures move it far past this:
# replicated state measured 14 GiB over its baseline (round 5), remat off
# adds the full unsaved activation set (tens of GiB).
# Real-chip fit is ~25-30 GiB by hand count (state 5 + remat boundaries
# 8.6 + chunkable logits 8.4 + transients), far under the 95 GiB budget.
CPU_ANALYSIS_BUDGET = 82 * 1024**3


@pytest.mark.slow
def test_llama7b_train_step_fits_v5p_hbm():
    config = llama.LlamaConfig.llama_7b()
    assert config.remat, "7B fit depends on remat; the config must keep it on"
    mesh = build_mesh({"data": 1, "fsdp": 8})
    rules = ShardingRules()
    spec_tree = llama.param_specs(config, rules)

    def loss(p, t):
        return llama.loss_fn(p, t, config, mesh=mesh, rules=rules)

    init_state, train_step = make_train_step(
        loss, optax.adamw(1e-3), mesh, spec_tree,
        rules.spec("batch", None), rules)
    p_shapes = jax.eval_shape(
        lambda k: llama.init(config, k), jax.random.PRNGKey(0))
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(p_shapes))
    assert 6.0e9 < n_params < 7.5e9, f"not a 7B config: {n_params/1e9:.2f}B"
    # eval_shape drops shardings, and train_step's in_shardings is None
    # (it follows its committed inputs) — lowering with plain
    # ShapeDtypeStructs would measure a REPLICATED 3x-params state
    # (~115 GiB/device, observed). Recover the true TrainState sharding
    # tree from the compiled init's output shardings.
    init_compiled = init_state.jit.lower(p_shapes).compile()
    state_shapes = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(init_state.jit, p_shapes),
        init_compiled.output_shardings)
    sharded_leaves = [
        l for l in jax.tree_util.tree_leaves(state_shapes)
        if l.sharding is not None and not l.sharding.is_fully_replicated]
    assert sharded_leaves, "init output shardings came back unsharded"
    # per-device rows = 8 == the real slice's batch 16 over data=2
    tokens = jax.ShapeDtypeStruct((8, 4096), jnp.int32)

    compiled = train_step.lower(state_shapes, tokens).compile()
    ma = compiled.memory_analysis()
    # donated state aliases args onto outputs; live per-device footprint
    # = non-aliased args + outputs + XLA temp buffers
    est = (ma.argument_size_in_bytes + ma.output_size_in_bytes
           - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    gib = est / 1024**3
    assert est < CPU_ANALYSIS_BUDGET, (
        f"7B train step analyzes at {gib:.1f} GiB/device — past the "
        f"calibrated {CPU_ANALYSIS_BUDGET / 1024**3:.0f} GiB guard (healthy "
        f"baseline 78.9); a sharding or remat change regressed the "
        f"north-star v5p fit")
    # and a floor: if the analysis ever reports nonsense (e.g. the state
    # stopped being threaded through), fail loudly instead of greenlighting
    assert est > 5 * 1024**3, f"implausibly small footprint: {gib:.2f} GiB"
