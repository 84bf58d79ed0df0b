"""The `train_hybrid` runner: `runners/train.py`'s Run (its window, its
traced steps, its readings, the step found in a trace as
`jit_train_step`) for a model whose layers are not all one dense decoder
layer. It replaces what is dense-only there: the configuration's
translation, the weights' shapes, the reference; and it takes the
counters the step returns beside its loss (`llama.loss_and_stats`
through `make_train_step(has_aux=True)`), which the FLOP count needs:
how many rows the router sent to the experts held here. It also holds
the cell's load: the state goes back to the seeded weights every
`restore_every` steps (`Run._restored`), inside the measured time.
"""
from __future__ import annotations

import statistics
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding

from benchmarks import check, flops_hybrid, weights_hybrid
from benchmarks.reference import lfm2_ref
from benchmarks.runners import train as base
from kubedl_tpu.models import llama
from kubedl_tpu.parallel.mesh import ShardingRules, build_mesh
from kubedl_tpu.parallel.train_step import make_train_step

MIXERS = {"conv": "conv", "full_attention": "attention"}


def numbers(program: Dict, reference: Dict) -> Dict[str, float]:
    """check.py's three numbers and four more.

    `grad_gap_median`: the median leaf's gap between the two sides'
    first-gradient norms, where `grad_gap` is the worst leaf's. A top-k
    choice that flips between bfloat16 and float32 moves one router's
    gradient by itself, so the worst leaf of all is a router's on most
    seeds; the median leaf does not feel a single router.
    `grad_gap_steady`: the worst leaf among those that decide no top-k
    choice (all but the reference's `selection_leaves`, the leaves its
    `route` reads), so that one wrong leaf of any other kind fails
    however sound the median is (PERF.md section 2).
    `held_rows_off_uniform`: the widest distance of any one step's
    `moe_rows_held`, over every step the program took, from what even
    routing sends to the experts held here, as a share of that: the
    cell's traffic is a load, and a run that left it measured another.
    `route_flip_share` is the reference's own estimate of the flips,
    carried along."""
    values = check.numbers(program, reference)
    quiet = check.quiet_leaves(reference["grad_norm"])
    gaps = check.leaf_gaps(program["grad_norm"], reference["grad_norm"], skip=quiet)
    values["grad_gap_median"] = statistics.median(gaps.values())
    values["grad_gap_steady"] = max(
        v for k, v in gaps.items() if k not in reference["selection_leaves"])
    if "rows_held_by_step" in program:  # the program's own; a control has none
        even = program["rows_held_even"]
        values["held_rows_off_uniform"] = max(
            abs(r - even) / even for r in program["rows_held_by_step"])
    values["route_flip_share"] = reference["route_flip_share"]
    return values


def hybrid_config(cfg: Dict, seen_len: int) -> "llama.LlamaConfig":
    """The published keys as the program's config. Nothing but names
    changes here; what the program lacks is an error."""
    for key, wired in (("conv_bias", False), ("norm_topk_prob", True),
                       ("use_expert_bias", True), ("routed_scaling_factor", 1)):
        if cfg[key] != wired:
            raise ValueError(f"{key} {cfg[key]!r} is not wired")
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        max_seq_len=seen_len, rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=dtypes[cfg["torch_dtype"]],
        remat=cfg["remat"] != "none",
        remat_policy="dots" if cfg["remat"] == "dots" else None,
        layer_types=tuple(MIXERS[k] for k in cfg["layer_types"]),
        conv_kernel=cfg["conv_L_cache"], qk_norm=True,
        n_experts=cfg["router_outputs"], n_experts_held=cfg["num_experts"],
        first_expert=cfg["first_expert"],
        expert_top_k=cfg["num_experts_per_tok"],
        n_dense_layers=cfg["num_dense_layers"],
        d_ff_expert=cfg["moe_intermediate_size"], moe_router="sigmoid",
    )


class Run(base.Run):
    def build(self) -> None:
        """The program's objects, built as trainer.main builds them."""
        opt = self.cell["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"optimizer {opt['name']!r} is not wired")
        self.config = hybrid_config(self.cfg, self.seen_len)
        mesh_axes = {k: int(v) for k, v in self.cell["mesh"].items()}
        self.mesh = build_mesh(mesh_axes, devices=self.devices)
        rules = ShardingRules()
        spec_tree = llama.param_specs(self.config, rules)
        config, mesh = self.config, self.mesh

        def loss(params, batch):
            return llama.loss_and_stats(params, batch, config, mesh=mesh, rules=rules)

        tx = optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                         eps=opt["eps"], weight_decay=opt["weight_decay"])
        self.init_state, self.jit_step = make_train_step(
            loss, tx, mesh, spec_tree, rules.spec("batch", None), rules,
            has_aux=True)
        self.step_metrics: List[Dict] = []  # every step's, still on the device
        self.restore_every = int(self.cell["restore_every"])
        self.since_seed = 0  # steps the state has taken from the seeded weights
        self.restores = 0

        def train_step(state, batch):
            if self.since_seed >= self.restore_every:
                state = self._restored(state)
            state, metrics = self.jit_step(state, batch)
            self.since_seed += 1
            self.step_metrics.append(metrics)
            return state, metrics

        self.train_step = train_step
        self.param_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), spec_tree)
        self.batch_sharding = NamedSharding(mesh, rules.spec("batch", None))
        self.make_weights = weights_hybrid.maker(self.cfg, self.param_shardings)

    def _restored(self, state):
        """The state made again from the seed, as set-up made it. Trained
        from fresh weights on uniform random tokens, a model's
        activations move fast and every router with them: from about the
        fifteenth step the experts held here lose rows step by step
        (PERF.md section 6, PR 26). The cell's traffic is a load, so the
        state goes back to the seed every `restore_every` steps and the
        load repeats. The seeded state is written into the old state's
        own buffers (donated, as a step's are) and dispatched like a
        step: two states and a step's temporaries do not fit, and a
        restore that waits for the steps in flight leaves the chip to the
        host, whose slow moments (seconds, now and then) then show."""
        self.since_seed = 0
        self.restores += 1
        return self.reseed(state, jax.random.PRNGKey(self.seed))

    def _steps_taken(self) -> List[Dict]:
        """The counters of the steps taken since the last call, read after
        their time was taken; every step's held rows go to the readings
        (`held_rows_off_uniform`)."""
        steps = jax.device_get(self.step_metrics)
        self.step_metrics.clear()
        self.readings["rows_held_by_step"] += [float(m["moe_rows_held"]) for m in steps]
        return steps

    def _counted(self, record: Dict) -> Dict:
        """The record with its steps' counters (summed over them) and the
        FLOPs those steps required by that count."""
        steps = self._steps_taken()
        names = [k for k in steps[0] if k.startswith(("moe_", "gmm_"))]
        counters = {k: float(sum(float(m[k]) for m in steps)) for k in names}
        counters["moe_load_max_over_mean"] /= len(steps)
        record["counters"] = counters
        record["rows_held_by_step"] = [float(m["moe_rows_held"]) for m in steps]
        record["restores"], self.restores = self.restores, 0
        record["required_flops"] = flops_hybrid.step_flops(
            self.cfg, self.batch, self.seen_len,
            counters["moe_rows_held"] / len(steps))["total"] * len(steps)
        return record

    def setup(self) -> None:
        expert_layers = self.cfg["num_hidden_layers"] - self.cfg["num_dense_layers"]
        self.readings["rows_held_even"] = expert_layers * flops_hybrid.uniform_rows_held(
            self.cfg, self.tokens_per_step)
        self.readings["rows_held_by_step"] = []
        super().setup()
        self._steps_taken()
        # the restore's program: compiled here and not in the window, which
        # so starts from the seed. Its outputs carry set-up's state's own
        # shardings, or the step after a restore would be traced anew; an
        # unused argument is not passed, so not donated, unless kept
        t0 = time.perf_counter()
        make, init = weights_hybrid.make_fn(self.cfg), self.init_state.jit
        self.reseed = jax.jit(
            lambda state, key: init(make(key)), donate_argnums=0, keep_unused=True,
            out_shardings=jax.tree_util.tree_map(lambda leaf: leaf.sharding, self.state))
        self.state = jax.block_until_ready(self._restored(self.state))
        self.restores = 0
        self.phases["run_setup_s"] += time.perf_counter() - t0

    def free(self) -> None:
        super().free()
        self.jit_step = self.reseed = None

    def window(self, seconds: float) -> Dict:
        return self._counted(super().window(seconds))

    def traced_steps(self, n: int, trace_dir: str) -> Dict:
        """From the seed again before the profiler starts, so that the
        traced steps run at the load of the window's steps and none of
        them makes weights."""
        if n > self.restore_every:
            raise ValueError(f"{n} traced steps span a restore (every {self.restore_every})")
        self.state = jax.block_until_ready(self._restored(self.state))
        return self._counted(super().traced_steps(n, trace_dir))

    def reference(self, mode: str = "f32", fault: Optional[str] = None) -> Dict:
        """The plain reference's readings over the same first batches."""
        ref = lfm2_ref.Reference(
            self.cfg, self.cell, self.seed, self.devices, mode=mode, fault=fault)
        return ref.run(self.first_batches, int(self.cell["reference"]["steps"]))

    def verify(self, mode: str = "f32", fault: Optional[str] = None):
        """The program against the float32 reference; with a `mode` or a
        `fault`, that control in the program's place against it."""
        self.reference_readings = self.reference()
        program = self.readings
        if mode != "f32" or fault:
            program = self.reference(mode, fault)
        values = numbers(program, self.reference_readings)
        return check.decide(values, self.cell.get("limits", {}))
