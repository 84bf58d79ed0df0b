"""The `train_afmoe` runner: `runners/train_hybrid.py`'s Run (its window,
its traced steps, the state back to the seed every `restore_every`
steps, `held_rows_off_uniform`) for a model of gated grouped-query
attention layers, windowed with RoPE or full with none by layer, four
norms a layer, with a shared expert beside the routed ones (Trinity's
`afmoe` layers). It replaces the configuration's translation, the
weights' shapes, the FLOP count and the reference, and carries the first
step's `attn_gate_mean` and `attn_gate_spread` beside the reference's own.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding

from benchmarks import check, flops_afmoe, weights_trinity
from benchmarks.reference import trinity_ref
from benchmarks.runners import train_hybrid as hybrid
from kubedl_tpu.models import llama
from kubedl_tpu.parallel.mesh import ShardingRules, build_mesh
from kubedl_tpu.parallel.train_step import make_train_step

COUNTERS = ("attn_gate_mean", "attn_gate_spread")  # compared with the reference's


def numbers(program: Dict, reference: Dict) -> Dict[str, float]:
    """`train_hybrid.numbers` and two more: the relative gaps of the first
    step's `attn_gate_mean` (`attn_gate_gap`: a gate left out reads 1) and
    `attn_gate_spread` (`attn_gate_spread_gap`) from the reference's. The
    spread, the mean of (gate - 1/2)^2, holds the gate's projection to its
    precision: rounding noise in the logits widens the sigmoid's spread
    whatever its sign, where the mean is moved by it only in second order
    (PERF.md section 2)."""
    values = hybrid.numbers(program, reference)
    for name, gap in (("attn_gate_mean", "attn_gate_gap"),
                      ("attn_gate_spread", "attn_gate_spread_gap")):
        ref = reference["counters"][name]
        values[gap] = abs(program["counters"][name] - ref) / abs(ref)
    return values


def afmoe_config(cfg: Dict, seen_len: int) -> "llama.LlamaConfig":
    """The published keys as the program's config. Nothing but names
    changes here; what the program lacks is an error."""
    for key, wired in (("score_func", "sigmoid"), ("route_norm", True), ("n_group", 1),
                       ("num_expert_groups", 1), ("num_limited_groups", 1),
                       ("topk_group", 1), ("hidden_act", "silu"), ("mup_enabled", True),
                       ("rope_scaling", None), ("tie_word_embeddings", False)):
        if cfg[key] != wired:
            raise ValueError(f"{key} {cfg[key]!r} is not wired")
    windows = tuple(flops_afmoe.layer_windows(cfg))
    hd = flops_afmoe.flops.head_dim(cfg)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim_override=hd if hd != cfg["hidden_size"] // cfg["num_attention_heads"] else None,
        d_ff=cfg["intermediate_size"], max_seq_len=seen_len,
        rope_theta=float(cfg["rope_theta"]), rms_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=False, dtype=dtypes[cfg["torch_dtype"]],
        remat=cfg["remat"] != "none",
        remat_policy="dots" if cfg["remat"] == "dots" else None,
        ce_chunks=int(cfg.get("ce_chunks", 0)),
        layer_windows=windows, layer_rope=tuple(w is not None for w in windows),
        attn_gate=True, qk_norm=True, post_block_norms=True,
        embed_scale=float(cfg["hidden_size"]) ** 0.5,
        n_experts=cfg["router_outputs"], n_experts_held=cfg["num_experts"],
        first_expert=cfg["first_expert"], expert_top_k=cfg["num_experts_per_tok"],
        n_dense_layers=cfg["num_dense_layers"],
        d_ff_expert=cfg["moe_intermediate_size"], moe_router="sigmoid",
        n_shared_experts=cfg["num_shared_experts"],
        routed_scaling_factor=float(cfg["route_scale"]),
        moe_norm_eps=float(cfg["router_norm_eps"]),
    )


class Run(hybrid.Run):
    def build(self) -> None:
        """The program's objects, built as trainer.main builds them."""
        opt = self.cell["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"optimizer {opt['name']!r} is not wired")
        self.config = afmoe_config(self.cfg, self.seen_len)
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
        self.make_weights = weights_trinity.maker(self.cfg, self.param_shardings)

    def _steps_taken(self) -> List[Dict]:
        steps = super()._steps_taken()
        if steps and "counters" not in self.readings:  # the first step's
            self.readings["counters"] = {k: float(steps[0][k]) for k in COUNTERS}
        return steps

    def _counted(self, record: Dict) -> Dict:
        """The record with its steps' counters (the moe_* and gmm_* summed
        over them, the attn_* averaged) and the FLOPs those steps required
        by that count."""
        steps = self._steps_taken()
        summed = [k for k in steps[0] if k.startswith(("moe_", "gmm_"))]
        counters = {k: float(sum(float(m[k]) for m in steps)) for k in summed}
        counters["moe_load_max_over_mean"] /= len(steps)
        for k in steps[0]:
            if k.startswith("attn_"):
                counters[k] = float(sum(float(m[k]) for m in steps)) / len(steps)
        record["counters"] = counters
        record["rows_held_by_step"] = [float(m["moe_rows_held"]) for m in steps]
        record["restores"], self.restores = self.restores, 0
        record["required_flops"] = flops_afmoe.step_flops(
            self.cfg, self.batch, self.seen_len,
            counters["moe_rows_held"] / len(steps))["total"] * len(steps)
        return record

    def setup(self) -> None:
        self.readings["rows_held_even"] = (
            flops_afmoe.expert_layers(self.cfg)
            * flops_afmoe.uniform_rows_held(self.cfg, self.tokens_per_step))
        self.readings["rows_held_by_step"] = []
        hybrid.base.Run.setup(self)
        self._steps_taken()
        # the restore's program, as train_hybrid compiles it: here and not
        # in the window, its outputs in set-up's state's own shardings
        t0 = time.perf_counter()
        make, init = weights_trinity.make_fn(self.cfg), self.init_state.jit
        self.reseed = jax.jit(
            lambda state, key: init(make(key)), donate_argnums=0, keep_unused=True,
            out_shardings=jax.tree_util.tree_map(lambda leaf: leaf.sharding, self.state))
        self.state = jax.block_until_ready(self._restored(self.state))
        self.restores = 0
        self.phases["run_setup_s"] += time.perf_counter() - t0

    def reference(self, mode: str = "f32", fault: Optional[str] = None) -> Dict:
        """The plain reference's readings over the same first batches."""
        ref = trinity_ref.Reference(
            self.cfg, self.cell, self.seed, self.devices, mode=mode, fault=fault)
        t0 = time.perf_counter()
        out = ref.run(self.first_batches, int(self.cell["reference"]["steps"]))
        # where the reference's time went, beside set-up's phases in the result
        self.phases[f"reference_{mode}_{fault or 'sound'}_s"] = time.perf_counter() - t0
        self.phases.update({f"reference_{k}": v for k, v in out["seconds"].items()})
        return out

    def verify(self, mode: str = "f32", fault: Optional[str] = None):
        """The program against the float32 reference; with a `mode` or a
        `fault`, that control in the program's place against it."""
        self.reference_readings = self.reference()
        program = self.readings
        if mode != "f32" or fault:
            program = self.reference(mode, fault)
        values = numbers(program, self.reference_readings)
        return check.decide(values, self.cell.get("limits", {}))
