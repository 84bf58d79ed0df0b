"""The `train_latent` runner: `runners/train_hybrid.py`'s Run (its window,
its traced steps, the state back to the seed every `restore_every`
steps, `held_rows_off_uniform`) for a model of latent-attention blocks on
several residual streams, with a shared expert beside the routed ones and
a multi-token prediction module. It replaces the configuration's
translation, the weights' shapes, the FLOP count and the reference, and
carries the first step's `ce`, `mtp_ce` and `hc_res_offdiag` beside the
reference's own.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding

from benchmarks import check, flops_latent, weights_xing
from benchmarks.reference import xing_ref
from benchmarks.runners import train_hybrid as hybrid
from kubedl_tpu.models import llama
from kubedl_tpu.parallel.mesh import ShardingRules, build_mesh
from kubedl_tpu.parallel.train_step import make_train_step

COUNTERS = ("ce", "mtp_ce", "hc_res_offdiag")  # compared with the reference's


def numbers(program: Dict, reference: Dict) -> Dict[str, float]:
    """`train_hybrid.numbers` and two more, carried along: the relative
    gaps of the first step's `mtp_ce` and `hc_res_offdiag` from the
    reference's (a module left out of the loss reads mtp_ce 0, streams
    that never mix hc_res_offdiag 0)."""
    values = hybrid.numbers(program, reference)
    for name in ("mtp_ce", "hc_res_offdiag"):
        ref = reference["counters"][name]
        values[f"{name}_gap"] = abs(program["counters"][name] - ref) / abs(ref)
    return values


def latent_config(cfg: Dict, seen_len: int) -> "llama.LlamaConfig":
    """The published keys as the program's config. Nothing but names
    changes here; what the program lacks is an error."""
    for key, wired in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                       ("n_group", 1), ("topk_group", 1), ("norm_topk_prob", True),
                       ("hidden_act", "silu"), ("attention_bias", False),
                       ("moe_layer_freq", 1), ("tie_word_embeddings", False)):
        if cfg[key] != wired:
            raise ValueError(f"{key} {cfg[key]!r} is not wired")
    sc = cfg["rope_scaling"]
    if sc["type"] != "yarn":
        raise ValueError(f"rope_scaling type {sc['type']!r} is not wired")
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        max_seq_len=seen_len, rope_theta=float(cfg["rope_theta"]),
        rope_scaling=llama.RopeScaling(
            kind="yarn", factor=float(sc["factor"]),
            original_max_position_embeddings=int(sc["original_max_position_embeddings"]),
            beta_fast=float(sc["beta_fast"]), beta_slow=float(sc["beta_slow"]),
            mscale=float(sc["mscale"]), mscale_all_dim=float(sc["mscale_all_dim"])),
        rms_eps=float(cfg["rms_norm_eps"]), tie_embeddings=False,
        dtype=dtypes[cfg["torch_dtype"]],
        remat=cfg["remat"] != "none",
        remat_policy="dots" if cfg["remat"] == "dots" else None,
        ce_chunks=int(cfg.get("ce_chunks", 0)),
        kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=cfg["q_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        hc_mult=cfg["hc_mult"], hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hc_eps=float(cfg["hc_eps"]),
        hc_res_clamp_min=float(cfg["mhc_h_res_clamp_min"]),
        hc_res_clamp_max=float(cfg["mhc_h_res_clamp_max"]),
        n_experts=cfg["router_outputs"], n_experts_held=cfg["n_routed_experts"],
        first_expert=cfg["first_expert"], expert_top_k=cfg["num_experts_per_tok"],
        n_dense_layers=cfg["first_k_dense_replace"],
        d_ff_expert=cfg["moe_intermediate_size"], moe_router="sigmoid",
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        moe_norm_eps=float(cfg["router_norm_eps"]),
        num_nextn_predict_layers=cfg["num_nextn_predict_layers"],
        mtp_loss_weight=float(cfg["mtp_loss_weight"]),
    )


class Run(hybrid.Run):
    def build(self) -> None:
        """The program's objects, built as trainer.main builds them."""
        opt = self.cell["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"optimizer {opt['name']!r} is not wired")
        self.config = latent_config(self.cfg, self.seen_len)
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
        self.make_weights = weights_xing.maker(self.cfg, self.param_shardings)

    def _steps_taken(self) -> List[Dict]:
        steps = super()._steps_taken()
        if steps and "counters" not in self.readings:  # the first step's
            self.readings["counters"] = {k: float(steps[0][k]) for k in COUNTERS}
        return steps

    def _counted(self, record: Dict) -> Dict:
        """The record with its steps' counters (the moe_* and gmm_* summed
        over them, the hc_* and mtp_* averaged) and the FLOPs those steps
        required by that count."""
        steps = self._steps_taken()
        summed = [k for k in steps[0] if k.startswith(("moe_", "gmm_"))]
        counters = {k: float(sum(float(m[k]) for m in steps)) for k in summed}
        counters["moe_load_max_over_mean"] /= len(steps)
        for k in steps[0]:
            if k.startswith(("hc_", "mtp_")) or k == "ce":
                counters[k] = float(sum(float(m[k]) for m in steps)) / len(steps)
        record["counters"] = counters
        record["rows_held_by_step"] = [float(m["moe_rows_held"]) for m in steps]
        record["restores"], self.restores = self.restores, 0
        record["required_flops"] = flops_latent.step_flops(
            self.cfg, self.batch, self.seen_len,
            counters["moe_rows_held"] / len(steps))["total"] * len(steps)
        return record

    def setup(self) -> None:
        self.readings["rows_held_even"] = (
            flops_latent.expert_blocks(self.cfg)
            * flops_latent.uniform_rows_held(self.cfg, self.tokens_per_step))
        self.readings["rows_held_by_step"] = []
        hybrid.base.Run.setup(self)
        self._steps_taken()
        # the restore's program, as train_hybrid compiles it: here and not
        # in the window, its outputs in set-up's state's own shardings
        t0 = time.perf_counter()
        make, init = weights_xing.make_fn(self.cfg), self.init_state.jit
        self.reseed = jax.jit(
            lambda state, key: init(make(key)), donate_argnums=0, keep_unused=True,
            out_shardings=jax.tree_util.tree_map(lambda leaf: leaf.sharding, self.state))
        self.state = jax.block_until_ready(self._restored(self.state))
        self.restores = 0
        self.phases["run_setup_s"] += time.perf_counter() - t0

    def reference(self, mode: str = "f32", fault: Optional[str] = None) -> Dict:
        """The plain reference's readings over the same first batches."""
        ref = xing_ref.Reference(
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
