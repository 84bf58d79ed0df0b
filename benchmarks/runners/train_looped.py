"""The `train_looped` runner: `runners/train.py`'s Run (its window, its
traced steps, its readings, the step found in a trace as
`jit_train_step`) for a looped decoder: a stack applied several times
over the same weights, with a head and an exit gate after every pass.
It replaces what is plain-decoder-only there: the configuration's
translation, the weights' shapes, the reference; and it takes the
counters the step returns beside its loss (`llama.loss_and_stats`
through `make_train_step(has_aux=True)`): the passes the step ran, which
the FLOP count needs, and each pass's mean exit probability, which
`correct` compares. A dense model's load does not drift, so the state is
never made again inside the window.
"""
from __future__ import annotations

import dataclasses
import itertools
import statistics
from typing import Dict, List, Optional

import jax
import optax
from jax.sharding import NamedSharding

from benchmarks import check, flops_looped, weights_looped
from benchmarks.reference import ouro_ref
from benchmarks.runners import train as base
from kubedl_tpu.models import llama
from kubedl_tpu.parallel.mesh import ShardingRules, build_mesh
from kubedl_tpu.parallel.train_step import make_train_step

PASSES_FAULT = "passes="  # `fault="passes=3"`: the reference with a pass left out


def numbers(program: Dict, reference: Dict) -> Dict[str, float]:
    """check.py's three numbers and two more.

    `grad_gap_median`: the median leaf's gap between the two sides'
    first-gradient norms, where `grad_gap` is the worst leaf's.
    `exit_mass_gap`: the widest distance between the two sides' mean exit
    probability of any pass, over the followed steps; a pass one side did
    not run has none of the mass."""
    values = check.numbers(program, reference)
    gaps = check.leaf_gaps(program["grad_norm"], reference["grad_norm"],
                           skip=check.quiet_leaves(reference["grad_norm"]))
    values["grad_gap_median"] = statistics.median(gaps.values())
    values["exit_mass_gap"] = max(
        abs(a - b)
        for ours, theirs in zip(program["exit_mass"], reference["exit_mass"])
        for a, b in itertools.zip_longest(ours, theirs, fillvalue=0.0))
    return values


def looped_config(cfg: Dict, seen_len: int) -> "llama.LlamaConfig":
    """The published keys as the program's config. Nothing but names
    changes here; what the program lacks is an error."""
    if not hasattr(llama.LlamaConfig, "total_ut_steps"):
        raise SystemExit(
            "benchmarks/runners/train_looped.py: this program has no looped "
            "stack (LlamaConfig lacks total_ut_steps); the cell cannot run")
    if cfg["early_exit_threshold"] != 1:
        raise ValueError(
            f"early_exit_threshold {cfg['early_exit_threshold']!r} is not "
            "wired: training runs every pass")
    if cfg.get("sliding_window") or cfg.get("use_sliding_window"):
        raise ValueError("a window is not wired into this runner")
    return dataclasses.replace(
        base.llama_config(cfg, seen_len), post_block_norms=True,
        total_ut_steps=int(cfg["total_ut_steps"]),
        exit_entropy_beta=float(cfg["exit_entropy_beta"]))


class Run(base.Run):
    def build(self) -> None:
        """The program's objects, built as trainer.main builds them."""
        opt = self.cell["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"optimizer {opt['name']!r} is not wired")
        self.config = looped_config(self.cfg, self.seen_len)
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

        def train_step(state, batch):
            state, metrics = self.jit_step(state, batch)
            self.step_metrics.append(metrics)
            return state, metrics

        self.train_step = train_step
        self.param_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), spec_tree)
        self.batch_sharding = NamedSharding(mesh, rules.spec("batch", None))
        self.make_weights = weights_looped.maker(self.cfg, self.param_shardings)

    def _steps_taken(self) -> List[Dict]:
        """The `loop_*` counters of the steps taken since the last call,
        read after their time was taken."""
        steps = [{k: float(v) for k, v in m.items() if k.startswith("loop_")}
                 for m in jax.device_get(self.step_metrics)]
        self.step_metrics.clear()
        return steps

    def _counted(self, record: Dict) -> Dict:
        """The record with its steps' counters (the counts summed, the
        means averaged over them) and the FLOPs those steps required by
        the passes each one counted."""
        steps = self._steps_taken()
        summed = ("loop_passes", "loop_layer_applications")
        record["counters"] = {
            k: sum(m[k] for m in steps) / (1 if k in summed else len(steps))
            for k in steps[0]}
        record["required_flops"] = sum(
            flops_looped.step_flops(self.cfg, self.batch, self.seen_len,
                                    m["loop_passes"])["total"] for m in steps)
        return record

    def setup(self) -> None:
        super().setup()
        passes = int(self.cfg["total_ut_steps"])
        self.readings["exit_mass"] = [
            [m[f"loop_exit_mass_{t + 1}"] for t in range(passes)]
            for m in self._steps_taken()]

    def free(self) -> None:
        super().free()
        self.jit_step = None

    def window(self, seconds: float) -> Dict:
        return self._counted(super().window(seconds))

    def traced_steps(self, n: int, trace_dir: str) -> Dict:
        return self._counted(super().traced_steps(n, trace_dir))

    def reference(self, mode: str = "f32", fault: Optional[str] = None) -> Dict:
        """The plain reference's readings over the same first batches."""
        passes = None
        if fault and fault.startswith(PASSES_FAULT):
            passes, fault = int(fault[len(PASSES_FAULT):]), None
        ref = ouro_ref.Reference(self.cfg, self.cell, self.seed, self.devices,
                                 mode=mode, fault=fault, passes=passes)
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
