"""The `train_ssm` runner: `runners/train.py`'s Run (its window, its
traced steps, its readings, the step found in a trace as
`jit_train_step`) for a model whose layers are state-space mixers and
attention layers without a position embedding, with scalar multipliers
on the embedding, each branch, the scores and the logits. It replaces
what is plain-decoder-only there: the configuration's translation, the
weights' shapes, the reference; it enters the head's loss through
`ce_chunks`; and it takes the counters the step returns beside its loss
(`llama.loss_and_stats` through `make_train_step(has_aux=True)`): the
state-space layers and chunks the step scanned, the mean step size and
the share of a chunk's incoming state that leaves it. A dense model's
load does not drift, so the state is never made again inside the window.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding

from benchmarks import check, flops_ssm, weights_ssm
from benchmarks.reference import granite_ref
from benchmarks.runners import train as base
from kubedl_tpu.models import llama
from kubedl_tpu.parallel.mesh import ShardingRules, build_mesh
from kubedl_tpu.parallel.train_step import make_train_step

MIXERS = {"mamba": "ssm", "attention": "attention"}
SUMMED = ("ssm_layers", "ssm_chunks")  # over a record's steps; the others are means
DECAY_LEAVES = ("ssm_A_log", "ssm_dt_bias")


def numbers(program: Dict, reference: Dict) -> Dict[str, float]:
    """check.py's three numbers and two more.

    `grad_gap_median`: the median leaf's gap between the two sides'
    first-gradient norms, where `grad_gap` is the worst leaf's.
    `grad_gap_decay`: the worst gap, each over the reference's own norm
    of that leaf, among the leaves through which the loss feels how long
    a head remembers (`DECAY_LEAVES` of every state-space layer). A
    state dropped between chunks moves y by a few percent with either
    sign, which turns the large leaves' gradients and leaves their norms
    where they were; these 64-entry leaves' gradients are sums over what
    each head carried and shrink by a third or more, but their norms are
    a hundredth of the median leaf's, which `grad_gap` divides by."""
    values = check.numbers(program, reference)
    gaps = check.leaf_gaps(program["grad_norm"], reference["grad_norm"],
                           skip=check.quiet_leaves(reference["grad_norm"]))
    values["grad_gap_median"] = statistics.median(gaps.values())
    ours, theirs = (check._flat(side["grad_norm"]) for side in (program, reference))
    values["grad_gap_decay"] = max(
        abs(ours[k] - theirs[k]) / theirs[k] for k in theirs
        if k.endswith(tuple(f"['{name}']" for name in DECAY_LEAVES)))
    return values


def ssm_config(cfg: Dict, seen_len: int) -> "llama.LlamaConfig":
    """The published keys as the program's config. Nothing but names
    changes here; what the program lacks is an error."""
    if not hasattr(llama.LlamaConfig, "ssm_heads"):
        raise SystemExit(
            "benchmarks/runners/train_ssm.py: this program has no state-space "
            "layer (LlamaConfig lacks ssm_heads); the cell cannot run")
    for key, wired in (("mamba_n_groups", 1), ("mamba_conv_bias", True),
                       ("mamba_proj_bias", False), ("attention_bias", False),
                       ("num_local_experts", 0), ("hidden_act", "silu"),
                       ("normalization_function", "rmsnorm"),
                       ("position_embedding_type", "nope"),
                       ("shared_intermediate_size", cfg["intermediate_size"])):
        if cfg[key] != wired:
            raise ValueError(f"{key} {cfg[key]!r} is not wired")
    sizes = weights_ssm.ssm_sizes(cfg)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        max_seq_len=seen_len, rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=dtypes[cfg["torch_dtype"]],
        remat=cfg["remat"] != "none",
        remat_policy="dots" if cfg["remat"] == "dots" else None,
        ce_chunks=int(cfg["ce_chunks"]),
        layer_types=tuple(MIXERS[k] for k in cfg["layer_types"]),
        ssm_heads=sizes["heads"], ssm_head_dim=sizes["head_dim"],
        ssm_state=sizes["state"], ssm_conv_kernel=sizes["taps"],
        ssm_chunk=cfg["mamba_chunk_size"],
        use_rope=False,
        # scores scale by attention_multiplier = query_pre_attn_scalar**-0.5
        query_pre_attn_scalar=float(cfg["attention_multiplier"]) ** -2,
        embed_scale=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
    )


class Run(base.Run):
    def build(self) -> None:
        """The program's objects, built as trainer.main builds them."""
        opt = self.cell["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"optimizer {opt['name']!r} is not wired")
        self.config = ssm_config(self.cfg, self.seen_len)
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
        self.make_weights = weights_ssm.maker(self.cfg, self.param_shardings)

    def _counted(self, record: Dict) -> Dict:
        """The record with the `ssm_*` counters of the steps taken since
        the last call (the counts summed, the means averaged over them),
        read after their time was taken, and the FLOPs those steps
        required."""
        steps = [{k: float(v) for k, v in m.items() if k.startswith("ssm_")}
                 for m in jax.device_get(self.step_metrics)]
        self.step_metrics.clear()
        record["counters"] = {
            k: sum(m[k] for m in steps) / (1 if k in SUMMED else len(steps))
            for k in steps[0]}
        record["required_flops"] = len(steps) * flops_ssm.step_flops(
            self.cfg, self.batch, self.seen_len)["total"]
        return record

    def setup(self) -> None:
        super().setup()
        self.readings["counters"] = self._counted({})["counters"]

    def free(self) -> None:
        super().free()
        self.jit_step = None

    def window(self, seconds: float) -> Dict:
        return self._counted(super().window(seconds))

    def traced_steps(self, n: int, trace_dir: str) -> Dict:
        return self._counted(super().traced_steps(n, trace_dir))

    def reference(self, mode: str = "f32", fault: Optional[str] = None) -> Dict:
        """The plain reference's readings over the same first batches."""
        ref = granite_ref.Reference(
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
