"""The `train` runner: one cell's compiled train step, driven as
`kubedl_tpu/train/trainer.py:main` drives it.

From the program it takes the system under test and nothing else:
`LlamaConfig`, `llama.param_specs`, `llama.loss_fn`, the mesh builder,
`make_train_step` and the optimizer the trainer uses. Weights, token
batches, clocks, the trace and every number reported are the
benchmark's own.

Set-up builds ONE object (the compiled step with its state), drives it
from the seed through the first steps while it reads what `correct`
compares (each step's loss, the first gradient's norms out of the
optimizer state, the parameters' change), and hands that same object to
the measured window.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding

from benchmarks import check, weights
from benchmarks.reference import llama_ref
from kubedl_tpu.models import llama
from kubedl_tpu.parallel.mesh import ShardingRules, build_mesh
from kubedl_tpu.parallel.train_step import make_train_step

# steps kept in flight by the window's loop: the host waits for step n-2
# before it dispatches step n+1, so the device always has work queued and
# the host's clock still follows the device
IN_FLIGHT = 2


def llama_config(cfg: Dict, seen_len: int) -> "llama.LlamaConfig":
    """The published keys as the program's config. Nothing but names
    changes here; an activation the program lacks is an error."""
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {cfg['hidden_act']!r} is not wired")
    hd = cfg.get("head_dim")
    derived = cfg["hidden_size"] // cfg["num_attention_heads"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        max_seq_len=seen_len, rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        sliding_window=cfg.get("sliding_window"),
        head_dim_override=hd if hd and hd != derived else None,
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        dtype=dtypes[cfg["torch_dtype"]],
        remat=cfg["remat"] != "none",
        remat_policy="dots" if cfg["remat"] == "dots" else None,
    )


def _adam_mu(opt_state):
    """The first-moment tree inside an optax chain's state."""
    found = [s.mu for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0]


class Run:
    def __init__(self, cell: Dict, cfg: Dict, seed: int, devices):
        self.cell, self.cfg, self.seed = cell, cfg, int(seed)
        self.devices = list(devices)
        self.batch, self.seen_len = int(cell["batch"]), int(cell["seen_len"])
        self.tokens_per_step = self.batch * self.seen_len
        self.rng = np.random.default_rng(self.seed)
        self.first_batches: List[np.ndarray] = []
        self.readings: Dict = {}
        self.phases: Dict[str, float] = {}  # where set-up's time went
        self.state = None

    # -- feed ---------------------------------------------------------------

    def next_batch(self) -> np.ndarray:
        """Token ids on the host, as the trainer draws them: the sequence
        fed is the seen length plus one (the loss runs on tokens[:, :-1])."""
        return self.rng.integers(
            0, self.cfg["vocab_size"], (self.batch, self.seen_len + 1),
            dtype=np.int32)

    def _put(self, tokens: np.ndarray):
        return jax.device_put(tokens, self.batch_sharding)

    # -- set-up -------------------------------------------------------------

    def build(self) -> None:
        """The program's objects, built as trainer.main builds them."""
        opt = self.cell["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"optimizer {opt['name']!r} is not wired")
        self.config = llama_config(self.cfg, self.seen_len)
        mesh_axes = {k: int(v) for k, v in self.cell["mesh"].items()}
        self.mesh = build_mesh(mesh_axes, devices=self.devices)
        rules = ShardingRules()
        spec_tree = llama.param_specs(self.config, rules)
        config, mesh = self.config, self.mesh

        def loss(params, batch):
            return llama.loss_fn(params, batch, config, mesh=mesh, rules=rules)

        tx = optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                         eps=opt["eps"], weight_decay=opt["weight_decay"])
        self.init_state, self.train_step = make_train_step(
            loss, tx, mesh, spec_tree, rules.spec("batch", None), rules)
        self.param_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), spec_tree)
        self.batch_sharding = NamedSharding(mesh, rules.spec("batch", None))
        self.make_weights = weights.maker(self.cfg, self.param_shardings)

    def setup(self) -> None:
        """Weights from the seed, the state, and the first steps through
        the window's own call and feed, with what `correct` compares read
        on the way. The reference follows all `reference.steps` of them."""
        t0 = time.perf_counter()
        self.build()
        b1 = self.cell["optimizer"]["b1"]
        n_full = int(self.cell["reference"]["steps"])
        tmap = jax.tree_util.tree_map
        # mu_1 = (1 - b1) * g_1 in the moments' own dtype, where 1 - b1 is
        # itself rounded (0.1 is 0.1001 in bf16): divide by what was
        # multiplied, or every leaf reads a thousandth off
        norms_of_mu = jax.jit(lambda mu: tmap(
            lambda m: jnp.sqrt(jnp.sum(jnp.square(m.astype(jnp.float32))))
            / jnp.asarray(1 - b1, m.dtype).astype(jnp.float32), mu))
        change_norms = jax.jit(lambda p, p0: tmap(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32)))), p, p0))

        params = self.make_weights(self.seed)
        self.state = self.init_state(params)
        del params
        jax.block_until_ready(self.state)
        self.phases["build_and_weights_s"] = time.perf_counter() - t0
        losses = []
        for k in range(n_full):
            tokens = self.next_batch()
            self.first_batches.append(tokens)
            self.state, metrics = self.train_step(self.state, self._put(tokens))
            losses.append(float(metrics["loss"]))
            if k == 0:
                self.phases["first_step_s"] = (
                    time.perf_counter() - t0 - self.phases["build_and_weights_s"])
                self.readings["grad_norm"] = jax.device_get(
                    norms_of_mu(_adam_mu(self.state.opt_state)))
            if k == n_full - 1:
                start = self.make_weights(self.seed)
                self.readings["change_norm"] = jax.device_get(
                    change_norms(self.state.params, start))
                del start
        self.readings["loss"] = losses
        jax.block_until_ready(self.state)
        self.phases["run_setup_s"] = time.perf_counter() - t0

    # -- the measured window --------------------------------------------------

    def window(self, seconds: float) -> Dict:
        """Steps dispatched back to back with no read of the loss, until
        the step during which `seconds` ran out (and those in flight
        behind it) has ended. The rate is all tokens over all the time."""
        pending, done_s = [], []
        steps = 0
        t0 = time.perf_counter()
        while True:
            batch = self._put(self.next_batch())
            self.state, metrics = self.train_step(self.state, batch)
            steps += 1
            pending.append(metrics["loss"])
            if len(pending) > IN_FLIGHT:
                pending.pop(0).block_until_ready()
                done_s.append(time.perf_counter() - t0)
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(self.state)
        elapsed = time.perf_counter() - t0
        # step_done_s: when the host saw each awaited step end; a stall
        # inside the window shows as one long interval
        return {"steps": steps, "tokens": steps * self.tokens_per_step,
                "elapsed_s": elapsed, "step_done_s": done_s}

    def traced_steps(self, n: int, trace_dir: str) -> Dict:
        """A handful of steady steps under the profiler, each ended by a
        wait, with the loop's own spans around batch making, dispatch and
        the closing wait."""
        ann = jax.profiler.TraceAnnotation
        step_s = []
        jax.profiler.start_trace(trace_dir)
        t_begin = time.perf_counter()
        try:
            for i in range(n):
                t0 = time.perf_counter()
                with ann("bench.batch"):
                    batch = self._put(self.next_batch())
                with ann("bench.dispatch"):
                    self.state, metrics = self.train_step(self.state, batch)
                with ann("bench.sync"):
                    jax.block_until_ready(metrics["loss"])
                step_s.append(time.perf_counter() - t0)
            jax.block_until_ready(self.state)
            window_s = time.perf_counter() - t_begin
        finally:
            jax.profiler.stop_trace()
        return {"step_s": step_s, "window_s": window_s, "steps": n}

    # -- after the window -------------------------------------------------------

    def memory_peak_bytes(self) -> Optional[int]:
        stats = [d.memory_stats() for d in self.devices]
        if any(s is None for s in stats):
            return None
        return max(int(s["peak_bytes_in_use"]) for s in stats)

    def end_to_end(self, window: Dict, setup_s: float) -> Dict:
        return {
            "train_tokens_per_s": {
                "value": window["tokens"] / window["elapsed_s"], "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }

    def free(self) -> None:
        """Drop the state so that the reference has the chip to itself."""
        self.state = None
        self.init_state = self.train_step = None

    def verify(self, mode: str = "f32", fault: Optional[str] = None):
        """The float32 reference over the same first batches, once the
        window has closed and the state is freed; every number compared
        beside its limit."""
        ref = llama_ref.Reference(
            self.cfg, self.cell, self.seed, self.devices, mode=mode, fault=fault)
        self.reference_readings = ref.run(
            self.first_batches, int(self.cell["reference"]["steps"]))
        del ref
        values = check.numbers(self.readings, self.reference_readings)
        return check.decide(values, self.cell.get("limits", {}))
