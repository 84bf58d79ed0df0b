"""Sharded train step factory — pjit + NamedSharding, no hand-rolled
collectives.

Builds the full SPMD training step for a model: params/opt-state sharded by
the model's param_specs (fsdp/tensor axes), batch sharded over data+fsdp,
gradients and updates computed under jit with donated state so XLA reuses
the buffers in place. Collectives (psum for grads across data, all-gather /
reduce-scatter for fsdp params) are inserted by XLA from the shardings —
the scaling-book recipe, not an NCCL translation.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubedl_tpu.obs import compiles
from kubedl_tpu.parallel.mesh import ShardingRules


@dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array

    def tree_flatten(self):
        return (self.params, self.opt_state, self.step), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten
)


def make_train_step(
    loss_fn: Callable,  # (params, batch) -> scalar loss  [or (loss, aux)]
    tx: optax.GradientTransformation,
    mesh: Mesh,
    param_spec_tree: Any,
    batch_spec: P,
    rules: Optional[ShardingRules] = None,
    accum_steps: int = 1,
    has_aux: bool = False,
) -> Tuple[Callable, Callable]:
    """Returns (init_state, train_step), both jitted over the mesh.

    init_state(params) -> TrainState with sharded params/opt state.
    train_step(state, batch) -> (state, metrics) with donated state.
    accum_steps > 1 accumulates gradients over that many micro-steps
    before applying the update (optax.MultiSteps) — the HBM-for-batch
    trade when the global batch doesn't fit.
    """
    # whoever drives the step (a trainer, the benchmark's runner, a probe
    # under hack/) finds its trace, lowering and compile in the process's
    # compile log (obs/compiles.py)
    compiles.install()
    rules = rules or ShardingRules()
    if accum_steps > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=accum_steps)
    param_sharding = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_spec_tree
    )
    # batch_spec may be one P or a pytree of Ps (e.g. (images, labels));
    # P subclasses tuple, so guard it as a leaf
    batch_sharding = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), batch_spec,
        is_leaf=lambda x: isinstance(x, P),
    )
    repl = NamedSharding(mesh, P())

    params_treedef = jax.tree_util.tree_structure(param_sharding)

    def _like_params(node) -> bool:
        return jax.tree_util.tree_structure(node) == params_treedef

    # the inner functions' names are what a profile shows: the device's
    # "XLA Modules" line reads jit_train_step / jit_init_state, the host's
    # PjitFunction(train_step) (PERF.md section 3)
    def init_state(params):
        # Optimizer moments have the params' shapes but no data
        # dependence on them (zeros), so nothing propagates the params'
        # shardings onto them: left alone they start replicated on every
        # device, and the step compiles twice (replicated moments in,
        # sharded out). Pin every params-shaped subtree of the state.
        opt_state = jax.tree_util.tree_map(
            lambda node: jax.lax.with_sharding_constraint(node, param_sharding)
            if _like_params(node) else node,
            tx.init(params), is_leaf=_like_params)
        return TrainState(params=params, opt_state=opt_state, step=jnp.zeros((), jnp.int32))

    init_jit = jax.jit(init_state, in_shardings=(param_sharding,))

    def train_step(state: TrainState, batch):
        if has_aux:
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, batch)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
            aux = {}
        with jax.named_scope("optimizer"):
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        with jax.named_scope("grad_norm"):
            gnorm = optax.global_norm(grads)
        return (
            TrainState(params=new_params, opt_state=new_opt, step=state.step + 1),
            {"loss": loss, "grad_norm": gnorm, **aux},
        )

    step_jit = jax.jit(
        train_step,
        in_shardings=(None, batch_sharding),
        donate_argnums=(0,),
    )

    def init_from_host(params):
        params = jax.device_put(params, param_sharding)
        return init_jit(params)

    # AOT access (fit checks, ahead-of-time compiles): the inner jit
    # accepts abstract params and its compiled output_shardings give the
    # full TrainState sharding tree — eval_shape alone drops shardings,
    # so an AOT lower of step_jit with plain ShapeDtypeStructs would
    # silently measure a REPLICATED state (tests/test_aot_fit.py)
    init_from_host.jit = init_jit

    return init_from_host, step_jit
