"""Pallas TPU kernels and the attention ops built on them.

Every layer of models/ takes its kernel by one rule, `takes_kernel`
joined to the kernel's own `supports(...)` of the shapes (elsewhere the
layer's XLA form runs), and runs it under a mesh by `on_rows`.
"""
import jax


def interpret() -> bool:
    """Interpret mode for every Pallas kernel in ops/ only where the
    backend is the CPU (tests, virtual meshes). Any other backend compiles
    the kernel or raises. Asked at call time, so that a script may steer
    `jax.default_backend()`."""
    return jax.default_backend() == "cpu"


def takes_kernel(mesh=None) -> bool:
    """Whether a layer may take its Pallas kernel under `mesh` (None: no
    mesh), its shapes aside: not where the kernels are interpreted, and
    not under a mesh that shards the model's width (a `tensor` axis above
    1), since a kernel sees whole rows."""
    return not interpret() and (mesh is None or mesh.shape.get("tensor", 1) == 1)


def on_rows(fn, mesh, in_specs, out_specs):
    """`fn`, which calls a Pallas kernel, run by each device on its own
    shard of `in_specs` where `mesh` has more than one device (GSPMD
    cannot partition a Mosaic call): a `shard_map` whose transpose sums a
    whole (replicated) argument's cotangent over the devices. `fn` itself
    under no mesh or one device."""
    if mesh is None or mesh.size == 1:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)
