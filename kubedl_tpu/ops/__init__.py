"""Pallas TPU kernels and the attention ops built on them."""
import jax


def interpret() -> bool:
    """The one rule for every Pallas kernel in ops/: interpret mode only
    where the backend is the CPU (tests, virtual meshes). Any other
    backend compiles the kernel or raises."""
    return jax.default_backend() == "cpu"
