"""MNIST training program — the minimum end-to-end workload.

The BASELINE.json anchor config is the reference's example/tf/tf_job_mnist.yaml
(a single-worker TF MNIST job). This is its TPU-native equivalent: a JAX MLP
classifier, jit-compiled so the matmuls land on the MXU in bf16, data-parallel
over all visible devices via shard_map-free pjit sharding. Dataset is
synthetic MNIST-shaped (the sandbox has no egress; the compute path — input
pipeline -> sharded train step -> metrics — is identical to real MNIST).

Usage (as a pod command):
    python -m kubedl_tpu.train.mnist --steps 200 --batch 256

Prints `step/sec` and exits 0 on success.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=int(os.environ.get("MNIST_STEPS", 100)))
    parser.add_argument("--batch", type=int, default=int(os.environ.get("MNIST_BATCH", 256)))
    parser.add_argument("--hidden", type=int, default=512)
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument("--steps-per-call", type=int,
                        default=int(os.environ.get("MNIST_STEPS_PER_CALL", 25)),
                        help="steps chained on-device per dispatch (lax.scan) "
                             "— host<->device round-trips, not compute, bound "
                             "small-model step rate")
    args = parser.parse_args(argv)

    from kubedl_tpu.train import coordinator

    info = coordinator.initialize()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("data",))
    repl = NamedSharding(mesh, P())

    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    params = {
        "w1": (jax.random.normal(k1, (784, args.hidden), jnp.float32) * 0.02),
        "b1": jnp.zeros((args.hidden,), jnp.float32),
        "w2": (jax.random.normal(k2, (args.hidden, 10), jnp.float32) * 0.02),
        "b2": jnp.zeros((10,), jnp.float32),
    }
    params = jax.device_put(params, repl)
    tx = optax.adam(args.lr)
    opt_state = jax.device_put(tx.init(params), repl)

    def loss_fn(params, x, y):
        # bf16 activations keep the matmuls on the MXU fast path
        h = jnp.maximum(x.astype(jnp.bfloat16) @ params["w1"].astype(jnp.bfloat16)
                        + params["b1"].astype(jnp.bfloat16), 0)
        logits = (h @ params["w2"].astype(jnp.bfloat16) + params["b2"].astype(jnp.bfloat16))
        logits = logits.astype(jnp.float32)
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    # k steps chained on-device per dispatch: at MLP sizes the ~1 ms
    # host->device dispatch, not the math, bounds step rate. Clamp k so a
    # small --steps runs exactly as many steps as asked (k must divide; pick
    # the largest divisor-ish chunk <= steps rather than rounding steps up).
    k = max(1, min(args.steps_per_call, args.steps))
    while args.steps % k:
        k -= 1

    @jax.jit
    def train_many(params, opt_state, xs, ys):
        def body(carry, xy):
            params, opt_state = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, *xy)
            updates, opt_state = tx.update(grads, opt_state)
            params = optax.apply_updates(params, updates)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(body, (params, opt_state), (xs, ys))
        return params, opt_state, losses[-1]

    # synthetic MNIST-shaped batches: k distinct batches per call, each
    # sharded over the data axis
    rng = np.random.default_rng(info.process_id)
    batch = max(args.batch // max(len(devices), 1) * len(devices), len(devices))
    batch_sharded = NamedSharding(mesh, P(None, "data"))
    xs = jax.device_put(
        jnp.asarray(rng.standard_normal((k, batch, 784), dtype=np.float32)),
        batch_sharded,
    )
    ys = jax.device_put(
        jnp.asarray(rng.integers(0, 10, (k, batch), dtype=np.int32)),
        batch_sharded,
    )

    n_calls = args.steps // k  # k divides steps exactly (clamp loop above)
    total_steps = args.steps

    # compile, then time; dispatch is asynchronous, so the timed region
    # ends in a wait for the device
    params, opt_state, loss = train_many(params, opt_state, xs, ys)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(n_calls):
        params, opt_state, loss = train_many(params, opt_state, xs, ys)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    steps_per_sec = total_steps / dt
    print(f"steps={total_steps} batch={batch} loss={float(loss):.4f} "
          f"step/sec={steps_per_sec:.1f} devices={len(devices)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
