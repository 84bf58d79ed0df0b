#!/usr/bin/env python3
"""The quickest proof that kubedl-tpu still starts on the chip.

    python chip_smoke.py             # one chip: kernels, train x2, serve
    python chip_smoke.py --chips 4   # fsdp: 4 against the one-chip job

One chip belongs to one process at a time, so this parent never imports
JAX: every phase runs in a child that owns the chip and has exited
before the next starts. The kernel phase is a child of this script; the
train and serve phases are JAXJobs applied to a real operator
(`python -m kubedl_tpu.cli --tpu-slices=... operator`), admitted over
its slice pool and run by its local executor as pod processes, and what
is checked is read back through the operator's own `get` / `describe` /
`logs` surface. Any phase that fails raises; the last line of stdout is
the device the children reported, and says "ok" only when all passed.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
MODEL = "bench-1b"  # the widest preset one v5e holds, at full depth
BATCH = 8
# the trainer's loss runs on tokens[:, :-1]: 1,025 gives the model 1,024,
# the shortest sequence flash_attention takes on a TPU (FLASH_MIN_SEQ).
# At --seq-len 1024 the model sees 1,023 and the step holds no kernel.
SEQ_LEN = 1025
STEPS = 12
# fsdp changes the order of every reduction (gathered weights, scattered
# gradients) and the parameters are bf16: per-step losses of the two
# layouts must agree to this relative tolerance
LOSS_RTOL = 1e-2


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# kernel phase (runs in a child: `chip_smoke.py --phase kernels`)
# ---------------------------------------------------------------------------


def kernels_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from kubedl_tpu.models.moe import _row_tile
    from kubedl_tpu.ops.flash_attention import (
        attention_reference, flash_attention)
    from kubedl_tpu.ops.gmm import gmm, gmm_scaled, gmm_swiglu
    from kubedl_tpu.train.coordinator import report_devices

    report_devices()  # the same line the pods log
    platform = jax.devices()[0].platform
    check(platform == "tpu", f"kernel phase ran on {platform}")
    key = jax.random.PRNGKey(seed)
    f32 = jnp.float32

    def compare(name, kernel_fn, ref_fn, args, ref_args, n_kernels, tol):
        """Outputs and gradients of kernel_fn against ref_fn; the lowered
        program must hold at least n_kernels Mosaic calls."""
        def scalar(fn):
            def wrapped(*a):
                out = fn(*a).astype(f32)
                # a fixed non-uniform cotangent, so the backward kernels
                # see more than ones
                w = jnp.cos(jnp.arange(out.size, dtype=f32)).reshape(out.shape)
                return jnp.sum(out * w), out
            return wrapped

        grad_args = tuple(
            i for i, a in enumerate(args) if jnp.issubdtype(a.dtype, jnp.floating))
        run = jax.jit(jax.value_and_grad(
            scalar(kernel_fn), argnums=grad_args, has_aux=True))
        text = run.lower(*args).as_text()
        calls = text.count("tpu_custom_call")
        check(calls >= n_kernels,
              f"{name}: {calls} tpu_custom_call in the lowered program, "
              f"expected >= {n_kernels} (interpret mode or a fallback?)")
        t0 = time.perf_counter()
        (_, out), grads = jax.block_until_ready(run(*args))
        first_s = time.perf_counter() - t0
        # the reference is float32 all the way: at the default precision
        # a TPU rounds f32 matmul operands to bf16 (the kernels themselves
        # keep theirs: Mosaic refuses a bf16 matmul at fp32 precision)
        with jax.default_matmul_precision("highest"):
            (_, want), want_grads = jax.block_until_ready(
                jax.jit(jax.value_and_grad(
                    scalar(ref_fn), argnums=grad_args, has_aux=True))(*ref_args))
        errs = {}
        for label, got, ref in [("out", out, want)] + [
                (f"d{i}", g, r) for i, g, r in zip(grad_args, grads, want_grads)]:
            check(got.shape == ref.shape, f"{name} {label}: shape {got.shape}")
            check(bool(jnp.isfinite(got).all()), f"{name} {label}: not finite")
            # reduced on the device: only the scalar crosses to the host
            errs[label] = float(jnp.max(jnp.abs(got.astype(f32) - ref))
                                / jnp.max(jnp.abs(ref)))
        print(f"kernel {name}: tpu_custom_call={calls} first_call_s={first_s:.2f} "
              + " ".join(f"{k}_err={v:.2e}" for k, v in errs.items()),
              flush=True)
        worst = max(errs.values())
        check(worst <= tol, f"{name}: error {worst:.3e} over {tol:.0e}")

    # -- flash attention, forward and backward ------------------------------
    for shape, window in [((4, 16, 2048, 128), None),
                          ((4, 8, 1024, 128), None),
                          ((4, 8, 1024, 128), 256)]:
        key, kq, kk, kv = jax.random.split(key, 4)
        q, k, v = (jax.random.normal(x, shape, jnp.bfloat16)
                   for x in (kq, kk, kv))
        compare(
            f"flash{shape}" + (f"/window{window}" if window else ""),
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, window=window),
            lambda q, k, v: attention_reference(
                q, k, v, causal=True, window=window),
            (q, k, v), tuple(x.astype(f32) for x in (q, k, v)),
            n_kernels=3, tol=2e-2)

    # -- grouped matmuls at the bench MoE shape and a 64-expert shape ------
    # (routed rows, d, ffn, experts): 8 x 1,024 tokens top-2 of 4 on the
    # 150M backbone; 8,192 rows over 64 experts at OLMoE's widths
    for rows, d, ffn, e in [(16384, 1024, 2816, 4), (8192, 2048, 1024, 64)]:
        tile = _row_tile(rows, e)
        m = rows + e * tile
        key, kx, kw, kw3, kt = jax.random.split(key, 5)
        x = jax.random.normal(kx, (m, d), jnp.bfloat16)
        w1 = jax.random.normal(kw, (e, d, ffn), jnp.bfloat16) * d ** -0.5
        w3 = jax.random.normal(kw3, (e, d, ffn), jnp.bfloat16) * d ** -0.5
        te = jnp.sort(jax.random.randint(kt, (m // tile,), 0, e)).astype(jnp.int32)
        amax = jnp.max(jnp.abs(w1.astype(f32)), axis=1)  # [E, N]
        scale = jnp.maximum(amax, 1e-8) / 127.0
        q8 = jnp.round(w1.astype(f32) / scale[:, None, :]).astype(jnp.int8)
        ones = jnp.ones((e, ffn), f32)

        def tiles(a):  # [m, k] -> [tiles, tile, k]
            return a.reshape(m // tile, tile, a.shape[-1])

        def ref_gmm(x, w, s=None):
            out = jnp.einsum("tmk,tkn->tmn", tiles(x), w[te])
            if s is not None:
                out = out * s[te][:, None, :]
            return out.reshape(m, -1)

        tag = f"({m}x{d}x{ffn},E={e},tile={tile})"
        compare(f"gmm{tag}",
                lambda x, w: gmm(x, w, te, row_tile=tile), ref_gmm,
                (x, w1), (x.astype(f32), w1.astype(f32)),
                n_kernels=3, tol=2e-2)
        compare(f"gmm_swiglu{tag}",
                lambda x, a, b, s1, s3: gmm_swiglu(
                    x, a, b, te, s1, s3, row_tile=tile),
                lambda x, a, b, s1, s3: jax.nn.silu(ref_gmm(x, a, s1))
                * ref_gmm(x, b, s3),
                (x, w1, w3, ones, ones),
                (x.astype(f32), w1.astype(f32), w3.astype(f32), ones, ones),
                n_kernels=7, tol=3e-2)
        compare(f"gmm_scaled/int8{tag}",
                lambda x, s: gmm_scaled(
                    x, q8.astype(x.dtype), te, s, row_tile=tile),
                lambda x, s: ref_gmm(x, q8.astype(f32), s),
                (x, scale), (x.astype(f32), scale),
                n_kernels=2, tol=2e-2)


# ---------------------------------------------------------------------------
# the operator, and the surface the smoke reads it through
# ---------------------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Operator:
    """`python -m kubedl_tpu.cli --tpu-slices=<pool> operator` as a child
    process, and the kubectl-style client commands against it."""

    def __init__(self, pool: str, workdir: str) -> None:
        self.port = free_port()
        self.server = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(workdir, "operator.log")
        self.env = dict(os.environ, PYTHONPATH=ROOT)
        self.log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "kubedl_tpu.cli", f"--tpu-slices={pool}",
             "operator", "--metrics-port", str(self.port),
             "--no-enable-leader-election"],
            cwd=ROOT, env=self.env, stdout=self.log, stderr=subprocess.STDOUT)
        deadline = time.monotonic() + 60
        while True:
            check(self.proc.poll() is None,
                  f"operator exited {self.proc.returncode}: {self.tail()}")
            try:
                urllib.request.urlopen(self.server + "/healthz", timeout=2)
                return
            except OSError:
                check(time.monotonic() < deadline, "operator never served")
                time.sleep(0.2)

    def tail(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()[-2000:]

    def cli(self, *args: str) -> str:
        out = subprocess.run(
            [sys.executable, "-m", "kubedl_tpu.cli", *args,
             "--server", self.server],
            cwd=ROOT, env=self.env, capture_output=True, text=True, timeout=120)
        check(out.returncode == 0,
              f"cli {' '.join(args)} exited {out.returncode}: "
              f"{out.stdout[-1000:]} {out.stderr[-1000:]}")
        return out.stdout

    def apply(self, manifest: dict, workdir: str) -> None:
        path = os.path.join(workdir, manifest["metadata"]["name"] + ".json")
        with open(path, "w") as f:
            json.dump(manifest, f)  # JSON is YAML
        self.cli("apply", "-f", path)

    def phase(self, name: str) -> str:
        m = re.search(r"^Status:\s+(\S+)", self.cli("describe", "jaxjob", name),
                      re.M)
        check(m is not None, f"describe jaxjob {name} shows no status")
        return m.group(1)

    def logs(self, pod: str) -> str:
        return self.cli("logs", pod)

    def stop(self) -> None:
        # SIGINT, not SIGTERM: the operator's own shutdown path stops the
        # executor, which kills whatever pod a failed phase left running
        self.proc.send_signal(signal.SIGINT)
        try:
            self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        self.log.close()


def jaxjob(name: str, command: list, chips: int, pool: str,
           mesh: dict | None = None, env: dict | None = None) -> dict:
    spec = {
        "runPolicy": {"cleanPodPolicy": "None",
                      "schedulingPolicy": {"tpuSlice": pool}},
        "jaxReplicaSpecs": {"Worker": {
            "replicas": 1,
            "restartPolicy": "Never",
            "template": {"spec": {"containers": [{
                "name": "jax",
                "image": "kubedl/jax-tpu:latest",
                "command": command,
                # JAX_LOG_COMPILES makes the pod log its compile-cache hits
                "env": {"JAX_LOG_COMPILES": "1", **(env or {})},
                "resources": {"limits": {"google.com/tpu": chips}},
            }]}},
        }},
    }
    if mesh:
        spec["mesh"] = mesh
    return {"apiVersion": "kubedl-tpu.io/v1alpha1", "kind": "JAXJob",
            "metadata": {"name": name, "namespace": "default"}, "spec": spec}


DEVICE_LINE = re.compile(
    r"^devices: platform=(\S+) device_kind=(.+) count=(\d+)$", re.M)
STEP_LINE = re.compile(r"^step (\d+): loss=(\S+) step/s=(\S+) tok/s=(\S+)$", re.M)


def device_of(log: str, who: str) -> dict:
    m = DEVICE_LINE.search(log)
    check(m is not None, f"{who} logged no device line:\n{log[-1500:]}")
    return {"platform": m.group(1), "kind": m.group(2), "count": int(m.group(3))}


def train_job(op: Operator, workdir: str, name: str, pool: str, chips: int,
              mesh: dict | None = None, env: dict | None = None,
              timeout: float = 600.0) -> dict:
    """One trainer JAXJob to JobSucceeded; what its pod logged."""
    op.apply(jaxjob(
        name,
        [sys.executable, "-m", "kubedl_tpu.train.trainer", "--model", MODEL,
         "--batch", str(BATCH), "--seq-len", str(SEQ_LEN),
         "--steps", str(STEPS), "--log-every", "1"],
        chips, pool, mesh=mesh, env=env), workdir)
    pod = f"{name}-worker-0"
    deadline = time.monotonic() + timeout
    while True:
        phase = op.phase(name)
        if phase == "Succeeded":
            break
        check(phase != "Failed",
              f"job {name} Failed:\n{op.cli('describe', 'jaxjob', name)}\n"
              f"{op.logs(pod)[-3000:]}")
        check(time.monotonic() < deadline,
              f"job {name} still {phase} after {timeout:.0f}s:\n"
              f"{op.logs(pod)[-3000:]}")
        time.sleep(1.0)
    log = op.logs(pod)
    # step time from tok/s: step/s is printed to two decimals, too coarse
    # for a first step that takes half a minute
    steps = [(int(n), float(loss), BATCH * (SEQ_LEN - 1) / float(tok_s))
             for n, loss, _, tok_s in STEP_LINE.findall(log)]
    check([n for n, _, _ in steps] == list(range(1, STEPS + 1)),
          f"job {name} logged steps {[n for n, _, _ in steps]}:\n{log[-2000:]}")
    losses = [loss for _, loss, _ in steps]
    check(all(math.isfinite(l) for l in losses),
          f"job {name}: losses not finite: {losses}")
    times = sorted(s for _, _, s in steps[2:])
    out = {
        "device": device_of(log, name),
        "losses": losses,
        "first_step_s": steps[0][2],
        "steady_step_s": times[len(times) // 2],
        "memory": re.findall(r"(\d+)=(\d+)MiB", "".join(
            re.findall(r"^device memory after init: (.*)$", log, re.M))),
        # JAX_LOG_COMPILES=1 (set in the manifest) makes JAX say so
        "step_from_cache": "Persistent compilation cache hit for 'jit_train_step'" in log,
    }
    print(f"{name}: devices {out['device']}")
    print(f"{name}: first step (compile"
          f"{', served from the compile cache' if out['step_from_cache'] else ''}) "
          f"{out['first_step_s']:.2f}s, "
          f"median steady step {out['steady_step_s']:.3f}s "
          f"({BATCH * (SEQ_LEN - 1) / out['steady_step_s']:.0f} tok/s)")
    print(f"{name}: losses " + " ".join(f"{l:.4f}" for l in losses))
    print(f"{name}: device memory after init (MiB) "
          + " ".join(f"{d}={b}" for d, b in out["memory"]), flush=True)
    op.cli("delete", "jaxjob", name)
    return out


def serve_job(op: Operator, workdir: str, pool: str, seed: int) -> dict:
    """The serving JAXJob: a handful of HTTP completions, one streamed,
    then stopped the way the README stops a job (`delete jaxjob`)."""
    import random

    name, port = "smoke-serve", free_port()
    pod = f"{name}-worker-0"
    op.apply(jaxjob(
        name,
        [sys.executable, "-m", "kubedl_tpu.train.serve", "--model", MODEL,
         "--allow-fresh-init", "--slots", "8", "--max-len", "1024",
         "--bind", "127.0.0.1", "--port", str(port)],
        1, pool), workdir)
    base = f"http://127.0.0.1:{port}"
    deadline = time.monotonic() + 420
    while True:
        try:
            urllib.request.urlopen(base + "/healthz", timeout=2)
            break
        except OSError:
            phase = op.phase(name)
            check(phase not in ("Failed", "Succeeded"),
                  f"server job {phase} before serving:\n{op.logs(pod)[-3000:]}")
            check(time.monotonic() < deadline,
                  f"server not up after 420s:\n{op.logs(pod)[-3000:]}")
            time.sleep(1.0)

    def post(body: dict, timeout: float = 300.0):
        req = urllib.request.Request(
            base + "/generate", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=timeout)

    rnd = random.Random(seed)
    vocab, new = 32000, 16
    prompts = [[rnd.randrange(vocab) for _ in range(n)] for n in (12, 96, 300)]
    answered = tokens_out = 0
    t0 = time.perf_counter()
    first = []
    for prompt in prompts:
        with post({"tokens": prompt, "max_new_tokens": new}) as r:
            toks = json.load(r)["tokens"]
        check(len(toks) == new and all(0 <= t < vocab for t in toks),
              f"completion of a {len(prompt)}-token prompt: {toks}")
        first.append(toks)
        answered += 1
        tokens_out += len(toks)
    # greedy: the same prompt again gives the same tokens
    with post({"tokens": prompts[0], "max_new_tokens": new}) as r:
        again = json.load(r)["tokens"]
    check(again == first[0], f"greedy repeat differs: {first[0]} vs {again}")
    answered += 1
    tokens_out += len(again)
    # streamed: one event per token, and the summary equals the plain answer
    events = []
    with post({"tokens": prompts[1], "max_new_tokens": new, "stream": True}) as r:
        check(r.headers["Content-Type"].startswith("text/event-stream"),
              "stream=true did not answer with an event stream")
        for raw in r:
            raw = raw.strip()
            if raw.startswith(b"data: "):
                events.append(json.loads(raw[len(b"data: "):]))
    streamed = [e["token"] for e in events[:-1]]
    check(events[-1].get("done") and events[-1]["tokens"] == streamed == first[1],
          f"streamed {streamed} vs plain {first[1]}")
    answered += 1
    tokens_out += len(streamed)
    # the batch form rides separate slots and must agree too
    with post({"requests": [{"tokens": p, "max_new_tokens": new}
                            for p in prompts]}) as r:
        batch = [x["tokens"] for x in json.load(r)["results"]]
    check(batch == first, f"batched {batch} vs single {first}")
    answered += len(batch)
    tokens_out += sum(len(t) for t in batch)
    wall = time.perf_counter() - t0
    device = device_of(op.logs(pod), name)
    op.cli("delete", "jaxjob", name)
    deadline = time.monotonic() + 60
    while True:  # the pod's process must be gone before the chip is free
        try:
            urllib.request.urlopen(base + "/healthz", timeout=2)
        except OSError:
            break
        check(time.monotonic() < deadline, "server still up 60s after delete")
        time.sleep(0.5)
    print(f"{name}: devices {device}")
    print(f"{name}: {answered} requests answered (1 streamed, 1 batch of "
          f"{len(batch)}), {tokens_out} tokens returned in {wall:.1f}s, "
          f"greedy repeat and stream agree", flush=True)
    return {"device": device}


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------


def files_under(root: str) -> set:
    return {os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs}


def one_chip(seed: int, workdir: str) -> dict:
    t0 = time.perf_counter()
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--phase", "kernels",
         "--seed", str(seed)],
        cwd=ROOT, env=dict(os.environ, PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=900)
    sys.stdout.write(out.stdout)
    check(out.returncode == 0,
          f"kernel phase exited {out.returncode}:\n{out.stderr[-4000:]}")
    devices = [device_of(out.stdout, "kernel phase")]
    print(f"phase kernels: ok in {time.perf_counter() - t0:.0f}s", flush=True)

    # jax-free at import: where the pods keep compiled programs unless
    # JAX_COMPILATION_CACHE_DIR names another place
    from kubedl_tpu.train.coordinator import (
        DEFAULT_COMPILE_CACHE_DIR as DEFAULT_CACHE)

    op = Operator("v5e-1", workdir)
    try:
        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE
        before = files_under(cache_dir), files_under(DEFAULT_CACHE)
        t0 = time.perf_counter()
        first = train_job(op, workdir, "smoke-train-1", "v5e-1", 1)
        written = files_under(cache_dir) - before[0]
        # a machine that comes with a warm cache directory serves even
        # the first job from it; otherwise the first job must fill it
        check(written or first["step_from_cache"],
              f"the first job wrote nothing under {cache_dir}")
        check(cache_dir == DEFAULT_CACHE
              or files_under(DEFAULT_CACHE) == before[1],
              f"JAX_COMPILATION_CACHE_DIR is set, yet {DEFAULT_CACHE} grew")
        second = train_job(op, workdir, "smoke-train-2", "v5e-1", 1)
        check(second["losses"] == first["losses"],
              "the same job on the same seeds gave other losses")
        print(f"compile cache {cache_dir}: {len(written)} entries written by "
              f"the first job; first step {first['first_step_s']:.2f}s in the "
              f"first job, {second['first_step_s']:.2f}s in the second")
        check(second["step_from_cache"],
              "the second job's train step was not served from the cache")
        print(f"phase train: ok in {time.perf_counter() - t0:.0f}s", flush=True)
        t0 = time.perf_counter()
        served = serve_job(op, workdir, "v5e-1", seed)
        print(f"phase serve: ok in {time.perf_counter() - t0:.0f}s", flush=True)
    finally:
        op.stop()
    devices += [first["device"], second["device"], served["device"]]
    check(all(d == devices[0] for d in devices),
          f"the phases disagree about the device: {devices}")
    check(devices[0]["count"] == 1, f"one chip expected: {devices[0]}")
    return devices[0]


def four_chips(workdir: str) -> dict:
    """Sharded training, and what it is compared with: the same JAXJob
    under spec.mesh {fsdp: 4} on all four chips of a v5e-4 slice (one
    process driving the four), against one chip on the same seeds."""
    op = Operator("v5e-4", workdir)
    try:
        # the one-chip job owns the whole slice for its turn but must see
        # one chip: libtpu's own way of giving a process a subset
        single = train_job(
            op, workdir, "smoke-train-1chip", "v5e-4", 4,
            env={"TPU_VISIBLE_CHIPS": "0",
                 "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                 "TPU_PROCESS_BOUNDS": "1,1,1"})
        check(single["device"]["count"] == 1,
              f"the one-chip job saw {single['device']}")
        sharded = train_job(
            op, workdir, "smoke-train-fsdp4", "v5e-4", 4, mesh={"fsdp": 4})
    finally:
        op.stop()
    check(sharded["device"]["count"] == 4,
          f"the sharded job saw {sharded['device']}")
    worst = max(abs(a - b) / abs(b)
                for a, b in zip(sharded["losses"], single["losses"]))
    print(f"fsdp4 vs one chip: worst relative loss difference {worst:.2e} "
          f"over {STEPS} steps (tolerance {LOSS_RTOL:.0e})")
    check(worst <= LOSS_RTOL, f"losses disagree: {worst:.3e} > {LOSS_RTOL}")
    per_dev = [int(b) for _, b in sharded["memory"]]
    alone = int(single["memory"][0][1])
    check(len(per_dev) == 4, f"memory reported for {len(per_dev)} devices")
    print(f"state bytes after init: one chip {alone} MiB; fsdp4 per device "
          f"{per_dev} MiB")
    # spread, not sitting on the first: every device holds its quarter
    # (a little more: norms and scalars are replicated)
    check(max(per_dev) < 0.4 * alone and min(per_dev) > 0.5 * max(per_dev),
          f"state is not spread over the four devices: {per_dev} vs {alone}")
    return sharded["device"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", choices=["kernels"], help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase == "kernels":
        kernels_phase(args.seed)
        return 0
    check("jax" not in sys.modules, "the smoke's parent must stay off JAX")
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as workdir:
        device = (one_chip(args.seed, workdir) if args.chips == 1
                  else four_chips(workdir))
    check(device["platform"] == "tpu", f"ran on {device}")
    check(device["count"] == args.chips, f"ran on {device}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
