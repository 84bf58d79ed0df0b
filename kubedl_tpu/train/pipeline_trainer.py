"""MPMD pipeline stage trainer — the pod entrypoint for JAXJob
`spec.pipeline.mpmd` (docs/pipeline.md).

Each pod runs ONE stage program built from the operator-injected
KUBEDL_PP_* env (train/pipeline_runtime.runtime_from_env): its layer
chunk + optimizer state, the 1F1B loop, and the serialized boundary
channels to its ring neighbors. Deliberately NOT the SPMD trainer:
stages never join one jax.distributed world — the boundary channel is
the only coupling (which is the point: no global barrier, no Megascale).

The endpoint stages (first and last) drive the data; this entrypoint
feeds the same synthetic next-token stream the SPMD trainer defaults to
(seeded identically on both endpoints so inputs and targets line up).
Checkpointing is stage-local: each stage saves {params, opt_state} under
<checkpoint>/stage-<i>/ on its own Orbax manager, restores on restart,
and banks a final save on SIGTERM — the whole-gang restart semantics of
the SPMD trainer, per stage.

Usage (as a pod command):
    python -m kubedl_tpu.train.pipeline_trainer --model tiny --steps 100

The boundary transport is env-selected (docs/transport.md): DirChannel
over KUBEDL_PP_BOUNDARY_DIR on the local executor, the authenticated
socket plane (KUBEDL_TRANSPORT=socket + KUBEDL_PP_PREV/NEXT_ADDR) in
kube mode — byte-identical boundary payloads either way.

Limitations (documented in docs/pipeline.md): one process per stage
(a stage spanning multiple hosts would need per-stage jax.distributed
wiring on top), synthetic data only (--data-path is refused rather
than silently ignored).
"""
from __future__ import annotations

import argparse
import os
import signal
import sys


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", default=os.environ.get("KUBEDL_MODEL", "tiny"))
    p.add_argument("--steps", type=int,
                   default=int(os.environ.get("KUBEDL_STEPS", 100)))
    p.add_argument("--batch", type=int,
                   default=int(os.environ.get("KUBEDL_BATCH", 8)))
    p.add_argument("--seq-len", type=int,
                   default=int(os.environ.get("KUBEDL_SEQ_LEN", 512)))
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--data-path",
                   default=os.environ.get("KUBEDL_DATA_PATH", ""))
    p.add_argument("--checkpoint-path",
                   default=os.environ.get("KUBEDL_CHECKPOINT_PATH", ""))
    p.add_argument("--checkpoint-interval", type=int,
                   default=int(os.environ.get("KUBEDL_CHECKPOINT_INTERVAL", 0)))
    # JAX profiler window, same contract as the SPMD trainer
    # (train/profile_window.py): N steps after the first, stopped
    # cleanly on preemption too
    p.add_argument("--profile-dir",
                   default=os.environ.get("KUBEDL_PROFILE_DIR", ""))
    p.add_argument("--profile-steps", type=int,
                   default=int(os.environ.get("KUBEDL_PROFILE_STEPS", 5)))
    return p.parse_args(argv)


def _common_restore_step(ckpt_path: str, n_stages: int):
    """Latest checkpoint step present in EVERY stage's dir (None = some
    stage has none — the gang starts fresh together; identical init
    seeds keep that consistent). A step dir mid-write fails the restore
    loudly rather than resuming on a partial save."""
    steps = None
    for s in range(n_stages):
        d = os.path.join(ckpt_path, f"stage-{s}")
        try:
            have = {int(x) for x in os.listdir(d) if x.isdigit()}
        except OSError:
            return None
        steps = have if steps is None else steps & have
        if not steps:
            return None
    return max(steps)


def main(argv=None) -> int:
    import time

    t_main0 = time.perf_counter()
    args = parse_args(argv)
    if args.data_path:
        print("pipeline_trainer supports synthetic data only for now "
              "(--data-path would need per-endpoint shard loaders)",
              file=sys.stderr)
        return 2  # permanent config error (utils/exit_codes.py)

    # flight recorder (docs/observability.md): per-stage step spans +
    # telemetry stream, correlated by the injected gang trace id — the
    # MPMD plane's pods share the job's KUBEDL_TRACE_DIR
    from kubedl_tpu.obs import StepStream, compiles, tracer_from_env

    tracer = tracer_from_env()
    step_stream = StepStream.from_env()

    # trainer.init's children, as in the SPMD trainer (a stage builds no
    # mesh, so there is no init.mesh)
    with tracer.span("init.imports"):
        import jax
        import numpy as np
        import optax

        from kubedl_tpu.models import llama
        from kubedl_tpu.train import coordinator, pipeline_runtime
        from kubedl_tpu.utils.exit_codes import EXIT_TPU_PREEMPTED

    compile_log = compiles.install(tracer)
    with tracer.span("init.backend"):
        coordinator.start_local()

    config = llama.LlamaConfig.config_for(args.model)
    stage = int(os.environ.get("KUBEDL_PP_STAGE", "0"))
    n_stages = int(os.environ.get("KUBEDL_PP_STAGES", "1"))
    tx = optax.adamw(args.lr, weight_decay=0.01)
    with tracer.span("init.state", stage=stage):
        try:
            rt = pipeline_runtime.runtime_from_env(
                config, llama.init(config, jax.random.PRNGKey(0)), tx)
        except ValueError as e:
            print(f"pipeline config invalid: {e}", file=sys.stderr)
            return 2
        endpoint = stage == 0 or stage == n_stages - 1
        print(f"stage {stage}/{n_stages}: layers "
              f"{rt.plan.layer_range(stage)} of {config.n_layers}, "
              f"microbatches={rt.plan.n_microbatches}, "
              f"{'endpoint (drives data)' if endpoint else 'middle'}",
              flush=True)

        # stage-local Orbax checkpoint: {params, opt_state, step}
        mngr = None
        start_step = 0
        if args.checkpoint_path:
            import orbax.checkpoint as ocp

            mngr = ocp.CheckpointManager(
                os.path.join(args.checkpoint_path, f"stage-{stage}"),
                options=ocp.CheckpointManagerOptions(max_to_keep=3, create=True))
            # Restore the latest step EVERY stage has, not this stage's own
            # latest: interval saves are per-stage and a crash can land
            # between them, so stages' latest steps may differ — restoring
            # independently would silently resume the gang at inconsistent
            # optimizer steps (and deadlock the tail, which expects equal
            # remaining step counts). The stage dirs share the checkpoint
            # volume, so every stage can compute the same common step.
            restore = _common_restore_step(args.checkpoint_path, n_stages)
            if restore is not None and os.environ.get(
                    "KUBEDL_CHECKPOINT_RESTORE", "1") == "1":
                with tracer.span("ckpt.restore", step=restore, stage=stage):
                    target = {"params": rt.params, "opt_state": rt.opt_state}
                    abstract = jax.tree.map(
                        ocp.utils.to_shape_dtype_struct, target)
                    restored = mngr.restore(
                        restore, args=ocp.args.StandardRestore(abstract))
                    rt.params, rt.opt_state = (
                        restored["params"], restored["opt_state"])
                start_step = restore
                own = mngr.latest_step()
                note = f" (own latest {own})" if own != restore else ""
                print(f"stage {stage}: restored gang-common checkpoint at "
                      f"step {restore}{note}", flush=True)

    ckpt_stall = {"v": 0.0}

    def save(step, final=False):
        if mngr is None:
            return
        import orbax.checkpoint as ocp

        with tracer.span("ckpt.save", step=step, stage=stage,
                         final=final) as save_span:
            mngr.save(step, args=ocp.args.StandardSave(
                {"params": rt.params, "opt_state": rt.opt_state}))
            if final:
                mngr.wait_until_finished()
                print(f"stage {stage}: saved final checkpoint at step {step}",
                      flush=True)
        ckpt_stall["v"] += save_span.dur

    preempted = {"flag": False}
    signal.signal(signal.SIGTERM, lambda *_: preempted.update(flag=True))

    # the SPMD trainer's profiler window, previously missing here
    # entirely: N steps after the first, stopped idempotently on
    # the preemption path and the finally backstop
    from kubedl_tpu.train.profile_window import window_from_args

    prof = window_from_args(args, start_step)

    tracer.record("trainer.init",
                  duration_s=time.perf_counter() - t_main0,
                  step=start_step, stage=stage, model=args.model)

    rng = np.random.default_rng(1234)  # same stream on BOTH endpoints
    step = start_step
    try:
        for step in range(start_step, args.steps):
            if prof is not None:
                prof.maybe_start(step)
            tokens = None
            if endpoint:
                tokens = rng.integers(
                    0, config.vocab_size,
                    (args.batch, args.seq_len), dtype=np.int32)
            # the step's span is open while it runs (run_step ends in a
            # wait), so a --profile-dir window shows it on its own clock;
            # it reaches the JSONL only under the injected trace env. A
            # step in which this thread compiled (the stage's programs on
            # the first, whatever traces anew later) is written as
            # train.compile with what compiled
            compiles_before = compile_log.count(thread=True)
            with tracer.span("pipeline.step", step=step + 1,
                             stage=stage) as step_span:
                out = rt.run_step(tokens)
                step_span.set(wait_s=round(out["wait_s"], 6))
                if out["loss"] is not None:
                    step_span.set(loss=out["loss"])
                compiled = compile_log.since(compiles_before)
                if compiled:
                    step_span.name = "train.compile"
                    step_span.set(**compiles.summed(compiled))
            if step_stream is not None:
                step_stream.record(
                    step + 1, out["step_s"], data_s=out["wait_s"],
                    loss=out["loss"], compile=bool(compiled),
                    ckpt_s=ckpt_stall["v"])
                ckpt_stall["v"] = 0.0
            if prof is not None and prof.should_stop(step):
                prof.stop()
            if out["loss"] is not None and (
                    step % args.log_every == 0 or step == args.steps - 1):
                print(f"step {step}: loss={out['loss']:.4f} "
                      f"step_s={out['step_s']:.3f} "
                      f"wait_s={out['wait_s']:.3f}", flush=True)
            if (args.checkpoint_interval
                    and (step + 1) % args.checkpoint_interval == 0):
                save(step + 1)
            if preempted["flag"]:
                if prof is not None:
                    prof.stop()
                save(step + 1, final=True)
                tracer.record("trainer.preempted", step=step + 1, stage=stage)
                print(f"stage {stage}: preempted at step {step + 1}; "
                      f"exiting retryable", flush=True)
                return EXIT_TPU_PREEMPTED
    finally:
        # SIGTERM/raise DURING the traced window must still stop the
        # profiler (idempotent: the paths above may have stopped already)
        if prof is not None:
            prof.stop()
        rt.close()
    save(args.steps, final=True)
    tracer.record("trainer.done", step=args.steps, stage=stage)
    if step_stream is not None:
        step_stream.close()
    compile_log.release(tracer)
    tracer.close()
    print(f"stage {stage}: done at step {args.steps}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
