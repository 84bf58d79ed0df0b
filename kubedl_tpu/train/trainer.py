"""Trainer — the JAXJob workload runtime (what the operator launches).

Ties the compute path together: coordinator bootstrap from injected env
(train/coordinator.py) -> mesh from KUBEDL_MESH (parallel/mesh.py) -> Llama
model (models/llama.py) -> sharded train step (parallel/train_step.py) ->
Orbax checkpointing with preemption-safe save/resume.

Checkpoint/resume is first-class (SURVEY.md §5 — the reference delegates it
entirely to training code): SIGTERM (TPU maintenance/preemption surfaces as
SIGTERM, ref pkg/util/train/train_util.go semantics) triggers a final save
and exit with the retryable preemption code, so the operator's ExitCode
policy restarts the pod and the trainer resumes from the latest step.

Usage (as a pod command):
    python -m kubedl_tpu.train.trainer --model tiny --steps 100
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
import time
from collections import deque
from typing import Optional

from kubedl_tpu.obs.compiles import summed

# steps the recorder leaves on the device while it waits for an older one
# (benchmarks/runners/train.py keeps the same two in its window)
IN_FLIGHT = 2


class StepRecorder:
    """The flight recorder's side of the step loop: one ``train.step``
    span record and one step-stream record a step, without draining the
    device.

    A step's loss is read only once ``IN_FLIGHT`` later steps have been
    dispatched, so the device always has work queued. ``step_s`` is the
    time between two consecutive awaited completions (from the step's own
    start where nothing was in flight before it), and the loss written
    for a step is that step's own. Whatever waits anyway (save,
    preemption, resize, eval, the log line) calls ``flush()`` first."""

    def __init__(self, tracer, step_stream) -> None:
        self.tracer = tracer
        self.step_stream = step_stream
        self.pending: deque = deque()
        self.last_done = 0.0  # perf_counter at the last awaited completion
        # checkpoint stall the loop felt since the last step record (the
        # async save's device->host copy + any final wait), folded into
        # the next heartbeat's ckpt_s
        self.ckpt_stall = 0.0

    def dispatched(self, step: int, loss, t_start: float, data_s: float,
                   dispatch_s: float, compiled, counters=None) -> None:
        """`compiled`: the compile log's records of what this thread
        compiled inside the step's dispatch (obs/compiles.py `since`;
        nothing for an ordinary step): such a step is written as
        `train.compile` with their `fun`, times and `cache`.
        `counters`: what the step returned beside its loss and gradient
        norm (an expert model's `moe_*` / `gmm_*`, a looped stack's
        `loop_*`, a state-space model's `ssm_*`, a model with convolution
        layers `short_conv_*`, a several-stream model's `hc_*`, a
        multi-token prediction module's `ce` and `mtp_*`, a model with
        gated attention or RoPE chosen by layer `attn_*`:
        llama.loss_and_stats);
        they ride the step's record, read when its loss is."""
        if compiled:
            # the steps before it ended while the host compiled, or
            # earlier: their records end where this dispatch began, so
            # that the compile lies in this step's record and in no other
            self.flush(until=t_start + data_s)
        self.pending.append((step, loss, t_start, data_s, dispatch_s, compiled,
                             counters or {}))
        while len(self.pending) > IN_FLIGHT:
            self._await_oldest()

    def flush(self, until: Optional[float] = None) -> None:
        while self.pending:
            self._await_oldest(until)

    def _await_oldest(self, until: Optional[float] = None) -> None:
        """`until`: a perf_counter reading the step is known to have been
        over by, where the wait itself comes later than that."""
        (step, loss, t_start, data_s, dispatch_s, compiled,
         counters) = self.pending.popleft()
        with self.tracer.span("train.wait", export=False, step=step) as wait:
            loss_v = float(loss)
        counters = {k: float(v) for k, v in counters.items()}
        read_at = time.perf_counter()
        now = read_at if until is None else min(read_at, until)
        step_s = max(now - max(self.last_done, t_start), 0.0)
        self.last_done = now
        self.tracer.record(
            "train.compile" if compiled else "train.step",
            duration_s=step_s, end_ts=time.time() - (read_at - now),
            step=step, loss=loss_v,
            data_wait_s=round(data_s, 6), dispatch_s=round(dispatch_s, 6),
            wait_s=round(wait.dur, 6), **counters,
            **(summed(compiled) if compiled else {}))
        if self.step_stream is not None:
            self.step_stream.record(
                step, step_s, data_s=data_s, loss=loss_v,
                compile=bool(compiled), ckpt_s=self.ckpt_stall)
            self.ckpt_stall = 0.0


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", default=os.environ.get("KUBEDL_MODEL", "tiny"),
                   choices=["tiny", "bench-1b", "llama-7b", "lfm2-8b-a1b",
                            "ouro-2.6b", "granite-4.0-h-micro",
                            "xing4.0-29b-a4b", "trinity-large-preview"])
    p.add_argument("--steps", type=int, default=int(os.environ.get("KUBEDL_STEPS", 100)))
    p.add_argument("--batch", type=int, default=int(os.environ.get("KUBEDL_BATCH", 8)))
    p.add_argument("--seq-len", type=int, default=int(os.environ.get("KUBEDL_SEQ_LEN", 512)))
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--lr-schedule", choices=["constant", "cosine"],
                   default=os.environ.get("KUBEDL_LR_SCHEDULE", "constant"),
                   help="cosine: warmup then cosine decay to 10%% of --lr "
                        "over --steps")
    p.add_argument("--warmup-steps", type=int,
                   default=int(os.environ.get("KUBEDL_WARMUP_STEPS", 0)),
                   help="linear LR warmup steps (used by both schedules)")
    p.add_argument("--grad-clip", type=float,
                   default=float(os.environ.get("KUBEDL_GRAD_CLIP", 0.0)),
                   help="clip gradients by global norm (0 = off)")
    p.add_argument("--eval-every", type=int,
                   default=int(os.environ.get("KUBEDL_EVAL_EVERY", 0)),
                   help="evaluate eval-set loss every N steps (0 = off)")
    p.add_argument("--eval-batches", type=int,
                   default=int(os.environ.get("KUBEDL_EVAL_BATCHES", 4)),
                   help="batches per eval pass (a fixed set each time)")
    p.add_argument("--eval-data-path",
                   default=os.environ.get("KUBEDL_EVAL_DATA_PATH", ""),
                   help="separate shards for a TRUE held-out set; without "
                        "it the eval set is a fixed probe drawn from the "
                        "training distribution (overlaps training data "
                        "after ~1 epoch)")
    p.add_argument("--accum-steps", type=int,
                   default=int(os.environ.get("KUBEDL_ACCUM_STEPS", 1)),
                   help="gradient accumulation micro-steps per update")
    p.add_argument("--log-every", type=int, default=10)
    # token shards (flat int32 files; native/loader.py). Unset -> synthetic.
    p.add_argument("--data-path", default=os.environ.get("KUBEDL_DATA_PATH", ""),
                   help="glob of token shard files, e.g. /data/shard-*.bin")
    p.add_argument("--data-seed", type=int,
                   default=int(os.environ.get("KUBEDL_DATA_SEED", 0)),
                   help="shared shuffle seed (same on every process)")
    p.add_argument("--checkpoint-path",
                   default=os.environ.get("KUBEDL_CHECKPOINT_PATH", ""))
    p.add_argument("--checkpoint-interval",
                   type=int, default=int(os.environ.get("KUBEDL_CHECKPOINT_INTERVAL", 0)))
    p.add_argument("--checkpoint-keep",
                   type=int, default=int(os.environ.get("KUBEDL_CHECKPOINT_KEEP", 3)))
    # JAX profiler / XProf hook (SURVEY.md §5: "TPU side gets JAX
    # profiler/XProf hooks" — net-new, the reference has no profiling)
    p.add_argument("--lora-rank", type=int,
                   default=int(os.environ.get("KUBEDL_LORA_RANK", 0)),
                   help="train low-rank adapters instead of full weights "
                        "(models/lora.py); 0 = full fine-tune/pretrain")
    p.add_argument("--lora-alpha", type=float, default=None,
                   help="LoRA scale numerator (default: rank, i.e. scale 1)")
    p.add_argument("--hf-model", default=os.environ.get("KUBEDL_HF_MODEL", ""),
                   help="start from Hugging Face Llama/Mistral weights "
                        "(models/import_hf.py) — the base for --lora-rank "
                        "or a full fine-tune")
    p.add_argument("--remat", choices=["full", "dots", "none"],
                   default=os.environ.get("KUBEDL_REMAT", ""),
                   help="override the model's remat: full recompute, "
                        "matmul-saving 'dots' policy, or none. full and "
                        "dots both keep the flash kernel's output and "
                        "log-sum-exp, 2*tokens*d_model + 4*tokens*n_heads "
                        "bytes a layer, and do not run it twice")
    p.add_argument("--ce-chunks", type=int,
                   default=int(os.environ.get("KUBEDL_CE_CHUNKS", 0)),
                   help=">1: chunked cross-entropy (no [b,t,V] logits)")
    p.add_argument("--profile-dir", default=os.environ.get("KUBEDL_PROFILE_DIR", ""))
    p.add_argument("--profile-steps", type=int,
                   default=int(os.environ.get("KUBEDL_PROFILE_STEPS", 5)),
                   help="trace this many steps after warmup into --profile-dir")
    args = p.parse_args(argv)
    # argparse validates `choices` only for command-line values; an env
    # default (KUBEDL_REMAT=off) would otherwise slip through and silently
    # mean "full remat" instead of erroring.
    if args.remat not in ("", "full", "dots", "none"):
        p.error(f"invalid KUBEDL_REMAT/--remat {args.remat!r} "
                f"(choose from full, dots, none)")
    if args.lr_schedule not in ("constant", "cosine"):
        p.error(f"invalid KUBEDL_LR_SCHEDULE/--lr-schedule "
                f"{args.lr_schedule!r} (choose from constant, cosine)")
    return args


def main(argv=None) -> int:
    t_main0 = time.perf_counter()
    args = parse_args(argv)

    # flight recorder (docs/observability.md): spans to the pod's JSONL in
    # the injected KUBEDL_TRACE_DIR + a bounded per-step telemetry stream
    # with a control-dir heartbeat the operator aggregates for straggler
    # detection. Without the env both stay inert (ring-only / None) and
    # the step loop keeps its plain async-dispatch behavior. Spans opened
    # with `with` also show in a --profile-dir window, on its clock.
    from kubedl_tpu.obs import StepStream, compiles, tracer_from_env

    tracer = tracer_from_env()
    step_stream = StepStream.from_env()

    # trainer.init's children (init.imports, init.backend, init.mesh,
    # init.state) are open where the work is; what lies between them is
    # argument checks and the live-reshard staging's files
    with tracer.span("init.imports"):
        import dataclasses

        import jax
        import jax.numpy as jnp
        import numpy as np
        import optax

        from kubedl_tpu.models import llama
        from kubedl_tpu.parallel.mesh import (
            ShardingRules, build_mesh, build_mesh_from_env)
        from kubedl_tpu.parallel.train_step import make_train_step
        from kubedl_tpu.train import coordinator, reshard_runtime
        from kubedl_tpu.utils.exit_codes import (
            EXIT_TPU_PREEMPTED, EXIT_XLA_COMPILE_ERROR)

    # from here on every compile is seen as JAX reports it: the state's,
    # a restore's, the step's, a recompile in the middle of the run
    compile_log = compiles.install(tracer)
    with tracer.span("init.backend"):
        # the coordinator's rendezvous and the first jax.devices()
        info = coordinator.initialize()

    hf_base = None
    if args.hf_model:
        from kubedl_tpu.models.import_hf import load_hf

        hf_base, config = load_hf(args.hf_model)
        print(f"base weights: {args.hf_model} "
              f"({config.n_layers}L/{config.d_model}d)", flush=True)
    else:
        config = llama.LlamaConfig.config_for(args.model)

    if args.remat:
        config = dataclasses.replace(
            config,
            remat=args.remat != "none",
            remat_policy="dots" if args.remat == "dots" else None,
        )
    if args.ce_chunks > 1:
        config = dataclasses.replace(config, ce_chunks=args.ce_chunks)

    # Pipeline parallelism (operator-injected KUBEDL_PP_*, docs/pipeline.md).
    # MPMD mode means THIS program is wrong — each stage runs its own
    # program (train/pipeline_trainer.py), not the SPMD trainer; fail
    # permanent rather than silently train un-pipelined.
    if os.environ.get("KUBEDL_PP_MPMD") == "1":
        print("spec.pipeline.mpmd pods must run the stage program: "
              "python -m kubedl_tpu.train.pipeline_trainer (this SPMD "
              "trainer would train the full model un-pipelined)",
              file=sys.stderr)
        return 2  # permanent config error (utils/exit_codes.py)
    pp_stages = int(os.environ.get("KUBEDL_PP_STAGES", "1"))
    pipelined = pp_stages > 1
    pp_micro = int(os.environ.get("KUBEDL_PP_MICROBATCHES", str(pp_stages)))
    pp_schedule = os.environ.get("KUBEDL_PP_SCHEDULE", "1f1b")
    pp_interleave = int(os.environ.get("KUBEDL_PP_INTERLEAVE", "1"))
    if pipelined:
        from kubedl_tpu.api.validation import validate_pipeline_shapes

        errs = validate_pipeline_shapes(
            pp_stages, pp_micro, pp_interleave, n_layers=config.n_layers)
        if args.batch % pp_micro:
            errs.append(f"--batch {args.batch} not divisible by "
                        f"{pp_micro} microbatches")
        if args.lora_rank > 0:
            errs.append("--lora-rank is unsupported on the pipelined "
                        "path (adapters target unstacked projections)")
        if info.live_reshard:
            errs.append("spec.elastic.liveReshard is unsupported with "
                        "spec.pipeline (the reshard planner does not "
                        "cover stage-stacked layouts)")
        if errs:
            print("pipeline config invalid: " + "; ".join(errs),
                  file=sys.stderr)
            return 2  # permanent config error

    # Live-reshard plumbing (train/reshard_runtime.py): control channel +
    # staging dir, active only when the operator opted the job in
    # (spec.elastic.liveReshard -> KUBEDL_LIVE_RESHARD=1).
    reshard_on = info.live_reshard
    reshard_dir = info.reshard_dir
    # transport-selected control endpoint: socket plane in kube mode,
    # KUBEDL_CONTROL_DIR polling on the local executor (same surface)
    ctl = reshard_runtime.control_from_env() if reshard_on else None

    # Staged-restart lane: a valid staging (written by the PREVIOUS
    # incarnation's quiesce) beats both the env mesh and the checkpoint —
    # it is the resharded state at the quiesce step. Anything invalid is
    # discarded (fallback closed to Orbax below).
    staged = None
    if reshard_on and reshard_dir and args.lora_rank == 0:
        staged = reshard_runtime.restore_staged(
            reshard_dir, info.process_id, info.num_processes)
        if staged is None:
            # discard only a PUBLISHED-but-invalid staging; a missing
            # manifest may just mean peers are still mid-stage and worker
            # 0 has not reached the commit point — their src files must
            # not be deleted from under them
            if reshard_runtime.staging_exists(reshard_dir):
                reshard_runtime.clear_staging(reshard_dir)
        if staged is not None and os.environ.get("TPU_SLICE_TYPE"):
            # the staging must match the GRANTED slice: a stale staging
            # from an earlier resize must never re-inflate the mesh past
            # what the scheduler granted now
            import math as _math

            from kubedl_tpu.executor.tpu_topology import parse_slice_type

            try:
                granted = parse_slice_type(
                    os.environ["TPU_SLICE_TYPE"]).chips
            except ValueError:
                granted = None
            if granted is not None and _math.prod(
                staged[1].values()) != granted:
                print(f"staging topology {staged[1]} != granted "
                      f"{granted}-chip slice; falling back to checkpoint",
                      file=sys.stderr)
                reshard_runtime.clear_staging(reshard_dir)
                staged = None
        if staged is not None and args.checkpoint_path:
            # a checkpoint NEWER than the staging wins (the staging is a
            # quiesce snapshot; replaying it over later saves would lose
            # steps) — staging only ever moves the state forward
            try:
                latest_ck = max(
                    (int(d) for d in os.listdir(args.checkpoint_path)
                     if d.isdigit()), default=None)
            except OSError:
                latest_ck = None
            if latest_ck is not None and latest_ck > staged[0]:
                reshard_runtime.clear_staging(reshard_dir)
                staged = None

    with tracer.span("init.mesh"):
        # hybrid ICIxDCN when the operator injected KUBEDL_DCN_MESH (multislice)
        if staged is not None:
            import math as _math

            n = _math.prod(staged[1].values())
            if n <= len(jax.devices()):
                mesh = build_mesh(staged[1], devices=jax.devices()[:n])
            else:
                reshard_runtime.clear_staging(reshard_dir)
                staged = None
                mesh = build_mesh_from_env()
        else:
            devices = None
            if reshard_on and os.environ.get("TPU_SLICE_TYPE"):
                # size the mesh to the GRANTED slice, not to every visible
                # device: after an elastic shrink the pod may see more
                # devices than its slice has chips (local-executor sim), and
                # a later grow must have headroom to reshard into
                from kubedl_tpu.executor.tpu_topology import parse_slice_type

                try:
                    chips = parse_slice_type(
                        os.environ["TPU_SLICE_TYPE"]).chips
                    if 0 < chips <= len(jax.devices()):
                        devices = jax.devices()[:chips]
                except ValueError:
                    pass
            mesh = build_mesh_from_env(devices=devices)
    rules = ShardingRules()
    model_name = args.hf_model or args.model
    print(f"mesh: {dict(mesh.shape)} devices={len(jax.devices())} "
          f"model={model_name} params≈{config.n_layers}L/{config.d_model}d", flush=True)

    # preemption flag flipped by SIGTERM
    preempted = {"flag": False}

    def on_sigterm(signum, frame):
        preempted["flag"] = True

    signal.signal(signal.SIGTERM, on_sigterm)

    def loss_on(a_mesh):
        if pipelined:
            def loss(params, batch):
                return llama.loss_fn_pp(
                    params, batch, config, a_mesh, rules=rules,
                    n_microbatches=pp_micro, schedule=pp_schedule,
                    interleave=pp_interleave)
            return loss

        def loss(params, batch):
            return llama.loss_fn(params, batch, config, mesh=a_mesh, rules=rules)
        return loss

    loss = loss_on(mesh)

    if args.lr_schedule == "cosine":
        # warmup -> cosine decay to 10% of peak over the run
        lr = optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=args.lr,
            warmup_steps=max(args.warmup_steps, 1),
            decay_steps=max(args.steps, args.warmup_steps + 1),
            end_value=args.lr * 0.1,
        )
    elif args.warmup_steps > 0:
        lr = optax.linear_schedule(0.0, args.lr, args.warmup_steps)
    else:
        lr = args.lr
    tx = optax.adamw(lr, weight_decay=0.01)
    if args.grad_clip > 0:
        tx = optax.chain(optax.clip_by_global_norm(args.grad_clip), tx)
    with tracer.span("init.state"):
        params = (hf_base if hf_base is not None
                  else llama.init(config, jax.random.PRNGKey(0)))
        if pipelined:
            # stacked-layer layout for the stage-axis schedule; the mesh must
            # carry the stage axis the operator validated at submit
            if mesh.shape.get("stage", 1) != pp_stages:
                print(f"KUBEDL_PP_STAGES={pp_stages} but the mesh stage axis "
                      f"is {mesh.shape.get('stage', 1)} (spec.mesh.stage must "
                      f"match spec.pipeline.stages)", file=sys.stderr)
                return 2
            from kubedl_tpu.parallel import pipeline as _pipeline

            params = llama.stack_params(params)
            print(f"pipeline: {pp_schedule} stages={pp_stages} "
                  f"microbatches={pp_micro} interleave={pp_interleave} "
                  f"(bubble {_pipeline.bubble_fraction(pp_micro, pp_stages, pp_interleave):.3f})",
                  flush=True)

        try:
            if args.lora_rank > 0:
                # adapter-only training: gradients + optimizer state cover the
                # low-rank deltas; the frozen base rides sharded through the
                # step (models/lora.py)
                from kubedl_tpu.models import lora as lora_mod

                adapters0, init_state, train_step = lora_mod.make_lora_step(
                    params, config, tx, mesh, rules=rules, rank=args.lora_rank,
                    alpha=args.lora_alpha, accum_steps=args.accum_steps,
                )
                state = init_state(adapters0)
                n_ad = lora_mod.adapter_count(adapters0)
                print(f"lora: rank {args.lora_rank}, {n_ad} adapter params "
                      f"({100.0 * n_ad / llama.param_count(params):.2f}% of base)",
                      flush=True)
                if args.eval_every:
                    print("note: --eval-every is skipped under --lora-rank "
                          "(restore with generate/serve --lora-checkpoint-path "
                          "to evaluate the merged model)", flush=True)
                    args.eval_every = 0
            else:
                def build_step(a_mesh):
                    """Mesh-dependent compute, rebuilt after a live reshard."""
                    spec_tree = (llama.param_specs_pp(config, rules) if pipelined
                                 else llama.param_specs(config, rules))
                    if pipelined:
                        step_loss = loss_on(a_mesh)
                    else:
                        def step_loss(params, batch):
                            # (loss, the counters of an expert model, a
                            # looped stack, state-space layers, several
                            # streams or a multi-token module, which ride the
                            # train.step record: {} for a dense model run once)
                            return llama.loss_and_stats(
                                params, batch, config, mesh=a_mesh, rules=rules)
                    return make_train_step(
                        step_loss, tx, a_mesh, spec_tree,
                        rules.spec("batch", None), rules,
                        accum_steps=args.accum_steps, has_aux=not pipelined,
                    )

                init_state, train_step = build_step(mesh)
                if staged is not None:
                    # staged-restart lane: the previous incarnation quiesced
                    # and streamed its shard intersections here — rebuild the
                    # resharded state instead of restoring a checkpoint. Any
                    # gap falls back closed to the Orbax path below.
                    try:
                        template = init_state(params)
                        state = reshard_runtime.state_from_staging(
                            staged[2], template)
                        del template
                        # NOT cleared here: peers may still be assembling from
                        # the same staging (clearing would fork the gang onto
                        # divergent restore points). Replay is safe: a stale
                        # staging is rejected by the granted-topology and
                        # newer-checkpoint guards above, and a valid replay IS
                        # the newest state.
                        print(f"restored live-reshard staging at step "
                              f"{staged[0]} (mesh {staged[1]})", flush=True)
                    except Exception as e:  # noqa: BLE001 — fallback closed
                        print(f"staging unusable ({e}); falling back to "
                              f"checkpoint restore", file=sys.stderr)
                        reshard_runtime.clear_staging(reshard_dir)
                        staged = None
                        state = init_state(params)
                else:
                    state = init_state(params)
            # the sharded copies live on the mesh now; a 7B HF import would
            # otherwise pin ~14 GB of dead host arrays for the whole run
            del params
            hf_base = None
        except Exception as e:
            if "RESOURCE_EXHAUSTED" in str(e) or "XlaRuntimeError" in type(e).__name__:
                print(f"compile/alloc failure: {e}", file=sys.stderr)
                return EXIT_XLA_COMPILE_ERROR
            raise

        # where the state landed: a sharded job's bytes are spread over its
        # devices, not sitting on the first (the CPU backend reports none)
        if jax.local_devices()[0].memory_stats() is not None:
            # the unsharded init copy is freed only once the init program,
            # dispatched asynchronously, has ended
            jax.block_until_ready(state)
            print("device memory after init: " + " ".join(
                f"{d.id}={d.memory_stats()['bytes_in_use'] / 2**20:.0f}MiB"
                for d in mesh.local_devices), flush=True)

        # checkpointing (Orbax)
        mngr = None
        start_step = staged[0] if staged is not None else 0
        if args.checkpoint_path:
            import orbax.checkpoint as ocp

            options = ocp.CheckpointManagerOptions(
                max_to_keep=args.checkpoint_keep, create=True
            )
            mngr = ocp.CheckpointManager(args.checkpoint_path, options=options)
            latest = mngr.latest_step()
            if staged is not None:
                pass  # live-reshard staging beats restore (start_step set above)
            elif latest is not None and os.environ.get("KUBEDL_CHECKPOINT_RESTORE", "1") == "1":
                # Restore straight into the SHARDED state: the live arrays act
                # as the abstract target, so each leaf comes back with its
                # param_specs sharding instead of landing replicated on one
                # device (mandatory for models that only fit sharded).
                with tracer.span("ckpt.restore") as restore_span:
                    abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, state)
                    state = mngr.restore(
                        latest, args=ocp.args.StandardRestore(abstract))
                    start_step = int(state.step)
                    restore_span.set(step=start_step)
                print(f"restored checkpoint at step {start_step}", flush=True)

    # interval saves are ASYNC: orbax's save() blocks only for the
    # device->host copy (so the next step may donate the state buffers
    # safely) and streams to disk in background — training overlaps the
    # write. Only final saves (preemption, end of run) wait for
    # durability. last-saved is tracked here, not via latest_step(),
    # which lags while a save is in flight.
    saved_step = {"v": mngr.latest_step() if mngr else None}
    # with the injected trace env the loop records every step, two steps
    # behind the dispatch (StepRecorder); without it there is none
    recorder = (StepRecorder(tracer, step_stream)
                if tracer.exporting or step_stream is not None else None)

    def save(step, final=False):
        if mngr is None:
            return
        did_save = saved_step["v"] != step  # else: interval hook saved it
        if not (did_save or final):
            return
        with tracer.span("ckpt.save", step=step, final=final) as save_span:
            if did_save:
                import orbax.checkpoint as ocp

                mngr.save(step, args=ocp.args.StandardSave(state))
                saved_step["v"] = step
            if final:
                mngr.wait_until_finished()
                print(f"saved final checkpoint at step {step}", flush=True)
        if recorder is not None:
            recorder.ckpt_stall += save_span.dur

    # -- live resize protocol (train/reshard_runtime.py ladder) ----------

    def _resize_fallback(msg, at_step, reason):
        """Fallback CLOSED: the old state is intact (live_resize raises
        pre-commit and device_put never donates), so bank it as a final
        checkpoint, tell the scheduler, and exit retryable — the restart
        comes back through checkpoint restore. A corrupted state is never
        saved and never trained on."""
        print(f"live reshard failed ({reason}); falling back to "
              f"checkpoint restore", file=sys.stderr)
        with tracer.span("reshard.fallback", step=at_step,
                         reason=str(reason)[:200]):
            try:
                save(at_step, final=True)
            except Exception:  # noqa: BLE001 — last interval save still holds
                pass
        if ctl is not None:
            ctl.reply(msg, outcome="fallback", step=at_step,
                      error=str(reason)[:300])
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(EXIT_TPU_PREEMPTED)

    def _resize_staged(msg, at_step, new_chips):
        """Multi-process lane: jax.distributed pins the world size, so the
        gang quiesces, streams shard intersections into the staging dir,
        and restarts onto the new topology (reassembly at startup). The
        manifest publishes only when every pod staged with a matching
        plan digest; any gap falls back closed."""
        try:
            with tracer.span("reshard.staged", step=at_step, chips=new_chips):
                if not reshard_dir:
                    raise reshard_runtime.ReshardError("no KUBEDL_RESHARD_DIR")
                leaves = reshard_runtime.leaves_from_state(state)
                new_axes = reshard_runtime.refit_axes(
                    dict(mesh.shape), new_chips)
                plan = reshard_runtime.plan_reshard(
                    leaves, dict(mesh.shape), new_axes,
                    info.num_processes, info.num_processes)
                blocks = reshard_runtime.addressable_blocks(state)
                reshard_runtime.stage_shards(
                    reshard_dir, plan, info.process_id,
                    reshard_runtime.provider_from_blocks(blocks), at_step)
                # the job's own quiesce budget (spec.elastic.quiesceTimeoutS,
                # injected by the controller) outranks the scheduler default
                quiesce = float(os.environ.get(
                    "KUBEDL_RESHARD_QUIESCE_S",
                    msg.get("quiesce_timeout_s", 30.0)))
                if info.process_id == 0 and not reshard_runtime.write_manifest(
                    reshard_dir, plan, at_step, info.num_processes,
                    timeout=quiesce,
                ):
                    raise reshard_runtime.ReshardError("manifest aborted")
        except Exception as e:  # noqa: BLE001 — fallback closed
            _resize_fallback(msg, at_step, f"staged lane: {e}")
        ctl.reply(msg, outcome="staged", step=at_step)
        print(f"staged reshard at step {at_step}: restarting onto the new "
              f"topology", flush=True)
        sys.stdout.flush()
        os._exit(EXIT_TPU_PREEMPTED)

    def handle_resize(msg, at_step):
        nonlocal mesh, loss, state, init_state, train_step
        nonlocal batch_sharding, eval_fn
        t0 = time.perf_counter()
        new_chips = int(msg.get("chips", 0))
        jax.block_until_ready(state.params)  # quiesce at the step boundary
        if new_chips <= 0:
            _resize_fallback(msg, at_step, f"bad chip count {new_chips}")
        if args.lora_rank > 0:
            _resize_fallback(msg, at_step, "lora runs restart via checkpoint")
        if info.num_processes > 1:
            _resize_staged(msg, at_step, new_chips)  # does not return
        with tracer.span("reshard.live", step=at_step,
                         chips=new_chips) as live_span:
            try:
                new_mesh, new_state, plan = reshard_runtime.live_resize(
                    state, mesh, new_chips)
            except reshard_runtime.ReshardError as e:
                _resize_fallback(msg, at_step, str(e))  # does not return
            mesh, state = new_mesh, new_state
            loss = loss_on(mesh)
            init_state, train_step = build_step(mesh)
            batch_sharding = rules.sharding(mesh, "batch", None)
            if eval_fn is not None:
                eval_fn = jax.jit(loss)
            jax.block_until_ready(jax.tree_util.tree_leaves(state.params))
            live_span.set(outcome="ok",
                          moved_mb=round(plan.moved_bytes / 2**20, 3))
        # reply NOW — downtime = quiesce -> full state resident on the new
        # mesh, a step dispatchable (the bench's definition). The first
        # post-reshard step's compile is ordinary training the scheduler
        # must not wait on: on a real model it takes minutes, and a reply
        # deferred past it would blow reshard_reply_timeout and turn every
        # successful reshard into a spurious pod teardown.
        downtime = time.perf_counter() - t0
        ctl.reply(msg, outcome="ok", step=at_step,
                  downtime_s=round(downtime, 4), chips=new_chips,
                  moved_mb=round(plan.moved_bytes / 2**20, 3))
        print(f"live reshard at step {at_step}: mesh -> "
              f"{ {k: v for k, v in dict(mesh.shape).items() if v > 1} } "
              f"({new_chips} devices, downtime {downtime:.3f}s); "
              f"live reshard: resumed at step {at_step + 1}", flush=True)

    # input pipeline: native mmap+prefetch loader over token shards, or
    # synthetic batches when no data path is given. All processes share one
    # seed/permutation and stride it by rank (batch id = step*world + rank),
    # so the global batch is disjoint across data-parallel processes and a
    # checkpoint resume at start_step continues the schedule, not replays it.
    loader = None
    if args.data_path:
        import glob as globlib

        from kubedl_tpu.native.loader import TokenLoader

        shard_paths = sorted(globlib.glob(args.data_path))
        if not shard_paths:
            print(f"no shards match {args.data_path!r}", file=sys.stderr)
            return 1
        loader = TokenLoader(
            shard_paths, batch=args.batch, seq_len=args.seq_len, seed=args.data_seed,
            # the trainer only random-accesses batch_at(); prefetch threads
            # would fill ring slots nobody consumes
            n_threads=0,
        )
        print(f"data: {len(shard_paths)} shards, {loader.n_windows} windows, "
              f"native={loader.is_native}", flush=True)

    rng = np.random.default_rng(info.process_id)
    batch_sharding = rules.sharding(mesh, "batch", None)
    global_batch = args.batch * info.num_processes

    def to_global(local):
        """Global [world*batch, seq] array from per-process local rows.

        Each process loads ONLY its own rows (rank-strided window ids) and
        contributes them via make_array_from_process_local_data — jnp.asarray
        would device-commit locally and cannot reshard onto the other
        processes' non-addressable devices on a multi-host mesh."""
        if info.num_processes == 1:
            return jnp.asarray(local)
        return jax.make_array_from_process_local_data(
            batch_sharding, np.asarray(local), (global_batch, args.seq_len)
        )

    def next_batch(step: int):
        if loader is not None:
            local = loader.batch_at(step * info.num_processes + info.process_id)
        else:
            local = rng.integers(
                0, config.vocab_size, (args.batch, args.seq_len), dtype=np.int32
            )
        return to_global(local)

    tokens_per_step = global_batch * (args.seq_len - 1)

    # eval: every pass scores the SAME fixed batch set (fresh rng / fixed
    # ids), so losses are comparable across the run. With
    # --eval-data-path the set comes from SEPARATE shards — a true
    # held-out set; otherwise it is a probe drawn from the training
    # distribution (batch_at wraps modulo the shard windows, so probe
    # batches overlap training data once a run covers an epoch).
    eval_fn = jax.jit(loss) if args.eval_every else None
    eval_loader = None
    if args.eval_every and args.eval_data_path:
        import glob as globlib

        from kubedl_tpu.native.loader import TokenLoader

        eval_shards = sorted(globlib.glob(args.eval_data_path))
        if not eval_shards:
            print(f"no shards match {args.eval_data_path!r}", file=sys.stderr)
            return 1
        eval_loader = TokenLoader(
            eval_shards, batch=args.batch, seq_len=args.seq_len,
            seed=args.data_seed, n_threads=0,
        )

    def eval_pass(step: int) -> None:
        erng = np.random.default_rng(10**9 + info.process_id)
        src = eval_loader if eval_loader is not None else loader
        losses = []
        for i in range(args.eval_batches):
            if src is not None:
                # held-out loader: its own shards, ids from 0. Probe mode
                # reads a fixed far region of the TRAINING loader — stable
                # across passes, but not disjoint from training in general
                base = 0 if eval_loader is not None else 2**20
                local = src.batch_at(
                    base + i * info.num_processes + info.process_id)
            else:
                local = erng.integers(
                    0, config.vocab_size, (args.batch, args.seq_len),
                    dtype=np.int32)
            losses.append(eval_fn(state.params, to_global(local)))
        ev = float(np.mean([float(jax.device_get(l)) for l in losses]))
        tag = "held-out" if eval_loader is not None else "probe"
        print(f"eval step {step}: loss={ev:.4f} "
              f"({args.eval_batches} {tag} batches)", flush=True)

    # profiler window: [start+1, start+1+profile_steps) — skips the
    # first step, whose dispatch holds the step's compile (a cache read on
    # a resumed pod: its train.compile record says which). A later
    # recompile inside the window shows there as jax.trace / jax.lower /
    # jax.compile. Shared with the MPMD stage trainer
    # (train/profile_window.py): stop() is idempotent and runs from the
    # preemption path AND the finally backstop, so SIGTERM (or a raise)
    # DURING the traced window still lands the trace on disk.
    from kubedl_tpu.train.profile_window import window_from_args

    prof = window_from_args(args, start_step)

    # The step loop has two bodies. Plain: next_batch, train_step, nothing
    # else, no object made per step. Instrumented, with the injected trace
    # env (recorder) or inside an open --profile-dir window: the same two
    # calls under train.data / train.dispatch spans and one
    # StepTraceAnnotation, which a profile shows beside the device's
    # operations; the recorder reads a step's loss two steps later
    # (StepRecorder), so recording does not drain the device. Whether a
    # step compiled is read off the compile log: this thread's count of
    # compiled functions across the dispatch, so a save's or a
    # transport's thread that compiles a copy turns no step into one.

    def settle(loss_arr) -> None:
        """Every step dispatched so far has ended, and the recorder has
        written them: what save, preemption, resize, eval, the log line
        and the end of the profile window do before they go on."""
        if recorder is not None:
            recorder.flush()
        with tracer.span("train.wait", export=False):
            jax.block_until_ready(loss_arr)

    tracer.record("trainer.init",
                  duration_s=time.perf_counter() - t_main0,
                  step=start_step, model=model_name,
                  devices=len(jax.devices()))

    t_start = time.perf_counter()
    last_log = t_start
    try:
        for step in range(start_step, args.steps):
            if prof is not None:
                prof.maybe_start(step)
            if recorder is None and not (prof is not None and prof.tracing):
                state, metrics = train_step(state, next_batch(step))
            else:
                with jax.profiler.StepTraceAnnotation("train", step_num=step + 1):
                    t_step0 = time.perf_counter()
                    with tracer.span("train.data", export=False) as data_span:
                        batch = next_batch(step)
                    compiles_before = compile_log.count(thread=True)
                    with tracer.span("train.dispatch",
                                     export=False) as dispatch_span:
                        state, metrics = train_step(state, batch)
                    if recorder is not None:
                        recorder.dispatched(
                            step + 1, metrics["loss"], t_step0, data_span.dur,
                            dispatch_span.dur, compile_log.since(compiles_before),
                            {k: v for k, v in metrics.items()
                             if k.startswith(("moe_", "gmm_", "loop_", "ssm_",
                                              "short_conv_", "hc_", "mtp_",
                                              "attn_")) or k == "ce"})
            if prof is not None and prof.should_stop(step):
                settle(metrics["loss"])
                prof.stop()
            if preempted["flag"]:
                settle(metrics["loss"])
                if prof is not None:
                    prof.stop()
                save(step + 1, final=True)
                tracer.record("trainer.preempted", step=step + 1)
                print("preempted: checkpoint saved, exiting retryable", flush=True)
                # A clean interpreter exit would block in jax.distributed's
                # shutdown barrier (atexit) while peers are still mid-collective
                # — the exact deadlock slice restart exists to break. The
                # checkpoint is durable; exit immediately.
                sys.stdout.flush()
                sys.stderr.flush()
                os._exit(EXIT_TPU_PREEMPTED)
            if ctl is not None:
                cmsg = ctl.poll()
                if cmsg is not None:
                    if cmsg.get("type") == "RESIZE":
                        settle(metrics["loss"])
                        handle_resize(cmsg, step + 1)
                    else:
                        ctl.reply(cmsg, outcome="failed",
                                  error=f"unknown control message "
                                        f"{cmsg.get('type')!r}")
            if args.checkpoint_interval and (step + 1) % args.checkpoint_interval == 0:
                settle(metrics["loss"])
                save(step + 1)
            if args.eval_every and (step + 1) % args.eval_every == 0:
                settle(metrics["loss"])
                eval_pass(step + 1)
            if (step + 1) % args.log_every == 0:
                settle(metrics["loss"])
                loss_v = float(metrics["loss"])
                now = time.perf_counter()
                sps = args.log_every / (now - last_log)
                last_log = now
                print(f"step {step + 1}: loss={loss_v:.4f} "
                      f"step/s={sps:.2f} tok/s={sps * tokens_per_step:.0f}", flush=True)
    finally:
        # SIGTERM or an exception DURING the traced window must not leave
        # the profiler open (stop is idempotent: re-stop is a no-op)
        if prof is not None:
            prof.stop()

    if recorder is not None:
        recorder.flush()
    jax.block_until_ready(state.step)
    total = time.perf_counter() - t_start
    steps_done = args.steps - start_step
    print(f"done: {steps_done} steps in {total:.1f}s "
          f"({steps_done / total:.2f} step/s, "
          f"{steps_done * tokens_per_step / total:.0f} tok/s)", flush=True)
    save(args.steps, final=True)
    tracer.record("trainer.done", step=args.steps, steps_done=steps_done,
                  wall_s=round(total, 3))
    if step_stream is not None:
        step_stream.close()
    compile_log.release(tracer)
    tracer.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
