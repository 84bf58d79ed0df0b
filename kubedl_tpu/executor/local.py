"""Local pod executor — the framework's kubelet.

The reference delegates pod execution to Kubernetes kubelets; this framework
is standalone, so the executor watches Pod objects and runs their containers
as real host processes: Pending -> Running (Ready condition stamped for
launch-delay metrics, ref pkg/metrics/job_metrics.go:139-194) ->
Succeeded/Failed with per-container exit codes, honoring pod-level restart
policies (Always/OnFailure restart in place with restart_count accrual, the
behavior pastBackoffLimit sums over — ref job.go:282-319).

Container images are not pulled: `command`+`args` run directly on the host,
which is exactly what CI needs (SURVEY.md §4: distribution is simulated
process-level). emptyDir volumes map to per-pod temp dirs.
"""
from __future__ import annotations

import logging
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from kubedl_tpu.api.meta import now
from kubedl_tpu.api.pod import (
    ContainerStateTerminated,
    ContainerStatus,
    Pod,
    PodCondition,
    PodPhase,
    PodRestartPolicy,
)
from kubedl_tpu.core.store import ADDED, DELETED, Conflict, NotFound, ObjectStore, write_status
from kubedl_tpu.analysis.witness import new_lock

log = logging.getLogger("kubedl_tpu.executor")


@dataclass
class _RunningPod:
    pod: Pod
    procs: Dict[str, subprocess.Popen] = field(default_factory=dict)
    restart_counts: Dict[str, int] = field(default_factory=dict)
    workdir: str = ""
    stop: bool = False
    thread: Optional[threading.Thread] = None


class LocalPodExecutor:
    """Runs pods as host processes, reflecting status back into the store."""

    def __init__(
        self,
        store: ObjectStore,
        scheduler=None,
        restart_backoff: float = 0.05,
        launch_hook=None,
        log_dir: Optional[str] = None,
        trace_root: Optional[str] = None,
    ) -> None:
        self.store = store
        # Optional TPU-slice scheduler (gang admission): pod stays Pending
        # until scheduler.assign(pod) returns a placement.
        self.scheduler = scheduler
        self.restart_backoff = restart_backoff
        self.launch_hook = launch_hook  # test seam: fn(pod) -> env overrides
        # container stdout/stderr land here (kubectl-logs equivalent),
        # appended across in-place restarts, removed when the pod is deleted
        self.log_dir = log_dir or tempfile.mkdtemp(prefix="kubedl-logs-")
        # flight recorder (obs/): per-JOB trace dirs under this root,
        # injected as KUBEDL_TRACE_DIR/_ID the same way KUBEDL_CONTROL_DIR
        # travels. Job-scoped, NOT removed with the pod — the recorder's
        # whole point is that the timeline survives the pods (the operator
        # exports its control-plane spans into the same dirs).
        self.trace_root = trace_root or tempfile.mkdtemp(prefix="kubedl-trace-")
        # per-pod control channel (the local analog of a sidecar/ConfigMap
        # watch): the scheduler posts JSON messages (live-reshard RESIZE,
        # sched/capacity.py) into the pod's dir, injected as
        # KUBEDL_CONTROL_DIR; the workload replies next to the message.
        # Survives in-place restarts, removed with the pod.
        self.control_root = tempfile.mkdtemp(prefix="kubedl-ctl-")
        self._control_seq = 0
        # transport plane selection + auth (docs/transport.md), injected
        # the same way KUBEDL_CONTROL_DIR travels: the local executor
        # defaults to the dir transport (shared filesystem IS the local
        # analog of DCN); kube manifests pin KUBEDL_TRANSPORT=socket.
        # The auth token is per JOB — every pod of a gang shares it, two
        # jobs never do — minted lazily on first launch.
        self.transport = os.environ.get("KUBEDL_TRANSPORT", "dir")
        self._job_tokens: Dict[str, str] = {}
        self._running: Dict[str, _RunningPod] = {}
        self._lock = new_lock("executor.local.LocalPodExecutor._lock")
        self._stop = threading.Event()
        self._watch = None
        self._thread: Optional[threading.Thread] = None

    # -- logs ------------------------------------------------------------

    def _pod_log_dir(self, namespace: str, name: str) -> str:
        return os.path.join(self.log_dir, f"{namespace}_{name}")

    def read_logs(
        self, namespace: str, name: str, container: Optional[str] = None,
        tail: Optional[int] = None,
    ) -> str:
        """Concatenated logs of one pod (optionally one container)."""
        d = self._pod_log_dir(namespace, name)
        try:
            files = sorted(os.listdir(d))
        except OSError:
            return ""
        if container is not None:
            files = [f for f in files if f == f"{container}.log"]
        chunks = []
        for f in files:
            try:
                with open(os.path.join(d, f), "r", errors="replace") as fh:
                    chunks.append(fh.read())
            except OSError:
                continue
        text = "".join(chunks)
        if tail is not None:
            # tail=0 means "no lines" (kubectl semantics); [-0:] would be all
            text = "\n".join(text.splitlines()[-tail:]) if tail > 0 else ""
        return text

    # -- control channel -------------------------------------------------

    def control_dir(self, namespace: str, name: str) -> str:
        d = os.path.join(self.control_root, f"{namespace}_{name}")
        os.makedirs(d, exist_ok=True)
        return d

    def post_control(self, namespace: str, name: str, message: Dict) -> Optional[str]:
        """Post a control message to a RUNNING pod; returns the absolute
        reply path the workload will write (reshard_runtime.ReshardControl
        conventions), or None when the pod is not running here. Atomic
        tmp+rename so the poller never parses a half-written message."""
        with self._lock:
            if f"{namespace}/{name}" not in self._running:
                return None
            self._control_seq += 1
            seq = self._control_seq
        d = self.control_dir(namespace, name)
        msg = dict(message)
        msg.setdefault("reply", f"reply-{seq:06d}.json")
        tmp = os.path.join(d, f".msg-{seq:06d}.json.tmp")
        try:
            with open(tmp, "w") as f:
                import json

                json.dump(msg, f)
            os.replace(tmp, os.path.join(d, f"msg-{seq:06d}.json"))
        except OSError:
            return None
        return os.path.join(d, msg["reply"])

    def job_transport_token(self, namespace: str, job: str) -> str:
        """The job's shared transport auth token (KUBEDL_TRANSPORT_TOKEN)
        — one random secret per job, every pod of the gang gets the same
        one, so pods of DIFFERENT jobs cannot speak on each other's
        planes even on a shared host."""
        import secrets

        key = f"{namespace}/{job}"
        with self._lock:
            tok = self._job_tokens.get(key)
            if tok is None:
                tok = self._job_tokens[key] = secrets.token_hex(16)
            return tok

    def read_heartbeats(self) -> List[Dict]:
        """Latest step-telemetry heartbeat of every pod that wrote one
        (obs/steps.py StepStream writes ``heartbeat.json`` into the pod's
        control dir, atomic-replaced each step). Pull model: the operator's
        StepAggregator calls this on each metrics scrape."""
        import json

        out: List[Dict] = []
        try:
            entries = sorted(os.listdir(self.control_root))
        except OSError:
            return out
        for entry in entries:
            path = os.path.join(self.control_root, entry, "heartbeat.json")
            try:
                with open(path) as f:
                    rec = json.load(f)
            except (OSError, ValueError):
                continue
            if not isinstance(rec, dict):
                continue
            namespace, _, pod = entry.partition("_")
            rec.setdefault("namespace", namespace)
            rec.setdefault("pod", pod)
            out.append(rec)
        return out

    # -- lifecycle -------------------------------------------------------

    def start(self) -> None:
        self._watch = self.store.watch(["Pod"])
        self._thread = threading.Thread(target=self._loop, name="executor", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._watch:
            self._watch.stop()
        with self._lock:
            entries = list(self._running.values())
        for entry in entries:
            self._kill(entry)
        if self._thread:
            self._thread.join(timeout=2.0)

    def _loop(self) -> None:
        while not self._stop.is_set():
            ev = self._watch.next(timeout=0.1)
            if ev is None:
                continue
            key = f"{ev.obj.metadata.namespace}/{ev.obj.metadata.name}"
            if ev.type == ADDED:
                self._maybe_launch(key, ev.obj)
            elif ev.type == DELETED:
                with self._lock:
                    entry = self._running.pop(key, None)
                if entry:
                    self._kill(entry)
                if self.scheduler is not None:
                    self.scheduler.release(ev.obj)
                shutil.rmtree(
                    self._pod_log_dir(
                        ev.obj.metadata.namespace, ev.obj.metadata.name
                    ),
                    ignore_errors=True,
                )
                shutil.rmtree(
                    os.path.join(
                        self.control_root,
                        f"{ev.obj.metadata.namespace}_{ev.obj.metadata.name}",
                    ),
                    ignore_errors=True,
                )

    def _maybe_launch(self, key: str, pod: Pod) -> None:
        with self._lock:
            if key in self._running:
                return
            entry = _RunningPod(pod=pod)
            self._running[key] = entry
        entry.thread = threading.Thread(
            target=self._run_pod, args=(key, entry), name=f"pod-{key}", daemon=True
        )
        entry.thread.start()

    # -- pod run loop ----------------------------------------------------

    def _run_pod(self, key: str, entry: _RunningPod) -> None:
        pod = entry.pod
        try:
            # 1. schedule (TPU slice admission when configured)
            placement = None
            if self.scheduler is not None:
                while not self._stop.is_set() and not entry.stop:
                    placement = self.scheduler.assign(pod)
                    if placement is not None:
                        break
                    time.sleep(0.05)
                if placement is None:
                    return
            if entry.stop:
                return

            entry.workdir = tempfile.mkdtemp(prefix=f"kubedl-pod-{pod.metadata.name}-")
            volumes = self._prepare_volumes(pod, entry.workdir)

            # 2. init containers run sequentially to completion
            for c in pod.spec.init_containers:
                rc = self._run_container(entry, c, volumes, placement, wait=True)
                if rc is not None and rc < 0:
                    rc = 128 - rc  # signal death -> kubelet-style 128+signum
                if rc != 0:
                    self._set_status(
                        key, PodPhase.FAILED,
                        [ContainerStatus(name=c.name, terminated=ContainerStateTerminated(exit_code=rc, reason="InitError"))],
                        message=f"init container {c.name} failed with exit code {rc}",
                    )
                    return

            # 3. main containers; restart in place per pod restart policy
            while not entry.stop and not self._stop.is_set():
                started = now()
                for c in pod.spec.containers:
                    self._run_container(entry, c, volumes, placement, wait=False)
                self._set_status(
                    key, PodPhase.RUNNING,
                    [
                        ContainerStatus(name=c.name, ready=True,
                                        restart_count=entry.restart_counts.get(c.name, 0))
                        for c in pod.spec.containers
                    ],
                    ready=True, start_time=started, placement=placement,
                )
                exit_codes = {}
                for name, proc in list(entry.procs.items()):
                    rc = proc.wait()
                    # signal deaths surface as negative returncodes from
                    # Popen; kubelets report 128+signum (SIGTERM -> 143,
                    # which the ExitCode policy treats as retryable)
                    exit_codes[name] = 128 - rc if rc < 0 else rc
                if entry.stop or self._stop.is_set():
                    return
                failed = {n: rc for n, rc in exit_codes.items() if rc != 0}
                policy = pod.spec.restart_policy
                should_restart = policy == PodRestartPolicy.ALWAYS or (
                    policy == PodRestartPolicy.ON_FAILURE and failed
                )
                statuses = [
                    ContainerStatus(
                        name=n,
                        restart_count=entry.restart_counts.get(n, 0),
                        terminated=ContainerStateTerminated(
                            exit_code=rc, finished_at=now(),
                            reason="Error" if rc else "Completed",
                        ),
                    )
                    for n, rc in exit_codes.items()
                ]
                if should_restart:
                    for n in exit_codes:
                        entry.restart_counts[n] = entry.restart_counts.get(n, 0) + 1
                    # keep phase Running with accrued restart counts, like a
                    # kubelet in CrashLoopBackOff-free fast path
                    self._set_status(
                        key, PodPhase.RUNNING,
                        [
                            ContainerStatus(name=n, ready=False,
                                            restart_count=entry.restart_counts.get(n, 0),
                                            terminated=s.terminated)
                            for n, s in zip(exit_codes, statuses)
                        ],
                        placement=placement,
                    )
                    time.sleep(self.restart_backoff)
                    continue
                phase = PodPhase.FAILED if failed else PodPhase.SUCCEEDED
                self._set_status(key, phase, statuses, placement=placement)
                return
        except Exception:
            from kubedl_tpu.utils.joblog import pod_logger

            pod_logger(log, entry.pod).exception("executor failed running pod")
            self._set_status(
                key, PodPhase.FAILED,
                [ContainerStatus(name="executor", terminated=ContainerStateTerminated(exit_code=127, reason="ExecutorError"))],
            )
        finally:
            if self.scheduler is not None and entry.pod.spec.tpu_chips() > 0:
                self.scheduler.release(entry.pod)
            if entry.workdir:
                shutil.rmtree(entry.workdir, ignore_errors=True)
            with self._lock:
                self._running.pop(key, None)

    def _prepare_volumes(self, pod: Pod, workdir: str) -> Dict[str, str]:
        paths = {}
        for vol in pod.spec.volumes:
            if vol.kind == "hostPath":
                paths[vol.name] = vol.host_path
            else:
                p = os.path.join(workdir, "vol", vol.name)
                os.makedirs(p, exist_ok=True)
                paths[vol.name] = p
        return paths

    def _localize_service_dns(self, env: Dict[str, str]) -> None:
        """The local-executor equivalent of cluster DNS: every pod runs on
        this host, so a simple `host` / `host:port` env value whose host is
        a headless-service DNS name (`name.ns.svc[...]`, ref
        tensorflow.go:122-136) — e.g. torch's MASTER_ADDR — rewrites to
        127.0.0.1. Consumers like torch c10d cannot resolve the cluster
        name themselves (the JAX coordinator does its own fallback,
        train/coordinator.py). JSON blobs (TF_CONFIG) are left alone."""
        import re

        services = {s.metadata.name for s in self.store.list("Service")}

        def local(host: str) -> str:
            # only a BARE hostname is eligible — host lists, URLs, or
            # suffixed addresses ("a.svc,b.svc", "zk.svc:2181/chroot")
            # pass through untouched rather than collapsing to an IP
            if not re.fullmatch(r"[A-Za-z0-9.-]+", host):
                return host
            first, _, rest = host.partition(".")
            if first in services and ".svc" in rest:
                return "127.0.0.1"
            return host

        for key, val in list(env.items()):
            if not isinstance(val, str) or "." not in val:
                continue
            host, sep, port = val.partition(":")
            if sep and port.isdigit():
                env[key] = f"{local(host)}{sep}{port}"
            else:
                env[key] = local(val)

    def _run_container(self, entry: _RunningPod, container, volumes, placement, wait: bool):
        pod = entry.pod
        env = dict(os.environ)
        env.update(container.env)
        env["POD_NAME"] = pod.metadata.name
        env["POD_NAMESPACE"] = pod.metadata.namespace
        env["KUBEDL_CONTROL_DIR"] = self.control_dir(
            pod.metadata.namespace, pod.metadata.name)
        # flight-recorder correlation (obs/trace.py): one gang-level trace
        # id + a shared per-job trace dir for every pod of the job, so the
        # control-plane and compute-plane spans merge into one timeline.
        # setdefault: a manifest that pins its own KUBEDL_TRACE_* wins.
        from kubedl_tpu.obs.trace import job_trace_dir, trace_id_for

        job_name = pod.metadata.labels.get("job-name") or pod.metadata.name
        # transport selection + per-job auth token (docs/transport.md);
        # setdefault — a manifest that pins its own transport env wins
        env.setdefault("KUBEDL_TRANSPORT", self.transport)
        env.setdefault("KUBEDL_TRANSPORT_TOKEN", self.job_transport_token(
            pod.metadata.namespace, job_name))
        trace_dir = job_trace_dir(
            self.trace_root, pod.metadata.namespace, job_name)
        try:
            os.makedirs(trace_dir, exist_ok=True)
            env.setdefault("KUBEDL_TRACE_DIR", trace_dir)
            env.setdefault(
                "KUBEDL_TRACE_ID",
                trace_id_for(pod.metadata.namespace, job_name))
        except OSError:
            pass  # recorder unavailable; the pod still runs
        for k, v in pod.metadata.labels.items():
            env[f"KUBEDL_LABEL_{k.upper().replace('-', '_')}"] = v
        if placement is not None:
            env.update(placement.env())
        on_slice = placement is not None and bool(placement.slice_name)
        if ((on_slice or container.resources.tpu_chips() > 0)
                and env.get("JAX_PLATFORMS") != "cpu"):
            # JAX falls back to the CPU with a warning when it finds no
            # TPU; a pod that was granted chips must fail instead of
            # training on the host and reporting success. JAX_PLATFORMS=cpu
            # said outright (tests, rehearsals) asks for the host and stays.
            env["JAX_PLATFORMS"] = "tpu,cpu"
        if self.launch_hook is not None:
            env.update(self.launch_hook(pod) or {})
        # volume mounts exported as env so host processes can find them
        for vm in container.volume_mounts:
            if vm.name in volumes:
                env[f"KUBEDL_VOLUME_{vm.name.upper().replace('-', '_')}"] = volumes[vm.name]
        self._localize_service_dns(env)
        # Local mode has no container images: make the framework's own
        # runtime modules (kubedl_tpu.train.*) importable from any cwd,
        # merging with (not clobbering) any user-set PYTHONPATH.
        pkg_parent = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        existing = env.get("PYTHONPATH", "")
        if pkg_parent not in existing.split(os.pathsep):
            env["PYTHONPATH"] = f"{pkg_parent}{os.pathsep}{existing}" if existing else pkg_parent
        argv = list(container.command) + list(container.args)
        if not argv:
            if "GIT_SYNC_REPO" in container.env:
                # an injected git-sync init container relies on its image
                # entrypoint on a cluster; locally there is no image, so run
                # the native sync runner (codesync/git_sync.py) instead
                argv = [sys.executable, "-m", "kubedl_tpu.codesync.git_sync"]
            else:
                argv = ["true"]
        cwd = container.working_dir or entry.workdir
        log_dir = self._pod_log_dir(pod.metadata.namespace, pod.metadata.name)
        os.makedirs(log_dir, exist_ok=True)
        log_fh = open(os.path.join(log_dir, f"{container.name}.log"), "ab")
        try:
            proc = subprocess.Popen(
                argv, env=env, cwd=cwd,
                stdout=log_fh, stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        finally:
            log_fh.close()  # child holds its own fd
        if wait:
            return proc.wait()
        entry.procs[container.name] = proc
        return None

    def _kill(self, entry: _RunningPod) -> None:
        entry.stop = True
        for proc in entry.procs.values():
            if proc.poll() is None:
                try:
                    os.killpg(os.getpgid(proc.pid), signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    pass
        deadline = time.monotonic() + 2.0
        for proc in entry.procs.values():
            remaining = deadline - time.monotonic()
            try:
                proc.wait(timeout=max(remaining, 0.1))
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass

    # -- status write ----------------------------------------------------

    def _set_status(
        self, key: str, phase: PodPhase, container_statuses: List[ContainerStatus],
        ready: bool = False, start_time: Optional[float] = None,
        placement=None, message: str = "",
    ) -> None:
        namespace, name = key.split("/", 1)
        for _ in range(5):
            try:
                pod = self.store.get("Pod", namespace, name)
            except NotFound:
                return
            pod.status.phase = phase
            pod.status.container_statuses = container_statuses
            pod.status.message = message
            if start_time is not None and pod.status.start_time is None:
                pod.status.start_time = start_time
            if ready and pod.status.ready_time() is None:
                pod.status.conditions = [
                    c for c in pod.status.conditions if c.type != "Ready"
                ] + [PodCondition(type="Ready", status="True", last_transition_time=now())]
            if placement is not None:
                pod.status.node_name = placement.node_name
                pod.status.tpu_slice = placement.slice_name
                pod.status.tpu_worker_id = placement.worker_id
            try:
                write_status(self.store, pod)
                return
            except Conflict:
                continue
        log.warning("status update for pod %s kept conflicting", key)
