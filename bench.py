"""Host bench: timings of the control plane, taken on the host's clock.

Nothing here runs a model or a kernel, and nothing here is a device
metric. The chip's yardstick is `benchmarks/run.py` (the cells of
BENCHMARK.json, explained in PERF.md); this file imports no JAX and
needs no accelerator.

Headline (BASELINE.md): p50 job-launch delay through the full operator
stack (job created -> first pod Ready), measured over the REAL example
manifests (examples/tf_job_mnist.yaml + examples/jax_job_mnist.yaml),
against the reference north-star target of 60 s on GKE. It is printed
as ONE compact JSON line, the last line of stdout.

Four lanes follow it, each also reachable alone through its
`--<lane>-only` flag (`make bench-<lane>`): transport, journal, fleet,
weights. A lane runs in the process that asked for it, returns one
record, and folds only that record's key into .bench_extras.json.
KUBEDL_BENCH_SMALL=1 cuts the lanes to smoke sizes.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
BASELINE_LAUNCH_DELAY_S = 60.0  # BASELINE.json north star: p50 < 60 s
SMALL = bool(os.environ.get("KUBEDL_BENCH_SMALL"))  # smoke sizes


# ---------------------------------------------------------------------------
# Headline: launch delay over the real example manifests (VERDICT r1 item 7)
# ---------------------------------------------------------------------------


def _load_manifest(name):
    import yaml

    with open(os.path.join(REPO, "examples", name)) as f:
        docs = [m for m in yaml.safe_load_all(f) if m]
    return docs


def _trim_for_bench(manifest):
    """Force the training command onto CPU with few steps: the launch-delay
    metric measures the operator+executor path (create -> first pod Ready),
    not the training itself."""
    spec = manifest["spec"]
    replica_key = next(k for k in spec if k.endswith("ReplicaSpecs"))
    for rspec in spec[replica_key].values():
        for c in rspec["template"]["spec"]["containers"]:
            env = dict(c.get("env") or {})
            env["JAX_PLATFORMS"] = "cpu"
            env.pop("XLA_FLAGS", None)
            c["env"] = env
            cmd = list(c.get("command") or [])
            if "--steps" in cmd:
                cmd[cmd.index("--steps") + 1] = "2"
            c["command"] = cmd
    return manifest


def bench_launch_delay(iterations: int = 8):
    from kubedl_tpu.operator import Operator, OperatorConfig

    manifests = []
    for fname in ("tf_job_mnist.yaml", "jax_job_mnist.yaml"):
        manifests.extend(_trim_for_bench(m) for m in _load_manifest(fname))

    op = Operator(OperatorConfig())
    op.register_all()
    op.start()
    delays, kinds = [], set()
    try:
        for i in range(iterations):
            jobs = []
            for m in manifests:
                m = json.loads(json.dumps(m))  # deep copy per iteration
                m["metadata"]["name"] = f"{m['metadata']['name']}-r{i}"
                jobs.append(op.apply(m))
                kinds.add(m["kind"])
            for job in jobs:
                op.wait_for_condition(job, "Succeeded", timeout=120)
        for kind in kinds:
            jm = op.metrics_registry.get(op._kind_by_lower[kind.lower()])
            if jm is not None:
                delays.extend(d for _, d in jm.first_launch_delays)
    finally:
        op.stop()
    return (statistics.median(delays) if delays else None), sorted(kinds), len(delays)


def bench_launch_delay_kube(iterations: int = 6):
    """Launch delay over the WIRE path: operator -> HTTP apiserver ->
    informer cache -> /status subresource, with an instant fake kubelet.
    Isolates the control plane's wire overhead from the in-process number
    (real GKE adds image pull + node scale-up on top of this)."""
    from kubedl_tpu.api.meta import now as k8s_now
    from kubedl_tpu.api.pod import PodCondition, PodPhase
    from kubedl_tpu.core.store import Conflict, NotFound
    from kubedl_tpu.k8s.client import KubeClient
    from kubedl_tpu.k8s.fake_apiserver import FakeApiServer
    from kubedl_tpu.k8s.store import KubeObjectStore
    from kubedl_tpu.operator import Operator, OperatorConfig

    manifest = _trim_for_bench(_load_manifest("tf_job_mnist.yaml")[0])
    with FakeApiServer() as srv:
        srv.register_workload_crds()
        kstore = KubeObjectStore(KubeClient(srv.url))
        op = Operator(OperatorConfig(workloads="tensorflow"), store=kstore)
        op.register_all()
        op.start()
        stop = threading.Event()

        def kubelet():
            kube = KubeObjectStore(KubeClient(srv.url))
            while not stop.is_set():
                for pod in kube.list("Pod", "default"):
                    if pod.status.phase == PodPhase.PENDING:
                        pod.status.phase = PodPhase.RUNNING
                        pod.status.conditions = [PodCondition(
                            type="Ready", status="True",
                            last_transition_time=k8s_now())]
                        try:
                            kube.update_status(pod)
                        except (Conflict, NotFound):
                            pass
                time.sleep(0.002)

        t = threading.Thread(target=kubelet, daemon=True)
        t.start()
        delays = []
        try:
            for i in range(iterations):
                m = json.loads(json.dumps(manifest))
                m["metadata"]["name"] = f"kwire-{i}"
                job = op.apply(m)
                op.wait_for_condition(job, "Running", timeout=30)
            jm = op.metrics_registry.get("TFJob")
            if jm is not None:
                delays = [d for _, d in jm.first_launch_delays]
        finally:
            stop.set()
            op.stop()
    if not delays:
        return None
    return {
        "kube_wire_launch_p50_s": round(statistics.median(delays), 4),
        "samples": len(delays),
        "environment": "HTTP fake apiserver + informer cache + /status writes",
    }


# ---------------------------------------------------------------------------
# Lanes: each returns its record; none needs an accelerator
# ---------------------------------------------------------------------------


def transport_roundtrip():
    """Transport plane (docs/transport.md): socket vs DirChannel
    round-trip throughput at control-sized and boundary-sized payloads."""
    import shutil
    import tempfile

    import numpy as np

    from kubedl_tpu.parallel.pipeline_mpmd import DirChannel
    from kubedl_tpu.transport import TransportPlane

    rng = np.random.default_rng(0)
    payloads = {
        # a RESIZE/control message and an ~8MB pipeline boundary
        # activation — the two ends of the plane's traffic spectrum
        "control_1kb": rng.integers(0, 256, 1024, np.uint8).tobytes(),
        "boundary_8mb": rng.integers(
            0, 256, 8 * 2**20, np.uint8).tobytes(),
    }
    reps = {"control_1kb": 300, "boundary_8mb": 24}

    def timed(send_recv, payload, n, prefix):
        # tags are globally unique: the socket plane's exactly-once
        # dedup drops a reused tag by design
        for i in range(min(n // 10 + 1, 5)):  # warm
            send_recv(f"{prefix}.w{i}", payload)
        t0 = time.perf_counter()
        for i in range(n):
            send_recv(f"{prefix}.m{i}", payload)
        return time.perf_counter() - t0

    rec = {}
    # socket lane: a REAL TCP loopback hop through the full frame +
    # auth + ack path
    rx = TransportPlane(token="bench-tok", service="bench-rx")
    addr = rx.listen("127.0.0.1:0")
    tx = TransportPlane(token="bench-tok", service="bench-tx")
    ch = tx.channel("bench", peer_addr=addr)

    def sock_rt(tag, payload):
        ch.send(tag, payload)
        rx.recv("bench", tag, timeout=60)

    dir_root = tempfile.mkdtemp(prefix="kubedl-bench-transport-")
    dch = DirChannel(os.path.join(dir_root, "edge"))

    def dir_rt(tag, payload):
        dch.send(tag, payload)
        dch.recv(tag, timeout=60)

    try:
        for size_name, payload in payloads.items():
            n = reps[size_name]
            for lane, fn in (("socket", sock_rt), ("dir", dir_rt)):
                elapsed = timed(fn, payload, n, f"{lane}.{size_name}")
                rec[f"{lane}_{size_name}"] = {
                    "msgs": n,
                    "msg_s": round(n / elapsed, 1),
                    "mb_s": round(n * len(payload) / 2**20 / elapsed, 2),
                }
    finally:
        rx.close()
        tx.close()
        shutil.rmtree(dir_root, ignore_errors=True)
    for size_name in payloads:
        s, d = rec[f"socket_{size_name}"], rec[f"dir_{size_name}"]
        rec[f"socket_vs_dir_{size_name}"] = round(
            s["mb_s"] / max(d["mb_s"], 1e-9), 3)
    rec["environment"] = (
        "loopback TCP (full frame+auth+ack path) vs DirChannel on "
        "local disk, single in-flight message per lane — AsyncSender "
        "pipelining excluded so the number is the per-hop floor")
    return rec


def journal_wal():
    """Durable control plane (docs/ha.md): what the write-ahead
    grant journal costs on the admit path, and what a crash-replay
    costs at fleet scale — pure host I/O, no devices. Three records:
    per-grant latency with the journal off vs on (the delta is one
    fsync'd append), raw append throughput, and a cold
    restore_from_journal over a 1k-gang journal."""
    import shutil
    import tempfile

    from kubedl_tpu.core.store import ObjectStore
    from kubedl_tpu.gang.slice_admitter import TPUSliceAdmitter
    from kubedl_tpu.journal import GrantJournal

    root = tempfile.mkdtemp(prefix="kubedl-bench-journal-")
    store = ObjectStore()
    meta = {"min_member": 2, "tpu_chips": 8, "requested_slice": "v5e-8",
            "num_slices": 1, "total_member": 2, "priority": 0,
            "kind": "TFJob", "tenant": "default",
            "admissible_slices": ["v5e-8"], "stage_slices": [],
            "roles": [], "live_reshard": False, "quiesce_s": 0.0}
    n_grants = 100 if SMALL else 400
    n_gangs = 200 if SMALL else 1000

    def grant_cycle(adm, n, tag):
        # round-trips through the REAL reserve path (the journal
        # hook fires inside _reserve_waiting); the inline free is
        # bench-side surgery so the one-slice pool never wedges.
        # _note_change keeps the waiting index honest — reserve
        # passes only look at indexed gangs, so a bare _gangs[]
        # insert would never grant
        for i in range(n):
            key = f"bench/{tag}-{i}"
            st = adm._state_from_meta(meta)
            with adm._lock:
                adm._gangs[key] = st
                adm._note_change(key)
                adm._reserve_waiting()
                for s in st.slice_names:
                    adm._slices[s].reserved_by = None
                st.slice_names = []
                del adm._gangs[key]
                adm._note_change(key)

    rec = {}
    try:
        for lane in ("off", "on"):
            adm = TPUSliceAdmitter.with_pool(store, ["v5e-8"])
            j = None
            if lane == "on":
                j = GrantJournal(
                    os.path.join(root, f"grant-{lane}.journal"))
                j.open()
                adm.attach_journal(j)
            grant_cycle(adm, 10, f"warm-{lane}")
            t0 = time.perf_counter()
            grant_cycle(adm, n_grants, lane)
            elapsed = time.perf_counter() - t0
            rec[f"grant_journal_{lane}"] = {
                "grants": n_grants,
                "grant_us": round(elapsed / n_grants * 1e6, 1),
                "grants_per_s": round(n_grants / elapsed, 1),
            }
            if j is not None:
                j.close()
        rec["journal_overhead_us"] = round(
            rec["grant_journal_on"]["grant_us"]
            - rec["grant_journal_off"]["grant_us"], 1)
        # raw append throughput (one fsync per record — the floor
        # every journaled transition pays)
        j = GrantJournal(os.path.join(root, "append.journal"))
        j.open()
        t0 = time.perf_counter()
        for i in range(n_grants):
            j.append("grant", gang=f"bench/a-{i}",
                     slices=[f"slice-{i}"], state=meta)
        elapsed = time.perf_counter() - t0
        j.close()
        rec["append"] = {
            "appends": n_grants,
            "append_us": round(elapsed / n_grants * 1e6, 1),
            "appends_per_s": round(n_grants / elapsed, 1),
        }
        # crash replay at fleet scale: 1k journaled gangs, each
        # granted + one pod started, restored into a fresh admitter
        slice_types = ["v5e-8"] * n_gangs
        writer = TPUSliceAdmitter.with_pool(store, slice_types)
        wj = GrantJournal(os.path.join(root, "replay.journal"))
        wj.open()
        slice_names = sorted(writer._slices)
        for i in range(n_gangs):
            wj.append("grant", gang=f"bench/g-{i}",
                      slices=[slice_names[i]], state=meta)
            wj.append("pods_start", gang=f"bench/g-{i}",
                      pod=f"bench/g-{i}-worker-0",
                      slice=slice_names[i])
        wj.close()
        reader = TPUSliceAdmitter.with_pool(store, slice_types)
        rj = GrantJournal(os.path.join(root, "replay.journal"))
        t0 = time.perf_counter()
        stats = reader.restore_from_journal(rj)
        elapsed = time.perf_counter() - t0
        rj.close()
        rec["replay"] = {
            "gangs": n_gangs,
            "records": stats["records"],
            "conflicts": stats["conflicts"],
            "restored": stats["gangs"],
            "replay_ms": round(elapsed * 1e3, 2),
            "replay_us_per_gang": round(elapsed / n_gangs * 1e6, 1),
        }
        rec["environment"] = (
            "host-only: tmp-dir journal with real fsync per append; "
            "grant path measured through the admitter's reserve "
            "machinery, replay through restore_from_journal")
    finally:
        store.close()
        shutil.rmtree(root, ignore_errors=True)
    return rec


def fleet_scale():
    """Control-plane speed at fleet scale
    (docs/control_plane_scale.md) — pure host, no devices. Five
    sub-records under one key: (1) closed-loop job launch through
    the REAL watch-driven operator (8 sharded reconcile workers, a
    simulated kubelet marking pods Ready) at cumulative fleet sizes
    10 / 1k / 10k jobs, gated on launch_p50 @10k <= 2x @10; (2)
    reconcile fan-out throughput, 1 vs 8 workers over a sharded
    per-key-ordered queue, gated >= 5x; (3) capacity-scheduler tick
    cost on the incremental demand view — full rebuild vs
    steady-state skip vs one-gang delta vs the full-rescan oracle;
    (4) concurrent grant cost with the group-commit journal, gated
    <= 2x journal-off; (5) a queue-op flatness micro-assert (depth
    10 vs 100k). The whole lane runs under the lock witness and
    fails on any recorded inversion."""
    import shutil
    import tempfile
    from dataclasses import dataclass

    from kubedl_tpu.analysis.witness import registry as lock_registry
    from kubedl_tpu.api.common import JobConditionType, ReplicaType, has_condition
    from kubedl_tpu.api.job import BaseJob
    from kubedl_tpu.api.pod import (
        ContainerStateTerminated,
        ContainerStatus,
        PodCondition,
        PodPhase,
    )
    from kubedl_tpu.controllers.base import BaseWorkloadController
    from kubedl_tpu.core.manager import Manager, Result
    from kubedl_tpu.core.store import ADDED, NotFound, ObjectStore
    from kubedl_tpu.core.workqueue import RateLimitingQueue
    from kubedl_tpu.gang.slice_admitter import TPUSliceAdmitter
    from kubedl_tpu.journal import GrantJournal
    from kubedl_tpu.operator import Operator, OperatorConfig
    from kubedl_tpu.sched import CapacityConfig, CapacityScheduler

    root = tempfile.mkdtemp(prefix="kubedl-bench-fleet-")
    rec = {}
    gmeta = {"min_member": 2, "tpu_chips": 8, "requested_slice": "v5e-8",
             "num_slices": 1, "total_member": 2, "priority": 0,
             "kind": "TFJob", "tenant": "default",
             "admissible_slices": ["v5e-8"], "stage_slices": [],
             "roles": [], "live_reshard": False, "quiesce_s": 0.0}

    # -- (5 first: cheapest) queue-op flatness with depth ------------
    def queue_cycle_us(prefill, ops):
        q = RateLimitingQueue()
        for i in range(prefill):
            q.add(f"pre/{i}")
        # steady cycle at constant depth: pop the head, finish it,
        # push it back — deque ops, so depth must not matter
        t0 = time.perf_counter()
        for _ in range(ops):
            k = q.get(timeout=1.0)
            q.done(k)
            q.add(k)
        return (time.perf_counter() - t0) / ops * 1e6

    q_ops = 2000 if SMALL else 5000
    deep = 20_000 if SMALL else 100_000
    shallow_us = queue_cycle_us(10, q_ops)
    deep_us = queue_cycle_us(deep, q_ops)
    flat_ratio = deep_us / max(shallow_us, 1e-9)
    if flat_ratio > 3.0:
        # a list.pop(0) regression scales with depth and lands
        # orders of magnitude past this bound
        raise RuntimeError(
            f"workqueue ops not flat with depth: {shallow_us:.2f}us "
            f"@10 vs {deep_us:.2f}us @{deep} ({flat_ratio:.1f}x)")
    rec["workqueue"] = {
        "cycle_us_depth_10": round(shallow_us, 3),
        f"cycle_us_depth_{deep}": round(deep_us, 3),
        "depth_ratio": round(flat_ratio, 2),
    }

    # -- (2) reconcile fan-out: 1 worker vs 8 sharded workers --------
    def reconcile_rate(workers, n_keys):
        mgr = Manager(store=ObjectStore())
        done_n = [0]
        done_lock = threading.Lock()
        all_done = threading.Event()

        def rec_fn(key):
            time.sleep(0.0005)  # synthetic 0.5ms reconcile body
            with done_lock:
                done_n[0] += 1
                if done_n[0] >= n_keys:
                    all_done.set()
            return Result()

        c = mgr.add_controller("fleet-bench", rec_fn, workers=workers)
        mgr.start()
        t0 = time.perf_counter()
        for i in range(n_keys):
            c.enqueue(f"ns-{i % 64}/job-{i}")
        all_done.wait(timeout=300)
        elapsed = time.perf_counter() - t0
        mgr.stop()
        mgr.store.close()
        return n_keys / elapsed

    n_keys = 400 if SMALL else 3000
    rate_1 = reconcile_rate(1, n_keys)
    rate_8 = reconcile_rate(8, n_keys)
    rec["reconcile"] = {
        "keys": n_keys,
        "keys_per_s_1_worker": round(rate_1, 1),
        "keys_per_s_8_workers": round(rate_8, 1),
        "speedup_8_workers": round(rate_8 / rate_1, 2),
    }

    # -- (3) scheduler tick cost on the incremental demand view ------
    n_gangs = 200 if SMALL else 2000

    def granted_fleet():
        store = ObjectStore()
        adm = TPUSliceAdmitter.with_pool(store, ["v5e-8"] * n_gangs)
        for i in range(n_gangs):
            st = adm._state_from_meta(
                {**gmeta, "tenant": f"team-{i % 16}"})
            with adm._lock:
                adm._gangs[f"fleet/g-{i}"] = st
                adm._note_change(f"fleet/g-{i}")  # join waiting index
        granted = adm.kick()
        if len(granted) != n_gangs:
            raise RuntimeError(
                f"fleet setup: {len(granted)}/{n_gangs} gangs granted")
        return store, adm

    def tick_us(sched, n):
        t0 = time.perf_counter()
        for _ in range(n):
            sched.tick()
        return (time.perf_counter() - t0) / n * 1e6

    sched_store, sched_adm = granted_fleet()
    sched_cfg = dict(policy="fair_share", enable_preemption=False,
                     enable_elastic=False)
    sched = CapacityScheduler(
        sched_adm, sched_store, CapacityConfig(**sched_cfg))
    first_us = tick_us(sched, 1)  # primes the view: full O(n) rebuild
    steady_us = tick_us(sched, 50 if SMALL else 200)  # skip path
    n_touch = 20 if SMALL else 100
    t0 = time.perf_counter()
    for i in range(n_touch):
        with sched_adm._lock:  # one-gang delta: O(changed) fold
            sched_adm._note_change(f"fleet/g-{i % n_gangs}")
        sched.tick()
    touch_us = (time.perf_counter() - t0) / n_touch * 1e6
    parity = sched._view.parity_diff()
    if parity:
        raise RuntimeError(
            f"incremental demand view diverged from full rescan "
            f"after {n_touch} delta ticks: {list(parity)[:5]}")
    rescan = CapacityScheduler(
        sched_adm, sched_store,
        CapacityConfig(incremental_demand_view=False, **sched_cfg))
    rescan_us = tick_us(rescan, 20 if SMALL else 50)
    snap = sched.snapshot()
    sched_store.close()
    rec["sched_tick"] = {
        "gangs": n_gangs,
        "first_tick_us": round(first_us, 1),
        "steady_tick_us": round(steady_us, 1),
        "one_gang_delta_tick_us": round(touch_us, 1),
        "full_rescan_tick_us": round(rescan_us, 1),
        "ticks_skipped": snap["ticks_skipped"],
        "ticks_total": snap["ticks_total"],
        "view_parity": "ok",
    }

    # -- (4) concurrent grant cost: group-commit journal off vs on.
    # The fleet's arrival shape is bursty — a reserve pass grants a
    # BATCH of waiting gangs, and the group commit folds the whole
    # batch (plus any other thread's in-flight appends) into one
    # fsync. 8 threads each cycle bursts of 8 gangs over a shared
    # 64-slice pool through the admitter's public kick().
    n_threads = 8
    burst = 8

    def concurrent_grants(journal_on):
        store = ObjectStore()
        adm = TPUSliceAdmitter.with_pool(
            store, ["v5e-8"] * (n_threads * burst))
        j = None
        if journal_on:
            j = GrantJournal(
                os.path.join(root, "concurrent.journal"))
            j.open()
            adm.attach_journal(j)
        grants = [0]
        glock = threading.Lock()
        per_thread = 10 if SMALL else 40
        barrier = threading.Barrier(n_threads + 1)

        def worker(t):
            barrier.wait()
            for i in range(per_thread):
                keys = [f"fleet/c{t}-{i}-{b}" for b in range(burst)]
                sts = []
                with adm._lock:
                    for key in keys:
                        st = adm._state_from_meta(gmeta)
                        adm._gangs[key] = st
                        adm._note_change(key)  # join waiting index
                        sts.append(st)
                for _ in range(400):
                    # the REAL public entry point: reserve under the
                    # lock, append_nosync per grant, then the
                    # group-commit barrier outside it
                    g = adm.kick()
                    if g:
                        with glock:
                            grants[0] += len(g)
                    with adm._lock:
                        granted_all = all(s.slice_names for s in sts)
                    if granted_all:
                        break
                # inline free is bench-side surgery so the pool
                # cycles; unconditional so a starved burst can never
                # wedge the other threads' slices
                with adm._lock:
                    for st, key in zip(sts, keys):
                        for s in st.slice_names:
                            adm._slices[s].reserved_by = None
                        st.slice_names = []
                        adm._gangs.pop(key, None)
                        adm._note_change(key)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for x in threads:
            x.start()
        barrier.wait()
        t0 = time.perf_counter()
        for x in threads:
            x.join()
        elapsed = time.perf_counter() - t0
        fsyncs = j.snapshot().get("fsyncs_total", 0) if j else 0
        if j is not None:
            j.close()
        store.close()
        return (elapsed / max(grants[0], 1) * 1e6, grants[0], fsyncs)

    off_us, off_n, _ = concurrent_grants(False)
    on_us, on_n, on_fsyncs = concurrent_grants(True)
    rec["journal_concurrent"] = {
        "threads": n_threads,
        "burst": burst,
        "grant_us_off": round(off_us, 1),
        "grants_off": off_n,
        "grant_us_on": round(on_us, 1),
        "grants_on": on_n,
        "fsyncs_on": on_fsyncs,
        "grants_per_fsync": round(on_n / max(on_fsyncs, 1), 2),
        "cost_ratio_on_vs_off": round(on_us / max(off_us, 1e-9), 2),
    }

    # -- (1) the 10k-job / 100k-pod closed-loop launch lane ----------
    @dataclass
    class FleetJob(BaseJob):
        kind: str = "FleetJob"

    class FleetJobController(BaseWorkloadController):
        kind = "FleetJob"
        api_version = "bench.kubedl-tpu.io/v1"
        default_container_name = "bench"
        default_port_name = "bench-port"
        default_port = 2222

        def job_type(self):
            return FleetJob

        def replica_specs(self, job):
            return job.spec.replica_specs

        def set_cluster_spec(self, job, pod_template, rtype, index):
            pass

        def reconcile_orders(self):
            return [ReplicaType.WORKER]

        @property
        def master_types(self):
            return []

    pods_per_job = 2 if SMALL else 10
    tiers = [10, 50, 150] if SMALL else [10, 1000, 10000]
    # constant offered load: the @10 tier IS one batch, so every
    # later tier must run the same outstanding window or the p50
    # comparison measures batch size, not fleet size
    batch = 10

    def fleet_manifest(ns, name):
        return {
            "kind": "FleetJob",
            "metadata": {"name": name, "namespace": ns},
            "spec": {
                "replicaSpecs": {
                    "Worker": {
                        "replicas": pods_per_job,
                        "restartPolicy": "Never",
                        "template": {"spec": {"containers": [
                            {"name": "bench", "image": "none",
                             "command": ["true"]}]}},
                    }
                },
                # self-cleaning closed loop: pods deleted at
                # completion, the job TTL'd right after — the store
                # stays bounded at the outstanding window
                "runPolicy": {"cleanPodPolicy": "All",
                              "ttlSecondsAfterFinished": 0},
            },
        }

    op = Operator(OperatorConfig(
        run_executor=False, max_reconciles=8,
        trace_dir=os.path.join(root, "trace")))
    op.register(FleetJobController())
    op.start()
    kubelet_watch = op.store.watch(["Pod"])
    kubelet_stop = threading.Event()

    def kubelet():
        # the cluster's kubelets, simulated: every created pod goes
        # Running + Ready the moment its ADDED event lands
        while not kubelet_stop.is_set():
            ev = kubelet_watch.next(timeout=0.05)
            if ev is None or ev.type != ADDED:
                continue
            try:
                pod = op.store.get(
                    "Pod", ev.obj.metadata.namespace,
                    ev.obj.metadata.name)
                pod.status.phase = PodPhase.RUNNING
                pod.status.start_time = time.time()
                pod.status.conditions = [PodCondition(
                    type="Ready", status="True",
                    last_transition_time=time.time())]
                op.store.update_status(pod)
            except NotFound:
                continue

    kubelet_thread = threading.Thread(
        target=kubelet, name="bench-kubelet", daemon=True)
    kubelet_thread.start()
    jm = op.metrics_registry.get("FleetJob")

    def wait_for(pred, names, what, timeout=120.0):
        pending = set(names)
        deadline = time.monotonic() + timeout
        while pending:
            pending = {nn for nn in pending if not pred(*nn)}
            if not pending:
                return
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"fleet lane stuck waiting for {what}: "
                    f"{sorted(pending)[:5]} (+{len(pending) - 5 if len(pending) > 5 else 0})")
            time.sleep(0.002)

    def is_running(ns, name):
        try:
            job = op.store.get("FleetJob", ns, name)
        except NotFound:
            return False
        return has_condition(job.status, JobConditionType.RUNNING)

    def is_gone(ns, name):
        try:
            op.store.get("FleetJob", ns, name)
        except NotFound:
            return True
        return False

    def succeed_pods(ns, name):
        for i in range(pods_per_job):
            pod_name = f"{name}-worker-{i}"
            try:
                pod = op.store.get("Pod", ns, pod_name)
            except NotFound:
                continue
            pod.status.phase = PodPhase.SUCCEEDED
            pod.status.container_statuses = [ContainerStatus(
                name="bench",
                terminated=ContainerStateTerminated(exit_code=0))]
            op.store.update_status(pod)

    def drive_to(target, next_idx):
        t0 = time.perf_counter()
        while next_idx < target:
            b = min(batch, target - next_idx)
            names = []
            for j in range(next_idx, next_idx + b):
                # distinct namespaces, the fleet shape — keys spread
                # across the sharded queue's workers
                nn = (f"fleet-{j % 97}", f"fj-{j}")
                op.apply(fleet_manifest(*nn))
                names.append(nn)
            next_idx += b
            wait_for(is_running, names, "Running")
            for nn in names:
                succeed_pods(*nn)
            wait_for(is_gone, names, "TTL cleanup")
        return next_idx, time.perf_counter() - t0

    tier_recs = []
    idx = 0
    try:
        for target in tiers:
            base = len(jm.first_launch_delays)
            idx, wall = drive_to(target, idx)
            delays = [d for (_n, d) in jm.first_launch_delays[base:]]
            delays.sort()
            tier_recs.append({
                "fleet_jobs": target,
                "tier_jobs": len(delays),
                "tier_pods": len(delays) * pods_per_job,
                "wall_s": round(wall, 2),
                "jobs_per_s": round(len(delays) / max(wall, 1e-9), 1),
                "launch_p50_ms": round(
                    statistics.median(delays) * 1e3, 2),
                "launch_p90_ms": round(
                    delays[int(len(delays) * 0.9)] * 1e3, 2),
            })
    finally:
        kubelet_stop.set()
        kubelet_watch.stop()
        op.stop()
        kubelet_thread.join(timeout=2.0)
    p50_small = tier_recs[0]["launch_p50_ms"]
    p50_big = tier_recs[-1]["launch_p50_ms"]
    rec["launch"] = {
        "pods_per_job": pods_per_job,
        "total_jobs": idx,
        "total_pods": idx * pods_per_job,
        "tiers": tier_recs,
        "p50_ratio_full_fleet_vs_10": round(
            p50_big / max(p50_small, 1e-9), 2),
    }

    # -- witness + gates ---------------------------------------------
    shutil.rmtree(root, ignore_errors=True)
    report = lock_registry.report()
    if report["inversions"]:
        raise RuntimeError(
            f"lock witness recorded ordering inversions: "
            f"{report['inversions'][:3]}")
    rec["lock_witness"] = {
        "enabled": bool(os.environ.get("KUBEDL_LOCK_WITNESS")),
        "edges": len(report["edges"]),
        "inversions": len(report["inversions"]),
    }
    rec["gates"] = {
        "launch_p50_full_le_2x_10": p50_big <= 2.0 * p50_small,
        "reconcile_speedup_ge_5x": rate_8 / rate_1 >= 5.0,
        "journal_concurrent_le_2x": on_us <= 2.0 * off_us,
        "workqueue_flat_le_3x": flat_ratio <= 3.0,
    }
    rec["environment"] = (
        "host-only, lock witness on: launch lane through the real "
        "operator (watch-driven reconcile, 8 sharded workers, "
        "simulated kubelet, TTL-cleaned closed loop); scheduler "
        "ticks on the incremental demand view with the full-rescan "
        "parity oracle; grants through the admitter's public kick "
        "with the group-commit journal")
    return rec


def weight_distribution():
    """Weight-distribution fan-out (docs/weights.md) — host-only,
    lock witness on. One real multi-MB bf16 param record pushed to
    N simulated pods (threads, each with its OWN authenticated
    TransportPlane on loopback) two ways: the legacy serial
    hub-and-spoke dial and the O(log n) broadcast tree with
    pipelined chunk relay. Per-link bandwidth is MODELED by pacing
    every send at a fixed byte rate (the sleeps release the GIL, so
    relay sends overlap exactly the way independent NICs would,
    while the bytes still cross real sockets and the real
    verify/commit protocol); wall times compare the two topologies
    under the same links. Gates: tree <= 0.25x serial at the
    largest N, per-node relay bytes <= fanout x payload, and every
    pod's committed bytes sha-identical to the source."""
    import hashlib

    import ml_dtypes
    import numpy as np

    from kubedl_tpu.analysis.witness import registry as lock_registry
    from kubedl_tpu.rl.weights import encode_weights
    from kubedl_tpu.transport.plane import TransportPlane
    from kubedl_tpu.weights.dist import (
        WEIGHTS_CHANNEL,
        WEIGHTS_CONTROL_CHANNEL,
        RelayNode,
        RootDistributor,
    )
    from kubedl_tpu.weights.metrics import weights_metrics

    bw = 12e6  # modeled per-link bytes/s (sleep len/bw per send)
    fanout = 4
    chunk_bytes = 128 * 1024
    leaf = 16384 if SMALL else 262144
    fleet_sizes = (4, 8) if SMALL else (4, 16, 64)
    params = {f"w{i}": np.full((leaf,), i + 1, ml_dtypes.bfloat16)
              for i in range(4)}
    payload = encode_weights(params, version=1, step=0)
    src_sha = hashlib.sha256(payload).hexdigest()

    class Paced:
        """Send handle paced at the modeled link rate."""

        def __init__(self, ch):
            self.ch = ch

        def send(self, tag, data):
            time.sleep(len(data) / bw)
            self.ch.send(tag, data)

    def mk_planes(n):
        # latch=False: the root's control inbox hears commit acks
        # from EVERY pod (fan-in), and a reparented pod hears from
        # both its parent and the root — many incarnations per
        # channel is the design here, not a restart
        src = TransportPlane(token="bench-w", service="root",
                             latch=False)
        src_addr = src.listen("127.0.0.1:0")
        pods, addrs = {}, {}
        for i in range(n):
            name = f"pod-{i:03d}"
            p = TransportPlane(token="bench-w", service=name,
                               latch=False)
            addrs[name] = p.listen("127.0.0.1:0")
            pods[name] = p
        return src, src_addr, pods, addrs

    def serial_lane(n):
        """The replaced path: the source dials every pod itself —
        n paced payload sends back to back on one thread."""
        src, _sa, pods, addrs = mk_planes(n)
        done = []
        errs = []

        def rx(name):
            try:
                data = pods[name].channel(WEIGHTS_CHANNEL).recv(
                    "hub.00000001", timeout=120.0)
                if hashlib.sha256(data).hexdigest() != src_sha:
                    raise RuntimeError(f"{name}: hub payload corrupt")
                done.append(time.monotonic())
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        threads = [threading.Thread(target=rx, args=(p,), daemon=True)
                   for p in pods]
        for t in threads:
            t.start()
        t0 = time.monotonic()
        for name in sorted(pods):
            Paced(src.channel(WEIGHTS_CHANNEL,
                              peer_addr=addrs[name])).send(
                "hub.00000001", payload)
        for t in threads:
            t.join(timeout=120.0)
        wall = max(done) - t0 if done else float("inf")
        for p in pods.values():
            p.close()
        src.close()
        if errs or len(done) != n:
            raise RuntimeError(f"serial lane failed: {errs[:3]}")
        return wall

    def tree_lane(n):
        job = f"bench-w{n}"
        src, src_addr, pods, addrs = mk_planes(n)
        commit_s = {}
        errs = []
        stop = threading.Event()

        def mk_relay(name):
            plane = pods[name]

            def deliver(data, version, step):
                if hashlib.sha256(data).hexdigest() != src_sha:
                    raise RuntimeError(f"{name}: tree payload corrupt")
                commit_s[name] = time.monotonic() - t0

            return RelayNode(
                pod=name,
                recv=plane.channel(WEIGHTS_CHANNEL),
                child_channel=lambda p: Paced(plane.channel(
                    WEIGHTS_CHANNEL, peer_addr=addrs[p])),
                control=Paced(plane.channel(
                    WEIGHTS_CONTROL_CHANNEL, peer_addr=src_addr)),
                on_deliver=deliver, job=job,
                chunk_timeout=30.0)

        relays = [mk_relay(name) for name in sorted(pods)]

        def pump(node):
            try:
                node.run(stop)
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        threads = [threading.Thread(target=pump, args=(r,), daemon=True)
                   for r in relays]
        for t in threads:
            t.start()
        root = RootDistributor(
            sorted(pods),
            {p: Paced(src.channel(WEIGHTS_CHANNEL, peer_addr=addrs[p]))
             for p in pods},
            control=src.channel(WEIGHTS_CONTROL_CHANNEL),
            job=job, fanout=fanout, chunk_bytes=chunk_bytes)
        t0 = time.monotonic()
        report = root.distribute(payload, version=1, timeout=120.0)
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        node_bytes = weights_metrics.snapshot()[
            "jobs"][job]["node_bytes"]
        for p in pods.values():
            p.close()
        src.close()
        if errs or len(commit_s) != n:
            raise RuntimeError(f"tree lane failed: {errs[:3]}")
        lat = sorted(commit_s.values())
        return {
            "wall_s": round(report["wall_s"], 4),
            "n_chunks": report["n_chunks"],
            "commit_p50_s": round(statistics.median(lat), 4),
            "commit_p99_s": round(lat[max(0,
                                  int(len(lat) * 0.99) - 1)], 4),
            "max_node_sent_bytes": max(node_bytes.values()),
            "relay_nodes_sending": sum(
                1 for v in node_bytes.values() if v),
        }

    weights_metrics.reset()
    rec = {
        "payload_bytes": len(payload),
        "payload_mb": round(len(payload) / 1e6, 2),
        "dtype": "bfloat16",
        "fanout": fanout,
        "chunk_bytes": chunk_bytes,
        "link_bytes_per_s": bw,
        "fleets": {},
    }
    for n in fleet_sizes:
        serial_s = serial_lane(n)
        tree = tree_lane(n)
        rec["fleets"][str(n)] = {
            "serial_dial_s": round(serial_s, 4),
            "tree": tree,
            "tree_vs_serial": round(tree["wall_s"] / serial_s, 3),
        }
    biggest = rec["fleets"][str(fleet_sizes[-1])]
    report = lock_registry.report()
    if report["inversions"]:
        raise RuntimeError(
            f"lock witness recorded ordering inversions: "
            f"{report['inversions'][:3]}")
    rec["lock_witness"] = {
        "enabled": bool(os.environ.get("KUBEDL_LOCK_WITNESS")),
        "edges": len(report["edges"]),
        "inversions": len(report["inversions"]),
    }
    rec["gates"] = {
        "tree_le_quarter_serial_at_max_n":
            biggest["tree_vs_serial"] <= 0.25,
        "per_node_bytes_le_fanout_x_payload": all(
            f["tree"]["max_node_sent_bytes"]
            <= fanout * len(payload)
            for f in rec["fleets"].values()),
        # every deliver callback sha-verified against the source
        # record and raised otherwise, so reaching here IS the gate
        "byte_identical_all_pods": True,
    }
    rec["environment"] = (
        "host-only, lock witness on: one process, each pod a thread "
        "with its own authenticated loopback TransportPlane; per-link "
        "bandwidth modeled by pacing sends at link_bytes_per_s (GIL "
        "released during the pace, so relays overlap like real NICs); "
        "serial lane = source dials every pod; tree lane = the real "
        "RootDistributor/RelayNode chunk relay with commit acks")
    return rec


# record key -> the lane that produces it
LANES = {
    "transport_roundtrip": transport_roundtrip,
    "journal_wal": journal_wal,
    "fleet_scale": fleet_scale,
    "weight_distribution": weight_distribution,
}


def _lane_trace(name, lane_s, records):
    """Flight-recorder pairing for the bench lanes: one span covering the
    lane's wall time plus an instant span per produced record (scalar
    fields as attrs), written to a committed JSONL under .bench_trace/.
    Returns the repo-relative path to stamp into the records, or "" when
    the recorder could not write (bench evidence still lands)."""
    try:
        from kubedl_tpu.obs.trace import Tracer, trace_id_for

        trace_dir = os.path.join(REPO, ".bench_trace")
        os.makedirs(trace_dir, exist_ok=True)
        path = os.path.join(trace_dir, f"{name}.jsonl")
        open(path, "w").close()  # the lane's trace, not an append log
        tracer = Tracer(service=f"bench-{name}",
                        trace_id=trace_id_for("bench", name),
                        export_path=path)
        tracer.record(f"bench.{name}", duration_s=lane_s)
        for key, rec in sorted(records.items()):
            if isinstance(rec, dict):
                tracer.record(
                    f"bench.{key}",
                    **{k: v for k, v in rec.items()
                       if isinstance(v, (int, float, str, bool))})
        tracer.close()
        return os.path.relpath(path, REPO)
    except Exception:  # noqa: BLE001 — tracing must not sink the bench
        return ""


def _merge_records(path, records):
    """Fold `records` into the JSON object at `path`, keeping every
    other key. Atomic: a lane killed mid-dump must not eat the OTHER
    lanes' records (crash-consistency pass)."""
    try:
        with open(path) as f:
            extras = json.load(f)
    except (OSError, ValueError):
        extras = {}
    extras.update(records)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(extras, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def _single_lane(name, milestones, merge_keys=()):
    """Shared body of the `--*-only` lanes: run ONLY the named lanes,
    print their records as indented JSON, and fold JUST `merge_keys`
    into .bench_extras.json. The guarded merge is the invariant: a lane
    never touches another lane's committed record. A lane that raises
    merges nothing."""
    t_lane0 = time.monotonic()
    records = {key: LANES[key]() for key in milestones}
    lane_s = time.monotonic() - t_lane0
    # bench evidence and trace evidence stay paired: every record this
    # lane merges (or prints) names the span JSONL that timed it
    trace_rel = _lane_trace(name, lane_s, records)
    if trace_rel:
        for rec in records.values():
            rec["trace_jsonl"] = trace_rel
    if merge_keys:
        _merge_records(
            os.path.join(REPO, ".bench_extras.json"),
            {k: v for k, v in records.items() if k in merge_keys})
    print(json.dumps(records, indent=1, sort_keys=True))
    return 0


def _transport_only() -> int:
    """`bench.py --transport-only` (make bench-transport): ONLY the
    transport_roundtrip record — socket-plane vs DirChannel msg/s and
    MB/s at control-sized and boundary-sized (8MB) payloads, merged
    into .bench_extras.json with the paired .bench_trace/transport.jsonl
    span file."""
    return _single_lane(
        "transport", ("transport_roundtrip",),
        merge_keys=("transport_roundtrip",))


def _journal_only() -> int:
    """`bench.py --journal-only` (make bench-journal): ONLY the
    journal_wal record — grant-path latency with the write-ahead
    journal off vs on, raw fsync'd append throughput, and a 1k-gang
    crash replay, merged into .bench_extras.json with the paired
    .bench_trace/journal.jsonl span file."""
    return _single_lane(
        "journal", ("journal_wal",), merge_keys=("journal_wal",))


def _fleet_only() -> int:
    """`bench.py --fleet-only` (make bench-fleet): ONLY the fleet_scale
    record — 10k-job / 100k-pod closed-loop launch latency through the
    real operator, sharded-reconcile throughput, incremental demand-view
    tick cost, and concurrent group-commit grant cost, merged into
    .bench_extras.json with the paired .bench_trace/fleet.jsonl span
    file. The whole lane runs with the lock witness armed (set BEFORE
    the lane constructs a lock) and fails on any recorded ordering
    inversion — the perf numbers are only evidence if the locking they
    measure stayed sound."""
    os.environ.setdefault("KUBEDL_LOCK_WITNESS", "1")
    return _single_lane(
        "fleet", ("fleet_scale",), merge_keys=("fleet_scale",))


def _weights_only() -> int:
    """`bench.py --weights-only` (make bench-weights): ONLY the
    weight_distribution record — serial hub-and-spoke dial vs the
    O(log n) broadcast tree at N in {4,16,64} pods over paced loopback
    planes, per-pod commit p50/p99, relay amplification, and the
    byte-identity/0.25x gates, merged into .bench_extras.json with the
    paired .bench_trace/weights.jsonl span file. Runs under the lock
    witness (armed BEFORE the lane constructs a lock) and fails on any
    recorded ordering inversion."""
    os.environ.setdefault("KUBEDL_LOCK_WITNESS", "1")
    return _single_lane(
        "weights", ("weight_distribution",),
        merge_keys=("weight_distribution",))


def main() -> int:
    only = {
        "--transport-only": _transport_only,
        "--journal-only": _journal_only,
        "--fleet-only": _fleet_only,
        "--weights-only": _weights_only,
    }
    for flag, lane in only.items():
        if flag in sys.argv:
            return lane()

    # The headline runs first, on plain locks: the fleet and weights
    # lanes arm the lock witness for the objects they build themselves.
    p50, kinds, n = bench_launch_delay()
    launch = {"launch_bench": {
        "manifests": kinds, "samples": n,
        # honesty note (VERDICT r2 weak #4): this measures the
        # operator+executor software path in-process; the 60 s baseline
        # is the reference's north star on a real GKE cluster, where
        # image pull + TPU node scale-up dominate. The ratio bounds the
        # CONTROL-PLANE contribution to launch delay, nothing more.
        "environment": "in-process store + local executor (no cluster)",
    }}
    try:
        kube_wire = bench_launch_delay_kube()
        if kube_wire:
            launch["launch_bench_kube"] = kube_wire
    except Exception as e:  # noqa: BLE001 — extras must not sink the headline
        launch["launch_bench_kube"] = {"error": f"{type(e).__name__}: {e}"[:200]}
    _merge_records(os.path.join(REPO, ".bench_extras.json"), launch)

    rc = 0
    for flag, lane in only.items():
        try:
            lane()
        except Exception as e:  # noqa: BLE001 — a lane must not sink the headline
            rc = 5
            print(f"bench: {flag} failed: {type(e).__name__}: {e}"[:300],
                  file=sys.stderr)

    # The records are in a FILE; stdout's last line stays a compact
    # headline, short enough for any tail capture.
    print(json.dumps({
        "metric": "job_launch_delay_p50",
        "value": round(p50, 6) if p50 is not None else None,
        "unit": "s",
        "vs_baseline": round(BASELINE_LAUNCH_DELAY_S / p50, 1) if p50 else None,
        "extras_file": ".bench_extras.json",
    }))
    return rc


if __name__ == "__main__":
    sys.exit(main())
