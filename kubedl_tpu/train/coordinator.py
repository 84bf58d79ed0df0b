"""Runtime-side coordinator bootstrap — the single rendezvous scheme.

The operator injects KUBEDL_COORDINATOR_ADDRESS / KUBEDL_NUM_PROCESSES /
KUBEDL_PROCESS_ID (workloads/common.py). Training programs call
`initialize()` once at startup; it wires jax.distributed so XLA collectives
ride ICI within a slice and DCN across slices — replacing the reference's
four per-framework bootstrap paths (TF_CONFIG gRPC ring, torch TCP store,
Rabit tracker, ZooKeeper; SURVEY.md §2.4).
"""
from __future__ import annotations

import logging
import os
import socket
from dataclasses import dataclass
from typing import Optional

log = logging.getLogger("kubedl_tpu.coordinator")

ENV_COORDINATOR_ADDRESS = "KUBEDL_COORDINATOR_ADDRESS"
ENV_NUM_PROCESSES = "KUBEDL_NUM_PROCESSES"
ENV_PROCESS_ID = "KUBEDL_PROCESS_ID"
# Multislice identity (workloads/jaxjob.py, numSlices > 1): which DCN-joined
# slice this process belongs to. The mesh layout itself comes from
# KUBEDL_DCN_MESH (parallel/mesh.py); these are for program-level use —
# logging, per-slice data sharding, profiling labels.
ENV_NUM_SLICES = "KUBEDL_NUM_SLICES"
ENV_SLICE_ID = "KUBEDL_SLICE_ID"
# Live-reshard protocol (train/reshard_runtime.py): the executor injects a
# per-pod control dir the scheduler posts RESIZE messages into; the
# operator opts jobs in via spec.elastic.liveReshard and points the gang
# at a shared staging dir for the multi-process (restart) lane. These are
# part of the SAME rendezvous contract: a resized gang re-joins the
# coordinator with the topology the staging manifest names.
ENV_CONTROL_DIR = "KUBEDL_CONTROL_DIR"
ENV_LIVE_RESHARD = "KUBEDL_LIVE_RESHARD"
ENV_RESHARD_DIR = "KUBEDL_RESHARD_DIR"


@dataclass
class ProcessInfo:
    coordinator_address: Optional[str]
    num_processes: int
    process_id: int
    num_slices: int = 1
    slice_id: int = 0
    # live-reshard wiring (empty/False when the job did not opt in)
    control_dir: str = ""
    live_reshard: bool = False
    reshard_dir: str = ""

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1

    @property
    def is_multislice(self) -> bool:
        return self.num_slices > 1


def process_info() -> ProcessInfo:
    return ProcessInfo(
        coordinator_address=os.environ.get(ENV_COORDINATOR_ADDRESS),
        num_processes=int(os.environ.get(ENV_NUM_PROCESSES, "1")),
        process_id=int(os.environ.get(ENV_PROCESS_ID, "0")),
        num_slices=int(os.environ.get(ENV_NUM_SLICES, "1")),
        slice_id=int(os.environ.get(ENV_SLICE_ID, "0")),
        control_dir=os.environ.get(ENV_CONTROL_DIR, ""),
        live_reshard=os.environ.get(ENV_LIVE_RESHARD, "") == "1",
        reshard_dir=os.environ.get(ENV_RESHARD_DIR, ""),
    )


def _resolve_local(address: str) -> str:
    """Map service-DNS coordinator addresses to loopback when the headless
    DNS name doesn't resolve (local executor mode: all processes share one
    host, so the coordination service is reachable on 127.0.0.1)."""
    host, _, port = address.partition(":")
    try:
        socket.getaddrinfo(host, None)
        return address
    except socket.gaierror:
        return f"127.0.0.1:{port or '8471'}"


# Where JAX_COMPILATION_CACHE_DIR is set (JAXJob spec.compilationCacheDir
# injects it) JAX reads it itself. Otherwise compiled programs are kept
# here: one fixed path inside the checkout, because the path is part of
# the cache key and a pod's working directory is a temporary one.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def place_compile_cache() -> None:
    """The one place that gives JAX's persistent compile cache a default
    directory; every training program and bench.py pass through it."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)


def report_devices() -> None:
    """One line saying which devices this process got. JAX falls back to
    the CPU with a warning when it finds no accelerator, so a pod's log
    must say where it ran (the executor makes a pod that was granted
    chips fail in that case: executor/local.py)."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        if "libtpu" in str(e) and "lockfile" in str(e):
            # libtpu's own text advises removing its lock file; the lock
            # is what keeps two processes off one chip
            raise SystemExit(
                "another process on this host holds the TPU. A chip "
                "belongs to one process at a time, and the local executor "
                "gives every pod that asks for chips all of the host's: "
                "run one such pod per host at a time (do not remove "
                f"libtpu's lock file). JAX said: {e}") from e
        raise
    print(f"devices: platform={devices[0].platform} "
          f"device_kind={devices[0].device_kind} count={len(devices)}",
          flush=True)


def start_local() -> None:
    """initialize() without the rendezvous, for programs that are one JAX
    process each and meet their peers over the transport plane (MPMD
    pipeline stages, the RL fleet)."""
    place_compile_cache()
    report_devices()


def initialize(info: Optional[ProcessInfo] = None) -> ProcessInfo:
    """Idempotently initialize jax.distributed from the injected env."""
    info = info or process_info()
    place_compile_cache()
    if info.is_distributed and info.coordinator_address is not None:
        _rendezvous(info)
    report_devices()
    return info


def _rendezvous(info: ProcessInfo) -> None:
    import jax

    addr = _resolve_local(info.coordinator_address)
    try:
        jax.distributed.initialize(
            coordinator_address=addr,
            num_processes=info.num_processes,
            process_id=info.process_id,
        )
        log.info(
            "jax.distributed initialized: %d/%d via %s",
            info.process_id, info.num_processes, addr,
        )
    except RuntimeError as e:
        if "already initialized" not in str(e):
            raise
