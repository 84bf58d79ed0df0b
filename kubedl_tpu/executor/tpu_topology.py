"""TPU slice topology — types, torus coordinates, ICI-aware ring placement.

Net-new vs the reference (which schedules generic GPU/CPU pods): models Cloud
TPU pod slices so gang admission can be all-or-nothing per slice
(SURVEY.md §2.4 "TPU-slice admission") and context-parallel rings can be laid
out on ICI-adjacent hosts (SURVEY.md §7 step 9).

A slice type like "v5e-16" resolves to a chip grid (e.g. 4x4), a
chips-per-host count, and host coordinates. `ring_order` returns hosts in a
snake walk through the torus so consecutive ranks are ICI neighbors — the
placement the JAXJob controller uses for the context-parallel mesh axis.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

# generation -> chips per host
CHIPS_PER_HOST = {"v4": 4, "v5p": 4, "v5e": 8, "v6e": 8}

# default chip-grid topologies per slice size (x, y[, z])
_DEFAULT_TOPOLOGY = {
    ("v5e", 1): (1, 1),
    ("v5e", 4): (2, 2),
    ("v5e", 8): (2, 4),
    ("v5e", 16): (4, 4),
    ("v5e", 32): (4, 8),
    ("v5e", 64): (8, 8),
    ("v5e", 128): (8, 16),
    ("v5e", 256): (16, 16),
    ("v6e", 8): (2, 4),
    ("v6e", 16): (4, 4),
    ("v6e", 32): (4, 8),
    ("v6e", 64): (8, 8),
    ("v6e", 256): (16, 16),
}


def _cube_topology(chips: int) -> Tuple[int, ...]:
    """v4/v5p 3D torus: closest factorization into x<=y<=z with 4-chip hosts."""
    best = None
    for x in range(1, int(round(chips ** (1 / 3))) + 2):
        if chips % x:
            continue
        rest = chips // x
        for y in range(x, int(rest**0.5) + 2):
            if rest % y:
                continue
            z = rest // y
            if z < y:
                continue
            cand = (x, y, z)
            score = z - x  # prefer near-cubes
            if best is None or score < best[0]:
                best = (score, cand)
    return best[1] if best else (1, 1, chips)


@dataclass(frozen=True)
class SliceType:
    generation: str  # "v5e" | "v5p" | "v4" | "v6e"
    chips: int
    topology: Tuple[int, ...]

    @property
    def name(self) -> str:
        return f"{self.generation}-{self.chips}"

    @property
    def chips_per_host(self) -> int:
        return min(CHIPS_PER_HOST[self.generation], self.chips)

    @property
    def num_hosts(self) -> int:
        return max(1, self.chips // self.chips_per_host)

    @property
    def topology_str(self) -> str:
        return "x".join(str(d) for d in self.topology)


def parse_slice_type(name: str) -> SliceType:
    """Parse "v5e-8", "v5p-32", "v4-16" into a SliceType."""
    m = re.fullmatch(r"(v\d+[ep]?)-(\d+)", name.strip())
    if not m:
        raise ValueError(f"unrecognized TPU slice type: {name!r}")
    gen, chips = m.group(1), int(m.group(2))
    if gen not in CHIPS_PER_HOST:
        raise ValueError(f"unknown TPU generation {gen!r} in {name!r}")
    if gen in ("v4", "v5p"):
        # v4/v5p slice names count TensorCores; chips = cores / 2.
        chip_count = max(chips // 2, 1)
        topo = _cube_topology(chip_count)
    else:
        chip_count = chips
        topo = _DEFAULT_TOPOLOGY.get((gen, chips)) or _grid_topology(chips)
    return SliceType(generation=gen, chips=chip_count, topology=topo)


def _grid_topology(chips: int) -> Tuple[int, int]:
    x = int(chips**0.5)
    while chips % x:
        x -= 1
    return (x, chips // x)


def host_coords(st: SliceType) -> List[Tuple[int, ...]]:
    """Host coordinates in the host grid (chip grid / host footprint)."""
    if len(st.topology) == 2:
        hx, hy = st.topology
        # v5e hosts are 2x4 chip blocks
        fx, fy = (2, 4) if st.chips_per_host == 8 else (1, st.chips_per_host)
        gx, gy = max(hx // fx, 1), max(hy // fy, 1)
        return [(i, j) for i in range(gx) for j in range(gy)]
    hx, hy, hz = st.topology
    # v4/v5p hosts are 2x2x1 chip blocks
    gx, gy, gz = max(hx // 2, 1), max(hy // 2, 1), hz
    return [(i, j, k) for i in range(gx) for j in range(gy) for k in range(gz)]


def ring_order(coords: List[Tuple[int, ...]]) -> List[int]:
    """Indices of `coords` in a snake walk: consecutive entries are grid
    neighbors, so a ring mapped onto this order rides ICI links.

    Works for 2D and 3D host grids; falls back to lexicographic order for
    degenerate shapes.
    """
    if not coords:
        return []
    dims = len(coords[0])
    index_of = {c: i for i, c in enumerate(coords)}
    order: List[int] = []
    if dims == 2:
        xs = sorted({c[0] for c in coords})
        for xi, x in enumerate(xs):
            col = sorted([c for c in coords if c[0] == x], key=lambda c: c[1])
            if xi % 2:
                col.reverse()
            order.extend(index_of[c] for c in col)
    else:
        xs = sorted({c[0] for c in coords})
        for xi, x in enumerate(xs):
            plane = [c for c in coords if c[0] == x]
            ys = sorted({c[1] for c in plane})
            if xi % 2:
                ys.reverse()
            for yi, y in enumerate(ys):
                row = sorted([c for c in plane if c[1] == y], key=lambda c: c[2])
                if (xi + yi) % 2:
                    row.reverse()
                order.extend(index_of[c] for c in row)
    return order


@dataclass
class Placement:
    """Where a pod landed; env() is merged into its containers' environment."""

    node_name: str = ""
    slice_name: str = ""
    slice_type: str = ""
    topology: str = ""
    worker_id: int = 0
    num_workers: int = 1

    def env(self) -> Dict[str, str]:
        """The admitter's grant, under names libtpu does not read. A
        locally executed pod loads libtpu itself, and libtpu learns the
        attached chips from the host's own TPU_WORKER_ID / TPU_TOPOLOGY /
        TPU_WORKER_HOSTNAMES: the pool's slice is a scheduling fact, not
        a description of this host, and must not overwrite those. The
        worker's index is KUBEDL_PROCESS_ID; the grid follows from
        TPU_SLICE_TYPE (parse_slice_type)."""
        return {
            "TPU_SLICE_NAME": self.slice_name,
            "TPU_SLICE_TYPE": self.slice_type,
            "TPU_NUM_WORKERS": str(self.num_workers),
        }


def pipeline_neighbor_env(
    stage: int,
    num_stages: int,
    prev_addr: str = "",
    next_addr: str = "",
) -> Dict[str, str]:
    """Env wiring for one MPMD pipeline stage: which stage this slice's
    program is, and the coordinator addresses of its ring neighbors —
    stage s streams activations to `next` and activation-gradients back
    to `prev`, so each program only ever dials its two neighbors (the
    DCN topology of the MPMD pipeline paper: a chain, not an all-to-all
    Megascale mesh). Endpoint stages carry an empty addr on the missing
    side. The JAXJob controller fills the addrs from the neighbor stage
    worker-0 services (workloads/jaxjob.py set_cluster_spec)."""
    if not (0 <= stage < num_stages):
        raise ValueError(f"stage {stage} out of range [0, {num_stages})")
    return {
        "KUBEDL_PP_STAGE": str(stage),
        "KUBEDL_PP_STAGES": str(num_stages),
        "KUBEDL_PP_PREV_ADDR": prev_addr if stage > 0 else "",
        "KUBEDL_PP_NEXT_ADDR": next_addr if stage < num_stages - 1 else "",
    }


def rl_fleet_env(
    role: str,
    index: int,
    n_actors: int,
    learner_addr: str = "",
    actor_addrs: str = "",
    weight_fanout: int = 4,
    weight_chunk_bytes: int = 1 << 20,
) -> Dict[str, str]:
    """Env wiring for one RL-fleet pod: its role, which actor it is, and
    the transport addresses of its peers — actors dial ONLY the learner
    (trajectories), the learner dials every actor (weight broadcast); a
    hub-and-spoke, not a mesh (the Sebulba topology: PAPERS.md,
    Podracer). `index` is the pod's worker index; actors occupy
    [0, n_actors), so an actor's KUBEDL_RL_ACTOR_INDEX is its worker
    index and the learner carries -1. The JAXJob controller fills the
    addrs from the peer pods' worker services (workloads/jaxjob.py
    set_cluster_spec); the local executor's DirChannel lane ignores
    them and rides KUBEDL_RL_QUEUE_DIR.

    Fleets past ~2 actors distribute weights over the O(log n)
    broadcast tree instead of n learner dials (docs/weights.md);
    KUBEDL_WEIGHTS_FANOUT and KUBEDL_WEIGHTS_CHUNK_BYTES shape that
    tree and ride into every fleet pod so all nodes agree on it."""
    if role not in ("actor", "learner"):
        raise ValueError(f"RL role must be actor|learner, got {role!r}")
    if role == "actor" and not (0 <= index < n_actors):
        raise ValueError(
            f"actor index {index} out of range [0, {n_actors})")
    if weight_fanout < 1:
        raise ValueError(f"weight fanout must be >= 1, got {weight_fanout}")
    if weight_chunk_bytes < 1:
        raise ValueError(
            f"weight chunk bytes must be >= 1, got {weight_chunk_bytes}")
    return {
        "KUBEDL_RL_ROLE": role,
        "KUBEDL_RL_ACTORS": str(n_actors),
        "KUBEDL_RL_ACTOR_INDEX": str(index if role == "actor" else -1),
        "KUBEDL_RL_LEARNER_ADDR": learner_addr if role == "actor" else "",
        "KUBEDL_RL_ACTOR_ADDRS": actor_addrs if role == "learner" else "",
        "KUBEDL_WEIGHTS_FANOUT": str(weight_fanout),
        "KUBEDL_WEIGHTS_CHUNK_BYTES": str(weight_chunk_bytes),
    }


@dataclass
class SliceInfo:
    """One physical slice in the pool."""

    name: str
    type: SliceType
    reserved_by: Optional[str] = None  # gang key holding the whole slice

    @property
    def num_hosts(self) -> int:
        return self.type.num_hosts
