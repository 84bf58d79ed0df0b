"""Podracer actor/learner RL plane (docs/rl.md).

Runs RL post-training as a DISAGGREGATED fleet instead of the monolithic
rollout->update loop (train/grpo.py): actor pods generate groups of
completions on their own slices and emit them — with the behavior
log-probs that are free at sample time — as exactly-once trajectories;
a learner pod folds them into the sharded GRPO update and broadcasts
version-stamped weights back; both flows ride the PR 11 socket
transport plane (DirChannel on the local executor). The Sebulba split
of *Podracer architectures for scalable RL* (PAPERS.md), grown on this
repo's own parts:

  * wire.py        — named-array record codec (per-array dtype recorded,
                     raw-uint8 payload: the bf16/|V2 discipline)
  * trajectory.py  — Trajectory + producer/consumer over any channel
  * weights.py     — versioned weight broadcast + receiver
  * actor.py       — ActorRuntime: batched rollouts, reward scoring,
                     weight pulls at generation boundaries
  * learner.py     — LearnerRuntime: staleness-bounded GRPO updates,
                     weight publishing, checkpointing hooks
  * fleet.py       — in-process harness (threads + QueueChannels) for
                     tests
  * metrics.py     — kubedl_rl_* families (module singleton, the
                     pipeline_metrics pattern)

Orchestration is first-class: JAXJob ``spec.rl`` declares the fleet,
the gang admitter admits the actor gang and learner gang as ONE
all-or-nothing unit (mixed ROLES riding the PR 9 hetero-gang
machinery), and the pod entrypoints live in train/rl_pod.py.
"""
from kubedl_tpu.rl.metrics import rl_metrics
from kubedl_tpu.rl.trajectory import (
    TRAJECTORY_CHANNEL,
    Trajectory,
    TrajectoryConsumer,
    TrajectoryProducer,
    decode_trajectory,
    encode_trajectory,
)
from kubedl_tpu.rl.weights import (
    WEIGHT_CHANNEL,
    WeightBroadcaster,
    WeightReceiver,
    decode_weights,
    encode_weights,
)

__all__ = [
    "TRAJECTORY_CHANNEL",
    "WEIGHT_CHANNEL",
    "Trajectory",
    "TrajectoryConsumer",
    "TrajectoryProducer",
    "WeightBroadcaster",
    "WeightReceiver",
    "decode_trajectory",
    "decode_weights",
    "encode_trajectory",
    "encode_weights",
    "rl_metrics",
]
