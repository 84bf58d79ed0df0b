"""Podracer actor/learner RL plane (kubedl_tpu/rl/, ISSUE 13): wire
codec + trajectory/broadcast contracts, exactly-once delivery under
reconnect, staleness bound, behavior-logprob parity oracle, learner
parity vs the monolithic GRPO loop, mixed-role gang admission, metrics
families, and the two-process actor+learner e2e on the local executor."""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubedl_tpu.rl.metrics import rl_metrics
from kubedl_tpu.rl.trajectory import (
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
from kubedl_tpu.rl.wire import decode_arrays, encode_arrays


@pytest.fixture(autouse=True)
def _reset_rl_metrics():
    rl_metrics.reset()
    yield
    rl_metrics.reset()


@pytest.fixture(scope="module")
def model():
    from kubedl_tpu.models import llama

    config = llama.LlamaConfig.tiny(dtype=jnp.float32, use_flash=False)
    params = llama.init(config, jax.random.PRNGKey(0))
    return params, config


def _traj(g=2, t=8, pl=3, version=0, seed=0):
    rng = np.random.default_rng(seed)
    return Trajectory(
        tokens=rng.integers(1, 100, (g, t)).astype(np.int32),
        prompt_len=pl,
        seq_lens=np.full(g, t, np.int32),
        rewards=rng.normal(size=g).astype(np.float32),
        behavior_logprobs=rng.normal(size=(g, t - 1)).astype(np.float32),
        weight_version=version,
    )


# ---------------------------------------------------------------------------
# wire codec + trajectory record
# ---------------------------------------------------------------------------


def test_wire_roundtrip_mixed_dtypes_incl_bf16():
    """The RL record codec carries PER-ARRAY dtypes — int32 tokens next
    to f32 rewards next to bf16 weights in ONE message, every buffer
    byte-identical after the round trip (the |V2 npz lesson)."""
    import ml_dtypes

    arrays = [
        ("tokens", np.arange(12, dtype=np.int32).reshape(3, 4)),
        ("rewards", np.linspace(-1, 1, 3).astype(np.float32)),
        ("w", (np.arange(6, dtype=np.float32) / 3).astype(
            ml_dtypes.bfloat16).reshape(2, 3)),
    ]
    data = encode_arrays(arrays, meta={"v": 7})
    out, meta = decode_arrays(data)
    assert meta == {"v": 7}
    assert list(out) == ["tokens", "rewards", "w"]
    for name, a in arrays:
        assert out[name].dtype == a.dtype
        assert out[name].tobytes() == a.tobytes()
    # corrupt/truncated records refuse loudly — never a silent prefix
    with pytest.raises(ValueError, match="truncated"):
        decode_arrays(data[:-3])
    with pytest.raises(ValueError, match="trailing"):
        decode_arrays(data + b"x")
    with pytest.raises(ValueError, match="magic"):
        decode_arrays(b"nope" + data)
    with pytest.raises(ValueError, match="duplicate"):
        encode_arrays([("a", np.zeros(1)), ("a", np.zeros(1))])


def test_trajectory_roundtrip_and_shape_validation():
    traj = _traj(g=3, t=10, pl=4, version=5)
    traj.actor, traj.seq = "actor-1", 9
    back = decode_trajectory(encode_trajectory(traj))
    assert back.weight_version == 5 and back.actor == "actor-1"
    assert back.seq == 9 and back.prompt_len == 4
    np.testing.assert_array_equal(back.tokens, traj.tokens)
    np.testing.assert_array_equal(back.behavior_logprobs,
                                  traj.behavior_logprobs)
    with pytest.raises(ValueError, match="group mismatch"):
        Trajectory(tokens=np.zeros((2, 8), np.int32), prompt_len=3,
                   seq_lens=np.zeros(3, np.int32),
                   rewards=np.zeros(2, np.float32),
                   behavior_logprobs=np.zeros((2, 7), np.float32))
    with pytest.raises(ValueError, match=r"\[G, T-1\]"):
        Trajectory(tokens=np.zeros((2, 8), np.int32), prompt_len=3,
                   seq_lens=np.zeros(2, np.int32),
                   rewards=np.zeros(2, np.float32),
                   behavior_logprobs=np.zeros((2, 8), np.float32))


# ---------------------------------------------------------------------------
# delivery contracts over the socket plane
# ---------------------------------------------------------------------------


def _plane_pair():
    from kubedl_tpu.transport.plane import TransportPlane

    rx = TransportPlane(token="rl-test", service="learner")
    addr = rx.listen("127.0.0.1:0")
    tx = TransportPlane(token="rl-test", service="actor")
    return rx, tx, addr


def test_trajectory_exactly_once_under_reconnect_and_resend():
    """Deterministic tags + the plane's ACK/dedup = exactly-once: a
    duplicate resend (lost-ACK replay) is dropped, a dropped connection
    reconnects and the stream continues in per-actor order."""
    from kubedl_tpu.transport.metrics import transport_metrics

    transport_metrics.reset()
    rx, tx, addr = _plane_pair()
    try:
        ch = tx.channel("rl-traj.actor-0", peer_addr=addr)
        producer = TrajectoryProducer(ch, "actor-0", job="j")
        t1, t2, t3 = _traj(seed=1), _traj(seed=2), _traj(seed=3)
        producer.send(t1)
        # lost-ACK replay: resend tag 1's exact bytes — dedup, not dup
        tx.send(addr, "rl-traj.actor-0", "actor-0.00000001",
                encode_trajectory(t1))
        producer.send(t2)
        # connection drop mid-stream: the next send reconnects + resends
        peer = tx._peer(addr)
        with peer.lock:
            peer._drop()
        producer.send(t3)
        consumer = TrajectoryConsumer(
            {"actor-0": rx.channel("rl-traj.actor-0")}, job="j")
        got = [consumer.take(timeout=5.0) for _ in range(3)]
        assert [g.seq for g in got] == [1, 2, 3]
        np.testing.assert_array_equal(got[0].tokens, t1.tokens)
        assert consumer.take(timeout=0.2) is None  # the dup never lands
        assert rl_metrics.snapshot()["jobs"]["j"]["produced"] == 3
    finally:
        rx.close()
        tx.close()


def test_consumer_round_robin_and_per_actor_order():
    from kubedl_tpu.parallel.pipeline_mpmd import QueueChannel

    a, b = QueueChannel(), QueueChannel()
    pa = TrajectoryProducer(a, "actor-0", job="j")
    pb = TrajectoryProducer(b, "actor-1", job="j")
    for s in (1, 2):
        pa.send(_traj(seed=s))
        pb.send(_traj(seed=10 + s))
    consumer = TrajectoryConsumer({"actor-0": a, "actor-1": b}, job="j")
    got = [consumer.take(timeout=2.0) for _ in range(4)]
    # fair across actors, in-order within each actor
    assert sorted((g.actor, g.seq) for g in got) == [
        ("actor-0", 1), ("actor-0", 2), ("actor-1", 1), ("actor-1", 2)]
    per_actor = {}
    for g in got:
        per_actor.setdefault(g.actor, []).append(g.seq)
    assert all(v == sorted(v) for v in per_actor.values())


def test_weight_broadcast_bf16_byte_identical_over_socket():
    """A bf16 param tree crosses a REAL loopback socket hop
    byte-identically, and the receiver adopts only the NEWEST of several
    pending versions (decoding one payload, not all)."""
    import ml_dtypes

    params = {
        "embed": (np.arange(24, dtype=np.float32) / 7).astype(
            ml_dtypes.bfloat16).reshape(4, 6),
        "layers": [{"w": np.ones((2, 3), np.float32)},
                   {"w": np.full((2, 3), 0.5, np.float32)}],
    }
    rx, tx, addr = _plane_pair()
    try:
        caster = WeightBroadcaster(
            [tx.channel(WEIGHT_CHANNEL, peer_addr=addr)])
        caster.publish(params, step=1)
        params2 = jax.tree.map(lambda a: a * 2, params)
        caster.publish(params2, step=2)
        receiver = WeightReceiver(rx.channel(WEIGHT_CHANNEL))
        leaves, version, step = receiver.poll(timeout=5.0)
        assert (version, step) == (2, 2) and receiver.version == 2
        want = jax.tree_util.tree_leaves(params2)
        assert len(leaves) == len(want)
        for got, exp in zip(leaves, want):
            assert got.dtype == exp.dtype  # bf16 stays bf16
            assert got.tobytes() == np.asarray(exp).tobytes()
        assert receiver.poll(timeout=0.1) is None
    finally:
        rx.close()
        tx.close()


def test_weight_record_version_and_truncation_guards():
    with pytest.raises(ValueError, match="version"):
        encode_weights({"w": np.ones(2)}, 0)
    with pytest.raises(ValueError, match="empty"):
        encode_weights({}, 1)
    data = encode_weights({"w": np.ones(2)}, 3, step=7)
    leaves, v, s = decode_weights(data)
    assert v == 3 and s == 7 and len(leaves) == 1
    with pytest.raises(ValueError, match="truncated"):
        decode_weights(data[:-1])


# ---------------------------------------------------------------------------
# staleness bound
# ---------------------------------------------------------------------------


def test_stale_trajectories_dropped_and_counted(model):
    """The learner refuses trajectories staler than maxWeightLag weight
    versions — dropped AND counted, never silently trained on."""
    from kubedl_tpu.parallel.pipeline_mpmd import QueueChannel
    from kubedl_tpu.rl.learner import LearnerConfig, LearnerRuntime

    params, config = model
    traj_ch, weight_ch = QueueChannel(), QueueChannel()
    learner = LearnerRuntime(
        params, config,
        LearnerConfig(prompts_per_step=1, group_size=2, max_weight_lag=1,
                      take_timeout_s=10.0, job="stale-job"),
        consumer=TrajectoryConsumer({"actor-0": traj_ch}, job="stale-job"),
        broadcaster=WeightBroadcaster([weight_ch]),
    )
    # advance the learner to version 3 without running updates
    for step in (1, 2, 3):
        learner.broadcaster.publish(params, step)
    producer = TrajectoryProducer(traj_ch, "actor-0", job="stale-job")
    producer.send(_traj(version=0, seed=1))  # lag 3 > 1: stale
    producer.send(_traj(version=1, seed=2))  # lag 2 > 1: stale
    producer.send(_traj(version=2, seed=3))  # lag 1: fresh
    groups = learner._collect_batch()
    assert [t.weight_version for t in groups] == [2]
    assert learner.stats.stale_dropped == 2
    assert learner.stats.consumed == 1
    assert learner.stats.max_lag_observed == 1
    rec = rl_metrics.snapshot()["jobs"]["stale-job"]
    assert rec["stale_dropped"] == 2 and rec["consumed"] == 1
    assert rec["weight_lag"] == 1


# ---------------------------------------------------------------------------
# behavior-logprob parity oracle (the grpo.py satellite)
# ---------------------------------------------------------------------------


def test_generate_with_logprobs_matches_recompute_oracle(model):
    """decode.generate's sampling-time logprobs == the training
    forward's recompute (train/preference.sequence_logprobs) at every
    completion position — the recompute stays as the parity oracle; the
    fleet ships the free sampling-time capture instead."""
    from kubedl_tpu.models import decode
    from kubedl_tpu.train.preference import sequence_logprobs

    params, config = model
    B, P, K = 3, 6, 5
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, config.vocab_size, (B, P)).astype(np.int32)
    toks, lps = jax.jit(
        lambda p, t, k: decode.generate(
            p, t, config, K, temperature=1.0, key=k, with_logprobs=True)
    )(params, jnp.asarray(prompts), jax.random.PRNGKey(7))
    toks, lps = np.asarray(toks), np.asarray(lps)
    full = np.concatenate([prompts, toks], axis=1)
    (lp_grid, mask), _ = sequence_logprobs(
        params, jnp.asarray(full),
        jnp.full(B, P, np.int32), jnp.full(B, P + K, np.int32),
        config, with_aux=True, per_token=True)
    lp_grid = np.asarray(lp_grid)
    # completion token j's recompute sits at grid index P - 1 + j
    np.testing.assert_allclose(
        lp_grid[:, P - 1:P - 1 + K], lps, rtol=0, atol=1e-4)
    # greedy path still returns plain tokens (no logprobs) — API intact
    plain = decode.generate(params, jnp.asarray(prompts), config, K)
    assert np.asarray(plain).shape == (B, K)


# ---------------------------------------------------------------------------
# learner parity vs the monolithic GRPO loop
# ---------------------------------------------------------------------------


def _reward_token5(prompt_ids, completion_ids):
    if not completion_ids:
        return 0.0
    return sum(1 for t in completion_ids if t == 5) / len(completion_ids)


def test_learner_parity_vs_monolithic_grpo_loop(model):
    """Fixed seed, lockstep fleet (1 actor, maxWeightLag=0) vs the
    monolithic rollout->update loop running the SAME sampling-time-
    logprob discipline: identical prompt picks, identical rollouts,
    matching losses — the trajectory/broadcast hop adds nothing."""
    import optax

    from kubedl_tpu.models import decode
    from kubedl_tpu.parallel.mesh import build_mesh
    from kubedl_tpu.rl.actor import ActorConfig
    from kubedl_tpu.rl.fleet import RLFleet
    from kubedl_tpu.rl.learner import LearnerConfig
    from kubedl_tpu.train.rl import group_advantages, make_grpo_step

    params, config = model
    seed, B, G, P, K, steps = 0, 2, 2, 6, 4, 3
    lr, clip_eps, kl_coef = 1e-4, 0.2, 0.04
    rng = np.random.default_rng(seed)
    prompts = [list(rng.integers(1, config.vocab_size, P))
               for _ in range(8)]

    # -- monolith: grpo.py's loop with the sampling-time old_lp path ----
    mesh = build_mesh({"data": 4, "tensor": 2})  # B*G = 4 rows
    tx = optax.chain(optax.clip_by_global_norm(1.0),
                     optax.adamw(lr, weight_decay=0.0))
    init_state, _, ref_fn, step = make_grpo_step(
        params, config, tx, mesh, clip_eps=clip_eps, kl_coef=kl_coef,
        use_old_logprobs=True)
    state = init_state(jax.tree.map(jnp.asarray, params))
    roll = jax.jit(lambda p, t, k: decode.generate(
        p, t, config, K, temperature=1.0, key=k, with_logprobs=True))
    base_key = jax.random.PRNGKey(seed)
    pad_to = P
    mono_losses = []
    for it in range(1, steps + 1):
        it_rng = np.random.default_rng((seed, it))
        pick = it_rng.choice(len(prompts), size=B,
                             replace=len(prompts) < B)
        toks = np.array([prompts[i] for i in pick], np.int32)
        tiled = np.repeat(toks, G, axis=0)
        comp, lps = roll(state.params, jnp.asarray(tiled),
                         jax.random.fold_in(base_key, it))
        comp, lps = np.asarray(comp), np.asarray(lps)
        n = B * G
        full = np.concatenate([tiled, comp], axis=1)
        seq_lens = np.full(n, pad_to + K, np.int32)
        plens = np.full(n, pad_to, np.int32)
        rewards = np.array([_reward_token5(list(tiled[i]), list(comp[i]))
                            for i in range(n)], np.float32)
        grid = np.zeros((n, pad_to + K - 1), np.float32)
        grid[:, pad_to - 1:pad_to - 1 + K] = lps
        adv = np.asarray(group_advantages(
            jnp.asarray(rewards.reshape(B, G)))).reshape(n)
        batch = (jnp.asarray(full), jnp.asarray(plens),
                 jnp.asarray(seq_lens))
        ref_lp = ref_fn(batch)
        state, metrics = step(
            state, (*batch, jnp.asarray(adv), jnp.asarray(grid), ref_lp))
        mono_losses.append(float(metrics["loss"]))

    # -- fleet: same seed, lockstep, behavior logprobs from the wire ----
    fleet = RLFleet(
        params, config, prompts, _reward_token5,
        ActorConfig(seed=seed, group_size=G, prompts_per_step=B,
                    max_new_tokens=K, temperature=1.0, max_weight_lag=0,
                    lockstep=True),
        LearnerConfig(prompts_per_step=B, group_size=G, max_weight_lag=0,
                      lr=lr, clip_eps=clip_eps, kl_coef=kl_coef,
                      take_timeout_s=120.0),
        n_actors=1, mesh=mesh)
    fleet_losses = []
    fleet.run(steps, on_step=lambda s, m: fleet_losses.append(m["loss"]))
    stats = fleet.learner.stats
    assert stats.stale_dropped == 0
    assert stats.max_lag_observed == 0  # lockstep IS strictly on-policy
    np.testing.assert_allclose(fleet_losses, mono_losses,
                               rtol=0, atol=1e-5)
    # the updated policies match too, not just the scalar losses. Same
    # tolerance as the losses: the fleet's learner jits its step over a
    # mesh and the monolithic loop does not, so XLA's CPU backend sums
    # in another order and a few of 32,768 parameters differ by ~3e-6
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(fleet.learner.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# serving-plane rollout mode
# ---------------------------------------------------------------------------


def test_serving_rollout_engine_groups_and_logprob_oracle(model):
    """The paged-KV serving plane as a rollout engine: G samples per
    prompt with behavior logprobs matching the training-forward oracle;
    swap_params refuses mid-flight version mixes."""
    from kubedl_tpu.serving.rollout import RolloutEngine
    from kubedl_tpu.train.preference import sequence_logprobs

    params, config = model
    G, K = 2, 4
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(1, config.vocab_size, 6)),
               list(rng.integers(1, config.vocab_size, 6))]
    engine = RolloutEngine(params, config, slots=4, max_len=32,
                           temperature=1.0, seed=0)
    waves = engine.rollout(prompts, G, K)
    assert len(waves) == 2 and all(len(g) == G for g in waves)
    for p, grp in zip(prompts, waves):
        for toks, lps in grp:
            assert 0 < len(toks) <= K and len(lps) == len(toks)
            full = np.array([p + toks], np.int32)
            (grid, _), _ = sequence_logprobs(
                params, jnp.asarray(full),
                jnp.asarray([len(p)], np.int32),
                jnp.asarray([len(p) + len(toks)], np.int32),
                config, with_aux=True, per_token=True)
            np.testing.assert_allclose(
                np.asarray(grid)[0, len(p) - 1:len(p) - 1 + len(toks)],
                lps, rtol=0, atol=1e-4)
    # generation boundary: swapping params is one attribute write
    engine.swap_params(jax.tree.map(lambda a: a, params))
    with pytest.raises(ValueError, match="temperature"):
        RolloutEngine(params, config, temperature=0.0)
    with pytest.raises(ValueError, match="group_size"):
        engine.rollout(prompts, 1, K)


# ---------------------------------------------------------------------------
# mixed-role gang admission (the stageSlices machinery, extended to roles)
# ---------------------------------------------------------------------------


def _rl_job(name, actor_slice, learner_slice, actors=2, tenant=""):
    from test_capacity_scheduler import ANNOTATION_TENANCY

    from kubedl_tpu.utils.serde import from_dict
    from kubedl_tpu.workloads.jaxjob import JAXJob

    ns = actors + 1
    manifest = {
        "metadata": {"name": name, "namespace": "default"},
        "spec": {
            "jaxReplicaSpecs": {"Worker": {"replicas": ns, "template": {
                "spec": {"containers": [{
                    "name": "jax", "image": "x",
                    "resources": {"limits": {"google.com/tpu": "4"}}}]}}}},
            "numSlices": ns,
            "rl": {"actorReplicas": actors, "learnerReplicas": 1,
                   "groupSize": 4, "actorSlice": actor_slice,
                   "learnerSlice": learner_slice},
            "checkpoint": {"path": "/ckpt"},
        }}
    job = from_dict(JAXJob, manifest)
    if tenant:
        job.metadata.annotations[ANNOTATION_TENANCY] = json.dumps(
            {"tenant": tenant})
    return job


def test_mixed_role_gang_admits_actors_then_learner():
    from test_capacity_scheduler import _setup

    adm, _ = _setup(["v5e-16", "v5e-8", "v5e-8"], policy="gavel")
    job = _rl_job("fleet", "v5e-8", "v5e-16", actors=2)
    st = adm.create_gang(job, job.spec.replica_specs)
    assert len(st.slice_names) == 3
    # slice_names[i] is pod i's slice (actors first): actors on the
    # 8-chip slices, the learner on the 16
    assert st.slice_names[0].endswith("v5e-8")
    assert st.slice_names[1].endswith("v5e-8")
    assert st.slice_names[2].endswith("v5e-16")
    snap = [g for g in adm.gang_snapshots() if g.key == "default/fleet"][0]
    assert snap.roles == ["actor", "actor", "learner"]
    assert snap.stage_slices == ["v5e-8", "v5e-8", "v5e-16"]


def test_mixed_role_gang_never_partial():
    """An actor fleet without a learner slice reserves NOTHING — and
    vice versa: all-or-nothing holds across the ROLE boundary."""
    from test_capacity_scheduler import _job, _reserved, _setup

    adm, _ = _setup(["v5e-16", "v5e-8", "v5e-8"], policy="gavel")
    big = _job("big", chips=16, tpu_slice="v5e-16")
    adm.create_gang(big, big.spec.replica_specs)
    assert _reserved(adm, "big")  # the learner's shape is taken
    fleet = _rl_job("fleet", "v5e-8", "v5e-16", actors=2)
    st = adm.create_gang(fleet, fleet.spec.replica_specs)
    assert st.slice_names == []
    free = [s for s in adm.utilization()["slices"] if not s["reserved_by"]]
    assert sorted(s["type"] for s in free) == ["v5e-8", "v5e-8"], (
        "a learner-less actor fleet must not take partial slices")
    # the learner shape frees -> the whole mixed-role gang admits
    adm.delete_gang(big)
    adm.kick()
    st = adm.get_gang("default", "fleet")
    assert len(st.slice_names) == 3


def test_mixed_role_gang_infeasible_never_wedges():
    from test_capacity_scheduler import _job, _reserved, _setup

    adm, _ = _setup(["v5e-8", "v5e-8"], policy="gavel")
    fleet = _rl_job("fleet", "v5e-8", "v5p-8", actors=1)  # no v5p at all
    st = adm.create_gang(fleet, fleet.spec.replica_specs)
    assert st.slice_names == []
    other = _job("other", chips=8, tpu_slice="v5e-8")
    adm.create_gang(other, other.spec.replica_specs)
    assert _reserved(adm, "other"), (
        "an infeasible mixed-role gang must not shield the pool")


def test_mixed_role_gang_respects_tenant_cap_sum():
    from test_capacity_scheduler import _setup

    adm, _ = _setup(["v5e-16", "v5e-8", "v5e-8"], policy="gavel",
                    tenant_caps={"t1": 24})  # sum needs 8+8+16 = 32
    fleet = _rl_job("fleet", "v5e-8", "v5e-16", actors=2, tenant="t1")
    st = adm.create_gang(fleet, fleet.spec.replica_specs)
    assert st.slice_names == []


# ---------------------------------------------------------------------------
# spec.rl validation + env wiring
# ---------------------------------------------------------------------------


def _rl_manifest(**rl_over):
    rl = {"actorReplicas": 2, "learnerReplicas": 1, "groupSize": 4,
          "maxWeightLag": 1, **rl_over}
    workers = rl["actorReplicas"] + rl["learnerReplicas"]
    return {
        "apiVersion": "kubedl-tpu.io/v1alpha1",
        "kind": "JAXJob",
        "metadata": {"name": "rl-validate"},
        "spec": {
            "jaxReplicaSpecs": {"Worker": {"replicas": workers, "template": {
                "spec": {"containers": [{"name": "jax", "image": "x"}]}}}},
            "rl": rl,
            "checkpoint": {"path": "/ckpt"},
        },
    }


def test_rl_spec_validation_matrix():
    from kubedl_tpu.api.validation import ValidationError, validate
    from kubedl_tpu.utils.serde import from_dict
    from kubedl_tpu.workloads.jaxjob import JAXJob, JAXJobController

    ctrl = JAXJobController()

    def check(manifest, match=None):
        job = from_dict(JAXJob, manifest)
        job.kind = "JAXJob"
        if match is None:
            validate(job, ctrl)
            return job
        with pytest.raises(ValidationError, match=match):
            validate(job, ctrl)

    check(_rl_manifest())  # the baseline is valid
    check(_rl_manifest(groupSize=1), match="groupSize")
    check(_rl_manifest(learnerReplicas=2, actorReplicas=1),
          match="learnerReplicas")
    check(_rl_manifest(maxWeightLag=-1), match="maxWeightLag")
    check(_rl_manifest(temperature=0.0), match="temperature")
    check(_rl_manifest(reward="nope"), match="reward")
    check(_rl_manifest(reward="length"), match="eosId")
    check(_rl_manifest(reward="length", eosId=2))  # valid with a stop id
    check(_rl_manifest(rolloutEngine="vllm"), match="rolloutEngine")
    # fleet-deadlock guard: past actorReplicas * (maxWeightLag + 1) the
    # actors' parking guard stops the trajectory supply before the
    # learner can reach the next publish
    check(_rl_manifest(broadcastInterval=5), match="broadcastInterval")
    check(_rl_manifest(broadcastInterval=4))  # == 2 * (1+1): still fine
    from kubedl_tpu.api.validation import validate_rl_shapes

    assert any("deadlock" in e for e in validate_rl_shapes(
        1, 1, 4, 0, broadcast_interval=2))
    check(_rl_manifest(actorSlice="v5e-8"), match="together")
    bad = _rl_manifest()
    bad["spec"]["jaxReplicaSpecs"]["Worker"]["replicas"] = 5
    check(bad, match="Worker replica count")
    slices = _rl_manifest(actorSlice="v5e-8", learnerSlice="v5e-16")
    check(slices, match="numSlices")  # role slices demand one pod/slice
    slices["spec"]["numSlices"] = 3
    check(slices)  # valid mixed-role gang
    combo = _rl_manifest()
    combo["spec"]["serving"] = {"prefillReplicas": 1, "decodeReplicas": 1}
    check(combo, match="spec.serving")
    combo = _rl_manifest()
    combo["spec"]["pipeline"] = {"stages": 2, "microbatches": 4}
    check(combo, match="spec.pipeline")
    nockpt = _rl_manifest()
    del nockpt["spec"]["checkpoint"]
    check(nockpt, match="spec.checkpoint")


def test_rl_env_wiring_roles_and_channels():
    """set_cluster_spec: roles by index (actors first), hub-and-spoke
    addresses, the queue dir on the checkpoint volume, NO Megascale env
    for the multi-slice fleet, and the rl-role label."""
    from kubedl_tpu.api.common import LABEL_RL_ROLE, LABEL_SLICE_ID
    from kubedl_tpu.api.pod import PodTemplateSpec
    from kubedl_tpu.utils.serde import from_dict
    from kubedl_tpu.workloads.jaxjob import JAXJob, JAXJobController

    manifest = _rl_manifest(actorSlice="v5e-8", learnerSlice="v5e-16")
    manifest["spec"]["numSlices"] = 3
    manifest["metadata"]["uid"] = "abc-123"
    job = from_dict(JAXJob, manifest)
    ctrl = JAXJobController()
    ctrl.set_defaults(job)

    def env_for(index):
        tpl = from_dict(PodTemplateSpec, {
            "spec": {"containers": [{"name": "jax", "image": "x"}]}})
        ctrl.set_cluster_spec(job, tpl, "Worker", index)
        return dict(tpl.spec.containers[0].env), tpl.metadata.labels

    env0, labels0 = env_for(0)
    env2, labels2 = env_for(2)
    assert env0["KUBEDL_RL_ROLE"] == "actor"
    assert env0["KUBEDL_RL_ACTOR_INDEX"] == "0"
    assert env0["KUBEDL_RL_LEARNER_ADDR"].endswith(":8478")
    assert labels0[LABEL_RL_ROLE] == "actor"
    assert labels0[LABEL_SLICE_ID] == "0"
    assert env2["KUBEDL_RL_ROLE"] == "learner"
    assert env2["KUBEDL_RL_ACTOR_INDEX"] == "-1"
    assert len(env2["KUBEDL_RL_ACTOR_ADDRS"].split(",")) == 2
    assert labels2[LABEL_RL_ROLE] == "learner"
    assert env2["KUBEDL_RL_QUEUE_DIR"] == "/ckpt/.rl"
    assert env2["KUBEDL_RL_GROUP_SIZE"] == "4"
    assert env2["KUBEDL_TRANSPORT_BIND"] == "0.0.0.0:8478"
    assert env2["KUBEDL_TRANSPORT_TOKEN"] == env0["KUBEDL_TRANSPORT_TOKEN"]
    # separate programs: Megascale must NOT be injected for the fleet
    assert "MEGASCALE_COORDINATOR_ADDRESS" not in env0
    assert "KUBEDL_DCN_MESH" not in env0


# ---------------------------------------------------------------------------
# metrics + goodput evidence
# ---------------------------------------------------------------------------


def test_rl_metrics_families_render():
    from kubedl_tpu.metrics.runtime_metrics import RuntimeMetrics

    rl_metrics.on_produced('ns/j"1')
    rl_metrics.on_produced('ns/j"1')
    rl_metrics.on_consumed('ns/j"1', weight_lag=1)
    rl_metrics.on_stale_dropped('ns/j"1', weight_lag=3)
    rm = RuntimeMetrics()
    rm.register_rl(rl_metrics.snapshot)
    text = rm.render()
    assert 'kubedl_rl_trajectory_queue_depth{job="ns/j\\"1"} 0' in text
    assert 'kubedl_rl_weight_lag_steps{job="ns/j\\"1"} 3' in text
    assert 'kubedl_rl_trajectories_produced_total{job="ns/j\\"1"} 2' in text
    assert 'kubedl_rl_trajectories_consumed_total{job="ns/j\\"1"} 1' in text
    assert ('kubedl_rl_trajectories_stale_dropped_total{job="ns/j\\"1"} 1'
            in text)
    assert rm.debug_vars()["rl"]["jobs"]


def test_top_renders_rl_table(capsys):
    """`kubedl-tpu top` grows the RL table (and the goodput table grows
    the starvation columns only when an RL job reports)."""
    from kubedl_tpu.cli import main as cli_main
    from kubedl_tpu.operator import Operator, OperatorConfig
    from kubedl_tpu.server import OperatorHTTPServer

    op = Operator(OperatorConfig())
    op.register_all()
    op.start()
    srv = OperatorHTTPServer(op, port=0)
    port = srv.start()
    try:
        rl_metrics.on_produced("default/fleet")
        rl_metrics.on_produced("default/fleet")
        rl_metrics.on_consumed("default/fleet", weight_lag=1)
        rc = cli_main(["top", "--server", f"http://127.0.0.1:{port}"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "RL_JOB" in out and "default/fleet" in out
        assert "STALE_DROP" in out and "WLAG" in out
    finally:
        srv.stop()
        op.stop()


def test_goodput_starved_buckets_distinguishable():
    """The coupling-claim evidence: actor-starved and learner-starved
    time land in SEPARATE buckets, rollout/learn/weight_sync classify,
    and the partition still sums to wall exactly."""
    from kubedl_tpu.obs.goodput import classify, goodput

    def mk(name, ts, dur, **attrs):
        return {"name": name, "ts": ts, "dur": dur, "attrs": attrs,
                "trace_id": "t"}

    assert classify(mk("rl.rollout", 0, 1)) == "rollout"
    assert classify(mk("rl.learn", 0, 1)) == "steps"
    assert classify(mk("rl.weight_sync", 0, 1)) == "weight_sync"
    assert classify(mk("rl.idle", 0, 1, cause="actor_starved")) == \
        "actor_starved"
    assert classify(mk("rl.idle", 0, 1, cause="learner_starved")) == \
        "learner_starved"
    assert classify(mk("rl.idle", 0, 1)) is None
    spans = [
        mk("rl.rollout", 0.0, 2.0),                       # actor plane
        mk("rl.idle", 0.5, 1.0, cause="actor_starved"),   # learner waits
        mk("rl.learn", 2.0, 1.0),
        mk("rl.idle", 2.0, 0.5, cause="learner_starved"),  # actor waits
        mk("rl.weight_sync", 3.0, 0.5),
    ]
    gp = goodput(spans)
    b = gp["buckets"]
    # starvation OUTRANKS the concurrent productive plane (that is the
    # evidence: starving-while-the-other-side-works = the bottleneck)
    assert b["actor_starved"] == pytest.approx(1.0)
    assert b["learner_starved"] == pytest.approx(0.5)
    assert b["rollout"] == pytest.approx(1.0)  # 2.0 minus the overlaps
    assert b["steps"] == pytest.approx(0.5)
    assert b["weight_sync"] == pytest.approx(0.5)
    assert sum(b.values()) == pytest.approx(gp["wall_s"], abs=1e-9)


# ---------------------------------------------------------------------------
# two-process actor+learner e2e on the local executor
# ---------------------------------------------------------------------------


def test_two_process_actor_learner_e2e_one_trace_id(tmp_path):
    """The acceptance path: a JAXJob spec.rl fleet runs as TWO real
    processes on the local executor, trajectories flow exactly-once over
    the channel plane, the learner's lag stays within maxWeightLag, and
    BOTH processes' rl.* spans land on ONE flight-recorder timeline."""
    from conftest import CPU_ENV

    from kubedl_tpu.obs import load_spans
    from kubedl_tpu.obs.goodput import goodput
    from kubedl_tpu.obs.trace import job_trace_dir, trace_id_for
    from kubedl_tpu.operator import Operator, OperatorConfig
    from kubedl_tpu.workloads.jaxjob import JAXJobController

    ckpt = str(tmp_path / "ckpt")
    trace_root = str(tmp_path / "trace")
    # the chaos/e2e lanes run with the runtime lock witness ON
    # (docs/static_analysis.md): each pod process records its real lock
    # acquisition orders and the fleet must complete inversion-free
    witness_dir = str(tmp_path / "witness")
    pod_env = {**CPU_ENV, "KUBEDL_LOCK_WITNESS": "1",
               "KUBEDL_LOCK_WITNESS_DIR": witness_dir}
    op = Operator(OperatorConfig(trace_dir=trace_root))
    op.register(JAXJobController())
    op.start()
    try:
        steps, B, G, K = 2, 2, 2, 4
        job = op.apply({
            "apiVersion": "kubedl-tpu.io/v1alpha1",
            "kind": "JAXJob",
            "metadata": {"name": "rl-e2e"},
            "spec": {
                "rl": {"actorReplicas": 1, "learnerReplicas": 1,
                       "groupSize": G, "promptsPerStep": B,
                       "maxNewTokens": K, "maxWeightLag": 0,
                       "broadcastInterval": 1},
                "checkpoint": {"path": ckpt, "saveIntervalSteps": 0},
                "jaxReplicaSpecs": {"Worker": {
                    "replicas": 2,
                    "restartPolicy": "ExitCode",
                    "template": {"spec": {"containers": [{
                        "name": "jax",
                        "env": pod_env,
                        "command": [
                            sys.executable, "-m", "kubedl_tpu.train.rl_pod",
                            "--model", "tiny", "--steps", str(steps),
                            "--lr", "1e-4", "--log-every", "1",
                        ],
                    }]}},
                }},
            },
        })
        assert op.wait_for_condition(job, "Succeeded", timeout=150), (
            "fleet did not complete; learner log:\n"
            + op.executor.read_logs("default", "rl-e2e-worker-1", tail=40)
            + "\nactor log:\n"
            + op.executor.read_logs("default", "rl-e2e-worker-0", tail=40))
        actor_log = op.executor.read_logs("default", "rl-e2e-worker-0")
        learner_log = op.executor.read_logs("default", "rl-e2e-worker-1")
        # exactly-once: every produced group was consumed, none stale
        assert f"consumed={steps * B} stale_dropped=0" in learner_log
        # the staleness bound held end to end
        assert "max_weight_lag_observed=0" in learner_log
        assert "actor-0: done" in actor_log
        # ONE timeline: both processes exported under the gang trace id
        spans = load_spans(job_trace_dir(trace_root, "default", "rl-e2e"))
        rl_spans = [s for s in spans if s["name"].startswith("rl.")]
        services = {s["service"] for s in rl_spans}
        assert {"rl-e2e-worker-0", "rl-e2e-worker-1"} <= services, services
        assert {s["trace_id"] for s in rl_spans} == {
            trace_id_for("default", "rl-e2e")}
        names = {s["name"] for s in rl_spans}
        assert {"rl.rollout", "rl.learn", "rl.weight_sync"} <= names
        # the goodput fold of the SAME spans shows the starvation split
        gp = goodput(spans)
        assert gp["buckets"]["rollout"] > 0
        assert gp["buckets"]["steps"] > 0
        # both pod processes exited cleanly -> both exported a witness
        # report; the disaggregated fleet ran with zero lock inversions
        reports = [f for f in os.listdir(witness_dir)
                   if f.startswith("witness-")]
        assert len(reports) >= 2, reports
        for name in reports:
            with open(os.path.join(witness_dir, name)) as f:
                data = json.load(f)
            assert data["inversions"] == [], data
    finally:
        op.stop()


def test_dir_lane_purges_stale_incarnation_messages(tmp_path):
    """The queue dir rides the PERSISTENT checkpoint volume: after a
    whole-gang restart, each side purges every dir it RECEIVES on, so a
    crashed incarnation's leftover trajectories/weights can never be
    consumed as current data (tags restart from 1). Send dirs are left
    alone — purging a peer's inbox is the peer's job."""
    from kubedl_tpu.train.rl_pod import channels_from_env

    root = tmp_path / "q"
    for d in ("traj-actor-0", "weights-actor-0"):
        (root / d).mkdir(parents=True)
    (root / "traj-actor-0" / "actor-0.00000001.msg").write_bytes(b"stale")
    (root / "weights-actor-0" / "w.00000001.msg").write_bytes(b"stale")
    env = {"KUBEDL_RL_QUEUE_DIR": str(root)}
    channels_from_env("learner", ["actor-0"], env=env)
    assert not list((root / "traj-actor-0").glob("*.msg"))  # learner recv
    assert list((root / "weights-actor-0").glob("*.msg"))   # not its inbox
    channels_from_env("actor", ["actor-0"], env=env)
    assert not list((root / "weights-actor-0").glob("*.msg"))  # actor recv


def test_rl_pod_refuses_roleless_invocation(monkeypatch):
    from kubedl_tpu.train import rl_pod

    monkeypatch.delenv("KUBEDL_RL_ROLE", raising=False)
    assert rl_pod.main([]) == 2  # permanent config error, not a crash
