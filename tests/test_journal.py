"""Durable control plane (docs/ha.md): write-ahead grant/drain journal
round trips, torn-tail/sha/epoch refusal semantics, crash replay through
TPUSliceAdmitter.restore_from_journal, and the fleet history store that
keeps answering after the CRD and the trace dir are both gone."""
import json
import os
import shutil
import sys
import time
import types
import urllib.request

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from kubedl_tpu.api.common import ReplicaSpec, RunPolicy, SchedulingPolicy
from kubedl_tpu.api.job import BaseJob, BaseJobSpec
from kubedl_tpu.api.meta import ObjectMeta
from kubedl_tpu.api.pod import (
    Container,
    PodSpec,
    PodTemplateSpec,
    ResourceRequirements,
)
from kubedl_tpu.core.leader import FileLeaseElector, read_epoch
from kubedl_tpu.core.store import ObjectStore
from kubedl_tpu.gang.slice_admitter import TPUSliceAdmitter
from kubedl_tpu.journal import (
    GrantJournal,
    HistoryStore,
    JournalError,
    StaleEpochError,
)
from kubedl_tpu.journal.wal import _sha

from fake_workload import TEST_KIND, TestJobController


# ---------------------------------------------------------------------------
# GrantJournal: append/replay mechanics
# ---------------------------------------------------------------------------


def _jpath(tmp_path):
    return str(tmp_path / "grant.journal")


def test_append_reopen_roundtrip(tmp_path):
    j = GrantJournal(_jpath(tmp_path))
    assert j.open() == []  # cold start
    j.append("grant", gang="default/a", slices=["s0"], state={"tpu_chips": 8})
    j.append("pods_start", gang="default/a", pod="default/p0", slice="s0")
    assert j.appends_total == 2
    j.close()

    j2 = GrantJournal(_jpath(tmp_path))
    records = j2.open()
    assert [r["op"] for r in records] == ["grant", "pods_start"]
    assert records[0]["data"]["slices"] == ["s0"]
    assert [r["seq"] for r in records] == [1, 2]
    # seq continues past the replayed tail — no reuse after restart
    rec = j2.append("delete_gang", gang="default/a", slices=["s0"])
    assert rec["seq"] == 3
    j2.close()


def test_torn_tail_is_skipped_and_append_continues(tmp_path):
    j = GrantJournal(_jpath(tmp_path))
    j.open()
    j.append("grant", gang="default/a", slices=["s0"], state={})
    j.close()
    with open(_jpath(tmp_path), "a", encoding="utf-8") as f:
        f.write('{"v": 1, "seq": 2, "op": "pods_st')  # crash mid-write

    j2 = GrantJournal(_jpath(tmp_path))
    records = j2.open()
    assert len(records) == 1 and records[0]["op"] == "grant"
    j2.append("delete_gang", gang="default/a")  # file still appendable
    j2.close()


def test_bad_sha_stops_replay(tmp_path):
    j = GrantJournal(_jpath(tmp_path))
    j.open()
    j.append("grant", gang="default/a", slices=["s0"], state={})
    j.append("grant", gang="default/b", slices=["s1"], state={})
    j.close()
    lines = open(_jpath(tmp_path)).read().splitlines()
    tampered = json.loads(lines[1])
    tampered["gang"] = "default/evil"  # flip a field, keep the old sha
    with open(_jpath(tmp_path), "w", encoding="utf-8") as f:
        f.write(lines[0] + "\n" + json.dumps(tampered, sort_keys=True) + "\n")

    records = GrantJournal(_jpath(tmp_path)).open()
    assert len(records) == 1 and records[0]["gang"] == "default/a"


def test_unknown_op_refused_at_append_and_replay(tmp_path):
    j = GrantJournal(_jpath(tmp_path))
    j.open()
    with pytest.raises(JournalError, match="unknown journal op"):
        j.append("frobnicate", gang="default/a")
    j.append("grant", gang="default/a", slices=["s0"], state={})
    j.close()
    # a validly-sha'd record with a foreign op (schema drift) must stop
    # replay, not be silently skipped
    drift = {"v": 1, "seq": 2, "epoch": 0, "t": 0.0, "op": "weird",
             "gang": "default/a", "data": {}}
    drift["sha"] = _sha(drift)
    with open(_jpath(tmp_path), "a", encoding="utf-8") as f:
        f.write(json.dumps(drift, sort_keys=True) + "\n")
        f.write(json.dumps(drift, sort_keys=True) + "\n")
    records = GrantJournal(_jpath(tmp_path)).open()
    assert [r["op"] for r in records] == ["grant"]


# ---------------------------------------------------------------------------
# fencing epochs
# ---------------------------------------------------------------------------


def test_open_refuses_file_written_by_newer_epoch(tmp_path):
    j = GrantJournal(_jpath(tmp_path), epoch=2)
    j.open()
    j.append("grant", gang="default/a", slices=["s0"], state={})
    j.close()
    stale = GrantJournal(_jpath(tmp_path), epoch=1)
    with pytest.raises(StaleEpochError, match="epoch 2"):
        stale.open()
    # epoch 0 = unfenced reader (tests, offline inspection) still works
    assert len(GrantJournal(_jpath(tmp_path)).open()) == 1


def test_append_refused_when_authority_shows_newer_leader(tmp_path, caplog):
    box = {"epoch": 1}
    j = GrantJournal(_jpath(tmp_path), epoch=1,
                     epoch_authority=lambda: box["epoch"])
    j.open()
    j.append("grant", gang="default/a", slices=["s0"], state={})
    box["epoch"] = 2  # a newer leader took the lease
    with caplog.at_level("ERROR"):
        with pytest.raises(StaleEpochError, match="superseded by 2"):
            j.append("delete_gang", gang="default/a")
    assert any("APPEND REFUSED" in r.message for r in caplog.records)
    assert j.stale_epoch_refusals == 1
    assert j.snapshot()["stale_epoch_refusals_total"] == 1
    # the refused record never reached disk
    assert len(open(_jpath(tmp_path)).read().splitlines()) == 1
    j.close()


def test_deposed_elector_journal_is_fenced(tmp_path, caplog):
    """The real handover: elector A acquires (epoch 1), its journal
    fences on read_epoch; A releases, B acquires (epoch 2) — A's
    journal refuses further appends loudly."""
    lease = str(tmp_path / "leader.lock")
    a = FileLeaseElector(lease_path=lease, identity="op-a")
    assert a.try_acquire() and a.epoch == 1
    ja = GrantJournal(_jpath(tmp_path), epoch=a.epoch,
                      epoch_authority=lambda: read_epoch(lease))
    ja.open()
    ja.append("grant", gang="default/a", slices=["s0"], state={})

    a.release()  # GC pause / partition: A *thinks* it is still leader
    b = FileLeaseElector(lease_path=lease, identity="op-b")
    assert b.try_acquire() and b.epoch == 2
    with caplog.at_level("ERROR"):
        with pytest.raises(StaleEpochError):
            ja.append("delete_gang", gang="default/a")
    assert any("APPEND REFUSED" in r.message for r in caplog.records)
    ja.close()
    b.release()
    # B's journal opens at the new epoch over A's records just fine
    jb = GrantJournal(_jpath(tmp_path), epoch=2,
                      epoch_authority=lambda: read_epoch(lease))
    assert len(jb.open()) == 1
    jb.close()


# ---------------------------------------------------------------------------
# crash replay through the admitter
# ---------------------------------------------------------------------------


def _job(name, chips=8, priority=0):
    tmpl = PodTemplateSpec(spec=PodSpec(containers=[
        Container(name="c", resources=ResourceRequirements(
            limits={"google.com/tpu": chips}))
    ]))
    return BaseJob(
        metadata=ObjectMeta(name=name, namespace="default"),
        spec=BaseJobSpec(
            replica_specs={"Worker": ReplicaSpec(replicas=1, template=tmpl)},
            run_policy=RunPolicy(
                scheduling_policy=SchedulingPolicy(priority=priority)),
        ),
        kind="TestJob",
    )


def _meta(chips=8, slice_type="v5e-8"):
    return {"min_member": 1, "tpu_chips": chips,
            "requested_slice": slice_type, "num_slices": 1,
            "total_member": 1, "priority": 0, "kind": "TestJob",
            "tenant": "default", "admissible_slices": [slice_type],
            "stage_slices": [], "roles": [], "live_reshard": False,
            "quiesce_s": 0.0}


def _restored(tmp_path, pool=("v5e-8", "v5e-8")):
    adm = TPUSliceAdmitter.with_pool(ObjectStore(), list(pool))
    stats = adm.restore_from_journal(GrantJournal(_jpath(tmp_path)))
    return adm, stats


def test_restore_grant_roundtrip(tmp_path):
    """A live grant journaled by one admitter is rebuilt by a fresh one:
    same slice, same reservation, meta round-tripped — the crash window
    the protocol model's journaled-restart machine proves safe."""
    adm1 = TPUSliceAdmitter.with_pool(ObjectStore(), ["v5e-8", "v5e-8"])
    j = GrantJournal(_jpath(tmp_path))
    j.open()
    adm1.attach_journal(j)
    job = _job("a")
    gang = adm1.create_gang(job, job.spec.replica_specs)
    assert gang.slice_name
    j.close()

    adm2, stats = _restored(tmp_path)
    assert stats == {"records": 1, "conflicts": 0, "gangs": 1}
    restored = adm2.get_gang("default", "a")
    assert restored.slice_name == gang.slice_name
    assert restored.tpu_chips == 8  # meta survived the round trip
    util = adm2.utilization()
    assert util["chips_reserved"] == 8
    owners = {s["name"]: s["reserved_by"] for s in util["slices"]}
    assert owners[gang.slice_name] == "default/a"


def test_restore_conflict_parks_free_slices_as_drain(tmp_path):
    """A journaled grant naming a slice the pool no longer has resolves
    conservatively: NOTHING re-grants (all-or-nothing), the still-free
    named slices park as a deadline-only drain, the gang goes back to
    waiting — never re-grant over a live pod."""
    j = GrantJournal(_jpath(tmp_path))
    j.open()
    j.append("grant", gang="default/a",
             slices=["slice-0-v5e-8", "slice-9-gone"], state=_meta())
    j.close()

    adm, stats = _restored(tmp_path)
    assert stats["conflicts"] == 1 and stats["gangs"] == 0
    assert adm.get_gang("default", "a") is None  # back to waiting
    owners = {s["name"]: s["reserved_by"]
              for s in adm.utilization()["slices"]}
    assert owners["slice-0-v5e-8"] == "drain:default/a"  # parked, not free
    assert owners["slice-1-v5e-8"] == ""


def test_restore_evict_drain_release_confirm_sequence(tmp_path):
    """evict → partial release replays to a drain tracking only the
    unconfirmed pod; a journaled confirm_drain erases it entirely."""
    j = GrantJournal(_jpath(tmp_path))
    j.open()
    j.append("grant", gang="default/a", slices=["slice-0-v5e-8"],
             state=_meta())
    j.append("evict", gang="default/a", slices=["slice-0-v5e-8"],
             drain=True, pods=["default/p0", "default/p1"],
             resize_to="", grow=[], state=None)
    j.append("release", gang="default/a", pod="default/p0")
    j.close()

    adm, stats = _restored(tmp_path)
    assert stats["gangs"] == 0
    assert adm._drains["default/a"].pods == {"default/p1"}
    assert adm.draining() == {"default/a": ["slice-0-v5e-8"]}

    j2 = GrantJournal(_jpath(tmp_path))
    j2.open()
    j2.append("confirm_drain", gang="default/a", slices=["slice-0-v5e-8"])
    j2.close()
    adm3, _ = _restored(tmp_path)
    assert adm3.draining() == {}
    assert adm3.utilization()["chips_reserved"] == 0  # fully freed


def test_restore_slice_failed_parks_owner_and_drops_free_dead(tmp_path):
    j = GrantJournal(_jpath(tmp_path))
    j.open()
    j.append("grant", gang="default/a", slices=["slice-0-v5e-8"],
             state=_meta())
    j.append("slice_failed", gang="default/a", slice="slice-0-v5e-8")
    j.append("slice_failed", gang="", slice="slice-1-v5e-8")  # free slice died
    j.close()

    adm, stats = _restored(tmp_path)
    assert stats["gangs"] == 0
    # the owner's grant became a deadline-only drain on the dead slice
    assert adm.draining() == {"default/a": ["slice-0-v5e-8"]}
    assert "slice-0-v5e-8" in adm._dead
    # the free dead slice left the pool: inventory owns resurrection
    util = adm.utilization()
    assert util["slices_total"] == 1


def test_restore_grow_regrants_pre_verified_slices(tmp_path):
    """A RESIZE grow rides the evict record: replay re-grants the
    pre-verified new slices at the resized shape while the old slice
    drains — the one-record atomicity the live path promises."""
    j = GrantJournal(_jpath(tmp_path))
    j.open()
    j.append("grant", gang="default/a", slices=["slice-0-v5e-8"],
             state=_meta())
    j.append("evict", gang="default/a", slices=["slice-0-v5e-8"],
             drain=True, pods=None, resize_to="v5e-8",
             grow=["slice-1-v5e-8"], state=_meta())
    j.close()

    adm, stats = _restored(tmp_path)
    assert stats == {"records": 2, "conflicts": 0, "gangs": 1}
    assert adm.get_gang("default", "a").slice_name == "slice-1-v5e-8"
    assert adm.draining() == {"default/a": ["slice-0-v5e-8"]}
    assert adm.utilization()["chips_reserved"] == 16  # both held, neither free


def test_restore_counts_live_pod_with_no_journaled_gang(tmp_path):
    """A live pod whose gang the journal does not know means the journal
    and reality disagree — counted loudly as a conflict (the reconcile
    loop deletes such pods; their slices are never free-for-grant)."""
    from kubedl_tpu.gang.slice_admitter import ANNOTATION_GANG_NAME
    from kubedl_tpu.api.pod import Pod

    store = ObjectStore()
    pod = Pod(metadata=ObjectMeta(
        name="ghost-0", namespace="default",
        annotations={ANNOTATION_GANG_NAME: "default/ghost"}))
    store.create(pod)
    adm = TPUSliceAdmitter.with_pool(store, ["v5e-8"])
    stats = adm.restore_from_journal(GrantJournal(_jpath(tmp_path)))
    assert stats["conflicts"] == 1 and stats["records"] == 0


# ---------------------------------------------------------------------------
# compaction (docs/control_plane_scale.md)
# ---------------------------------------------------------------------------


def test_replay_after_compaction_is_state_equivalent(tmp_path):
    """The size-threshold compaction at the admitter's kick() choke
    point must be invisible to replay: a fresh admitter restored from
    the compacted journal rebuilds the exact same grants, drains, and
    dead-slice set as one restored from the full history — with the file
    shrunk to the effective-state snapshot and seq still monotonic."""
    adm1 = TPUSliceAdmitter.with_pool(ObjectStore(), ["v5e-8"] * 3)
    # threshold of 1 byte: every kick() with a non-empty file compacts
    j = GrantJournal(_jpath(tmp_path), compact_bytes=1)
    j.open()
    adm1.attach_journal(j)
    jobs = [_job(f"g{i}") for i in range(5)]
    for job in jobs:
        adm1.create_gang(job, job.spec.replica_specs)
    granted = sorted(g.key for g in adm1.gang_snapshots() if g.slice_names)
    assert len(granted) == 3  # pool-bound; g3/g4 wait
    # churn grows the history: each eviction frees a slice that a
    # waiting gang immediately re-reserves (evict + grant records), so
    # the compacted snapshot is strictly smaller than the full log
    for _ in range(3):
        g = next(g for g in adm1.gang_snapshots() if g.slice_names)
        adm1.evict_gang(g.namespace, g.name)
    # one granted slice dies: its gang parks as a deadline-only drain
    owner = next(g for g in adm1.gang_snapshots() if g.slice_names)
    victim = owner.slice_names[0]
    assert adm1.slice_failed(victim) == owner.key
    seq_before = j.snapshot()["seq"]
    lines_before = len(open(_jpath(tmp_path)).read().splitlines())

    adm1.kick()  # the compaction choke point
    assert j.compactions_total >= 1
    seq_after = j.snapshot()["seq"]
    assert seq_after > seq_before  # snapshot re-stamped ABOVE the watermark
    lines_after = len(open(_jpath(tmp_path)).read().splitlines())
    assert lines_after < lines_before
    # the journal is still appendable after the os.replace swap: finish
    # one of the still-granted jobs
    done = next(g for g in adm1.gang_snapshots() if g.slice_names)
    adm1.delete_gang(jobs[int(done.name[1:])])
    j.close()

    adm2, stats = _restored(tmp_path, pool=("v5e-8",) * 3)
    assert stats["conflicts"] == 0
    live1 = {g.key: sorted(g.slice_names)
             for g in adm1.gang_snapshots() if g.slice_names}
    live2 = {g.key: sorted(g.slice_names)
             for g in adm2.gang_snapshots() if g.slice_names}
    assert live2 == live1 and live2  # something survived, identically
    assert adm2.get_gang(done.namespace, done.name) is None
    # the drain and the dead-slice report survived the compaction
    assert adm2.draining() == adm1.draining()
    assert adm2.draining() == {owner.key: [victim]}
    assert victim in adm2._dead
    u1, u2 = adm1.utilization(), adm2.utilization()
    assert (u2["chips_reserved"], u2["slices_draining"]) == (
        u1["chips_reserved"], u1["slices_draining"])


def test_compaction_disabled_at_zero_threshold(tmp_path):
    """compact_bytes=0 (the default) must never compact — the knob's
    documented off switch."""
    adm = TPUSliceAdmitter.with_pool(ObjectStore(), ["v5e-8"])
    j = GrantJournal(_jpath(tmp_path))
    j.open()
    adm.attach_journal(j)
    job = _job("a")
    adm.create_gang(job, job.spec.replica_specs)
    assert not j.should_compact()
    adm.kick()
    assert j.compactions_total == 0
    j.close()


# ---------------------------------------------------------------------------
# HistoryStore
# ---------------------------------------------------------------------------


def test_history_roundtrip_survives_restart_and_torn_tail(tmp_path):
    hs = HistoryStore(str(tmp_path / "hist"))
    hs.initialize()
    hs.record_spans("default", "j1",
                    [{"name": "train.step", "dur": 1.0}],
                    {"goodput": 0.9})
    hs.record_lifecycle("default", "j1", "deleted", uid="u1")
    hs.close()
    with open(hs.path, "a", encoding="utf-8") as f:
        f.write('{"k": "default/j1", "kind": "tr')  # crash mid-append

    hs2 = HistoryStore(str(tmp_path / "hist"))
    hs2.initialize()
    rec = hs2.get("default", "j1")
    assert rec["spans"] == [{"name": "train.step", "dur": 1.0}]
    assert rec["goodput"] == {"goodput": 0.9}
    assert [e["event"] for e in rec["lifecycle"]] == ["deleted"]
    assert hs2.get("default", "unknown") is None
    hs2.close()


def test_history_retention_prunes_and_replays_cleanly(tmp_path):
    """Retention bounds rewrite history.jsonl via tmp+replace with an
    epoch-stamped keyless marker: old records disappear, recent ones
    survive byte-for-byte, a reopened store replays to the SAME state
    (the marker itself is skipped, only its epoch carried), and the
    max-bytes bound keeps the file from growing without limit."""
    hs = HistoryStore(str(tmp_path / "hist"), retention_max_age_s=3600.0)
    hs.initialize()
    hs.record_lifecycle("default", "old", "deleted", uid="u0")
    hs.record_spans("default", "new", [{"name": "s", "dur": 1.0}],
                    {"goodput": 1.0})
    # age the first record past the bound, keep the second fresh
    hs._lifecycle["default/old"][0]["t"] = time.time() - 7200.0
    assert hs.prune() == 1
    assert hs.prune_epoch == 1 and hs.pruned_records == 1
    assert hs.prune() == 0  # idempotent once within bounds
    assert hs.get("default", "old") is None
    assert hs.get("default", "new")["spans"] == [{"name": "s", "dur": 1.0}]
    assert not os.path.exists(hs.path + ".tmp")  # rewrite committed
    hs.close()

    # replay after prune: same state, epoch carried, marker not indexed
    hs2 = HistoryStore(str(tmp_path / "hist"))
    hs2.initialize()
    assert hs2.prune_epoch == 1
    assert hs2.get("default", "old") is None
    assert hs2.get("default", "new")["spans"] == [{"name": "s", "dur": 1.0}]
    hs2.close()

    # max-bytes: appending past the bound drops the oldest records
    # automatically, and the survivor set is the newest suffix
    hb = HistoryStore(str(tmp_path / "hist-b"), retention_max_bytes=600)
    hb.initialize()
    for i in range(20):
        hb.record_lifecycle("default", f"j{i:02d}", "deleted", uid="u")
    assert os.path.getsize(hb.path) <= 600 + 200  # bound + one marker
    assert hb.pruned_records > 0
    assert hb.get("default", "j19") is not None  # newest always kept
    assert hb.get("default", "j00") is None
    hb.close()


def test_history_joins_storage_backend_rows(tmp_path):
    row = types.SimpleNamespace(
        kind="TestJob", job_id="u1", status="Succeeded", deleted=1,
        resources="{}", tenant="default", gmt_created="2026-08-07",
        gmt_finished="2026-08-07")
    ev = types.SimpleNamespace(
        reason="SuccessfulCreatePod", message="created", type="Normal",
        count=1, last_timestamp="2026-08-07")
    obj_backend = types.SimpleNamespace(list_jobs=lambda q: [row])
    ev_backend = types.SimpleNamespace(list_events=lambda ns, n: [ev])
    hs = HistoryStore(str(tmp_path / "hist"), object_backend=obj_backend,
                      event_backend=ev_backend)
    hs.initialize()
    hs.record_lifecycle("default", "j1", "deleted", uid="u1")
    rec = hs.get("default", "j1")
    assert rec["job_record"]["status"] == "Succeeded"
    assert rec["job_record"]["deleted"] == 1
    assert rec["events"][0]["reason"] == "SuccessfulCreatePod"
    hs.close()


# ---------------------------------------------------------------------------
# the acceptance pin: history answers after TTL deletion AND trace-dir GC
# ---------------------------------------------------------------------------


def _get_json(url):
    try:
        with urllib.request.urlopen(url, timeout=5) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_history_outlives_job_ttl_and_trace_dir(tmp_path):
    from kubedl_tpu.operator import Operator, OperatorConfig
    from kubedl_tpu.server import OperatorHTTPServer

    op = Operator(OperatorConfig(
        enable_gang_scheduling=True,
        tpu_slices=["v5e-8"],
        trace_dir=str(tmp_path / "trace"),
        journal_dir=str(tmp_path / "journal"),
        history_dir=str(tmp_path / "history"),
        object_storage="sqlite",
        event_storage="sqlite",
    ))
    op.register(TestJobController())
    op.start()
    srv = OperatorHTTPServer(op, port=0)
    port = srv.start()
    base = f"http://127.0.0.1:{port}"
    try:
        manifest = {
            "kind": TEST_KIND,
            "metadata": {"name": "ttl-job"},
            "spec": {
                "replicaSpecs": {
                    "Worker": {
                        "replicas": 2,
                        "restartPolicy": "Never",
                        "template": {"spec": {"containers": [{
                            "name": "c", "image": "none",
                            "command": [sys.executable, "-c",
                                        "import time; time.sleep(0.2)"],
                            "resources": {"limits": {"google.com/tpu": 4}},
                        }]}},
                    }
                },
                "runPolicy": {},
            },
        }
        job = op.apply(manifest)
        assert op.wait_for_condition(job, "Succeeded", timeout=45)

        # the journal saw the whole grant/start lifecycle
        snap = op.journal.snapshot()
        assert snap["appends_total"] >= 3  # grant + 2 pods_start

        # a TTL fires seconds after the job ended, when the persist
        # controller has long written the terminal status; a delete in the
        # same instant as the condition would close the row as Stopped
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            rec = op.history_store.get("default", "ttl-job")
            if rec and (rec["job_record"] or {}).get("status") == "Succeeded":
                break
            time.sleep(0.05)
        else:
            pytest.fail("the job's terminal status was never persisted")

        # TTL fires: the CRD disappears, then the trace dir is GC'd
        op.store.delete(TEST_KIND, "default", "ttl-job")
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            rec = op.history_store.get("default", "ttl-job")
            if rec and any(e["event"] == "deleted"
                           for e in rec["lifecycle"]) and rec["spans"]:
                break
            time.sleep(0.1)
        else:
            pytest.fail("history controller never snapshotted the deletion")
        shutil.rmtree(str(tmp_path / "trace"))

        # live surfaces are gone...
        code, _ = _get_json(f"{base}/trace/default/ttl-job")
        assert code == 404
        # ...history still answers, with the full join
        code, rec = _get_json(f"{base}/history/default/ttl-job")
        assert code == 200
        assert rec["spans"] and rec["goodput"]
        assert any(e["event"] == "deleted" for e in rec["lifecycle"])
        assert rec["job_record"]["status"] == "Succeeded"
        assert rec["job_record"]["deleted"] == 1
        assert any(e["reason"] == "SuccessfulCreatePod"
                   for e in rec["events"])
        # the journal metrics family is rendered
        with urllib.request.urlopen(f"{base}/metrics", timeout=5) as r:
            body = r.read().decode()
        assert "kubedl_journal_appends_total" in body
        assert "kubedl_leader_epoch" in body
        code, unknown = _get_json(f"{base}/history/default/never-existed")
        assert code == 404
    finally:
        srv.stop()
        op.stop()
