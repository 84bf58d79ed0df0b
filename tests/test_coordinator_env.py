"""Coordinator-env contract: process id 0 must be the pod at the advertised
coordinator address, ids unique in [0, num_processes) across ALL replica
types — the invariant jax.distributed.initialize depends on."""
import pytest

from kubedl_tpu.controllers.engine import JobReconciler
from kubedl_tpu.core.store import ObjectStore
from kubedl_tpu.utils.serde import from_dict
from kubedl_tpu.workloads.tensorflow import TFJob, TFJobController
from kubedl_tpu.workloads.xdl import XDLJob, XDLJobController
from kubedl_tpu.workloads.xgboost import XGBoostJob, XGBoostJobController


def reconcile(ctrl, cls, replica_field, replicas, container):
    spec = {replica_field: {}}
    for rtype, n in replicas.items():
        spec[replica_field][rtype] = {
            "replicas": n,
            "template": {"spec": {"containers": [{"name": container, "image": "i"}]}},
        }
    job = from_dict(cls, {"metadata": {"name": "j", "uid": "u1"}, "spec": spec})
    store = ObjectStore()
    engine = JobReconciler(store, ctrl)
    ctrl.engine = engine
    created = store.create(job)
    engine.reconcile(created.key)
    return store


def coord_contract(store, container):
    """(address, {pod_name: process_id}, num_processes) + invariant checks."""
    ids = {}
    addrs = set()
    nums = set()
    for pod in store.list("Pod"):
        env = next(c for c in pod.spec.containers if c.name == container).env
        ids[pod.metadata.name] = int(env["KUBEDL_PROCESS_ID"])
        addrs.add(env["KUBEDL_COORDINATOR_ADDRESS"])
        nums.add(int(env["KUBEDL_NUM_PROCESSES"]))
    assert len(addrs) == 1 and len(nums) == 1
    n = nums.pop()
    assert sorted(ids.values()) == list(range(n)), f"ids not unique/dense: {ids}"
    addr = addrs.pop()
    coordinator_pod = addr.split(".")[0]
    assert ids[coordinator_pod] == 0, (
        f"process 0 is not at the coordinator address {addr}: {ids}"
    )
    return addr, ids, n


def test_xdl_multi_role_ranks():
    store = reconcile(
        XDLJobController(), XDLJob, "xdlReplicaSpecs",
        {"PS": 1, "Scheduler": 1, "Worker": 2}, "xdl",
    )
    addr, ids, n = coord_contract(store, "xdl")
    assert n == 4
    assert addr.startswith("j-scheduler-0.")


def test_xgboost_master_is_process_zero():
    store = reconcile(
        XGBoostJobController(), XGBoostJob, "xgbReplicaSpecs",
        {"Master": 1, "Worker": 2}, "xgboostjob",
    )
    addr, ids, n = coord_contract(store, "xgboostjob")
    assert n == 3
    assert ids["j-master-0"] == 0
    assert addr.startswith("j-master-0.")


def test_tf_ps_job_coordinator_is_rank_zero():
    store = reconcile(
        TFJobController(), TFJob, "tfReplicaSpecs",
        {"PS": 2, "Worker": 2}, "tensorflow",
    )
    addr, ids, n = coord_contract(store, "tensorflow")
    assert n == 4
    # no chief/master -> worker-0 coordinates and must be process 0
    assert addr.startswith("j-worker-0.")
    assert ids["j-worker-0"] == 0


def test_tf_chief_job_coordinator_is_rank_zero():
    store = reconcile(
        TFJobController(), TFJob, "tfReplicaSpecs",
        {"Chief": 1, "PS": 1, "Worker": 2}, "tensorflow",
    )
    addr, ids, n = coord_contract(store, "tensorflow")
    assert addr.startswith("j-chief-0.")
    assert ids["j-chief-0"] == 0


# -- what every training program does before its first JAX call -------------


def test_compile_cache_default_is_one_fixed_place_in_the_checkout(monkeypatch):
    import os

    import jax

    from kubedl_tpu.train import coordinator

    was = jax.config.jax_compilation_cache_dir
    try:
        # set from outside: JAX reads the variable itself, code sets nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        coordinator.place_compile_cache()
        assert jax.config.jax_compilation_cache_dir == was
        # not set: one fixed path under the repo root, whatever the cwd
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        monkeypatch.chdir("/")
        coordinator.place_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_report_devices_names_platform_kind_and_count(capsys):
    import jax

    from kubedl_tpu.train import coordinator

    coordinator.report_devices()
    assert capsys.readouterr().out == (
        f"devices: platform=cpu device_kind=cpu count={len(jax.devices())}\n")


def test_chip_held_by_another_process_is_a_readable_failure(monkeypatch):
    import jax

    from kubedl_tpu.train import coordinator

    def held():
        raise RuntimeError(
            "Unable to initialize backend 'tpu': ABORTED: Internal error when "
            "accessing libtpu multi-process lockfile. Run \"$ sudo rm "
            "/tmp/libtpu_lockfile\".")

    monkeypatch.setattr(jax, "devices", held)
    with pytest.raises(SystemExit) as exc:
        coordinator.report_devices()
    assert "one process at a time" in str(exc.value)
    assert "do not remove" in str(exc.value)
