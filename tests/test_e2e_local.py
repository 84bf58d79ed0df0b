"""End-to-end: job manifest -> operator -> real processes -> Succeeded.

This is the milestone the reference never had (its tests stop at fakes —
SURVEY.md §4): the full watch-driven loop with pods running as actual host
processes through the local executor, including gang slice admission.
"""
import sys
import os
import time

import pytest

from kubedl_tpu.api.common import JobConditionType, has_condition
from kubedl_tpu.core.store import NotFound
from kubedl_tpu.operator import Operator, OperatorConfig

from fake_workload import TEST_KIND, TestJobController


def make_operator(**kw):
    op = Operator(OperatorConfig(**kw))
    op.register(TestJobController())
    op.start()
    return op


def job_manifest(name="e2e-job", workers=2, command=None, chips=0, **run_policy):
    command = command or [sys.executable, "-c", "import time; time.sleep(0.1)"]
    container = {
        "name": "test-container",
        "image": "none",
        "command": command,
    }
    if chips:
        container["resources"] = {"limits": {"google.com/tpu": chips}}
    return {
        "kind": TEST_KIND,
        "metadata": {"name": name},
        "spec": {
            "replicaSpecs": {
                "Worker": {
                    "replicas": workers,
                    "restartPolicy": "Never",
                    "template": {"spec": {"containers": [container]}},
                }
            },
            "runPolicy": run_policy,
        },
    }


def test_job_runs_to_succeeded():
    op = make_operator()
    try:
        job = op.apply(job_manifest())
        assert op.wait_for_condition(job, "Running", timeout=30)
        assert op.wait_for_condition(job, "Succeeded", timeout=45)
        status = op.get_job(TEST_KIND, "default", "e2e-job").status
        assert status.replica_statuses["Worker"].succeeded == 2
        # launch-delay metrics were observed
        jm = op.metrics_registry.get(TEST_KIND)
        assert jm.created == 1 and jm.successful == 1
        assert jm.first_launch_delays and jm.all_launch_delays
        # events were recorded
        reasons = {e.reason for e in op.store.list("Event")}
        assert "SuccessfulCreatePod" in reasons
    finally:
        op.stop()


def test_failing_job_goes_failed():
    op = make_operator()
    try:
        job = op.apply(
            job_manifest(
                name="fail-job", workers=1,
                command=[sys.executable, "-c", "raise SystemExit(1)"],
            )
        )
        assert op.wait_for_condition(job, "Failed", timeout=15)
        jm = op.metrics_registry.get(TEST_KIND)
        assert jm.failed >= 1
    finally:
        op.stop()


def test_exit_code_retry_then_success(tmp_path):
    # First run exits 143 (retryable); the retry finds the marker file and
    # succeeds — exercising delete+recreate through the real executor.
    marker = tmp_path / "marker"
    script = (
        "import os, sys\n"
        f"m = {str(marker)!r}\n"
        "if os.path.exists(m): sys.exit(0)\n"
        "open(m, 'w').close(); sys.exit(143)\n"
    )
    op = make_operator()
    try:
        manifest = job_manifest(
            name="retry-job", workers=1, command=[sys.executable, "-c", script]
        )
        manifest["spec"]["replicaSpecs"]["Worker"]["restartPolicy"] = "ExitCode"
        job = op.apply(manifest)
        assert op.wait_for_condition(job, "Succeeded", timeout=20)
    finally:
        op.stop()


def test_gang_admission_on_tpu_slice():
    op = make_operator(
        enable_gang_scheduling=True, tpu_slices=["v5e-16"]
    )
    try:
        script = (
            "import os, sys, time\n"
            "assert os.environ['TPU_SLICE_TYPE'] == 'v5e-16', os.environ.get('TPU_SLICE_TYPE')\n"
            "assert os.environ['TPU_NUM_WORKERS'] == '2', os.environ.get('TPU_NUM_WORKERS')\n"
            # names libtpu reads describe the host, not the pool's slice:
            # the executor passes the host's own through (none are set here)
            f"assert os.environ.get('TPU_TOPOLOGY') == {os.environ.get('TPU_TOPOLOGY')!r}\n"
            f"assert os.environ.get('TPU_WORKER_ID') == {os.environ.get('TPU_WORKER_ID')!r}\n"
            "time.sleep(0.5)\n"
            "sys.exit(0)\n"
        )
        job = op.apply(
            job_manifest(
                name="tpu-job", workers=2,
                command=[sys.executable, "-c", script], chips=8,
            )
        )
        assert op.wait_for_condition(job, "Running", timeout=10)
        # gang PodGroup mirrored + reserved while the job runs
        pgs = op.store.list("PodGroup")
        assert len(pgs) == 1 and pgs[0].spec.tpu_chips == 16
        assert op.wait_for_condition(job, "Succeeded", timeout=20)
        # gang deleted with the job's terminal pass (ref job.go:168-176)
        deadline = time.monotonic() + 5
        while op.store.list("PodGroup") and time.monotonic() < deadline:
            time.sleep(0.05)
        assert op.store.list("PodGroup") == []
    finally:
        op.stop()


@pytest.mark.parametrize("said,chips,want", [
    (None, 1, "tpu,cpu"),   # granted a chip: no silent fall-back to the host
    ("cpu", 1, "cpu"),      # cpu said outright (tests, rehearsals) stays
    (None, 0, None),        # no chips asked for: nothing is imposed
])
def test_pod_granted_chips_must_run_on_tpu(monkeypatch, said, chips, want):
    """JAX falls back to the CPU with a warning when it finds no TPU, so a
    pod that was granted chips could train on the host and report
    success. The executor pins such a pod's platform unless its
    environment asks for the CPU outright."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    if said is not None:
        monkeypatch.setenv("JAX_PLATFORMS", said)
    op = make_operator(enable_gang_scheduling=True, tpu_slices=["v5e-1"])
    try:
        script = (
            "import os, sys\n"
            f"sys.exit(0 if os.environ.get('JAX_PLATFORMS') == {want!r} else 1)\n"
        )
        job = op.apply(job_manifest(
            name="platform-job", workers=1,
            command=[sys.executable, "-c", script], chips=chips))
        assert op.wait_for_condition(job, "Succeeded", timeout=30)
    finally:
        op.stop()


def test_gang_blocks_until_slice_free():
    # pool has ONE v5e-8 slice; two 8-chip jobs must serialize
    op = make_operator(enable_gang_scheduling=True, tpu_slices=["v5e-8"])
    try:
        slow = job_manifest(
            name="holder", workers=1,
            command=[sys.executable, "-c", "import time; time.sleep(1.0)"], chips=8,
        )
        fast = job_manifest(
            name="waiter", workers=1,
            command=[sys.executable, "-c", "import sys; sys.exit(0)"], chips=8,
        )
        j1 = op.apply(slow)
        assert op.wait_for_condition(j1, "Running", timeout=10)
        j2 = op.apply(fast)
        time.sleep(0.5)
        # while holder runs, waiter's pod must still be Pending
        waiter_pods = [
            p for p in op.store.list("Pod") if p.metadata.labels.get("job-name") == "waiter"
        ]
        assert waiter_pods and waiter_pods[0].status.phase.value == "Pending"
        assert op.wait_for_condition(j1, "Succeeded", timeout=15)
        assert op.wait_for_condition(j2, "Succeeded", timeout=15)
    finally:
        op.stop()


def test_ttl_cleanup_end_to_end():
    op = make_operator()
    try:
        job = op.apply(
            job_manifest(
                name="ttl-job", workers=1,
                command=[sys.executable, "-c", "pass"],
                ttlSecondsAfterFinished=1,
            )
        )
        assert op.wait_for_condition(job, "Succeeded", timeout=15)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            try:
                op.store.get(TEST_KIND, "default", "ttl-job")
            except NotFound:
                break
            time.sleep(0.1)
        else:
            pytest.fail("job was not TTL-deleted")
    finally:
        op.stop()


def test_trainer_memory_knobs_run_end_to_end():
    """--remat dots and --ce-chunks through the real trainer process:
    the memory knobs must not change convergence-path behavior (job
    completes; losses logged are finite)."""
    import subprocess

    from conftest import CPU_ENV

    env = dict(os.environ)
    env.update(CPU_ENV)
    p = subprocess.run(
        [sys.executable, "-m", "kubedl_tpu.train.trainer",
         "--model", "tiny", "--steps", "4", "--batch", "4",
         "--seq-len", "33", "--remat", "dots", "--ce-chunks", "4",
         "--log-every", "2"],
        env=env, capture_output=True, text=True, timeout=180,
    )
    assert p.returncode == 0, p.stderr[-800:]
    assert "done: 4 steps" in p.stdout, p.stdout


@pytest.mark.slow
def test_generate_allow_fresh_init_round_trip(tmp_path):
    """--allow-fresh-init serves random weights with an explicit opt-in;
    without it an empty checkpoint dir is a hard error."""
    import subprocess

    from conftest import CPU_ENV

    env = dict(os.environ)
    env.update(CPU_ENV)
    empty = str(tmp_path / "nockpt")
    os.makedirs(empty)
    base = [sys.executable, "-m", "kubedl_tpu.train.generate",
            "--model", "tiny", "--checkpoint-path", empty,
            "--batch", "1", "--prompt-len", "4", "--max-new-tokens", "2"]
    p = subprocess.run(base, env=env, capture_output=True, text=True, timeout=180)
    assert p.returncode == 1 and "no checkpoint" in p.stderr
    p = subprocess.run(base + ["--allow-fresh-init"], env=env,
                       capture_output=True, text=True, timeout=180)
    assert p.returncode == 0, p.stderr[-800:]
    assert "done: generated" in p.stdout


@pytest.mark.slow
def test_trainer_lr_schedule_resumes_from_checkpoint(tmp_path):
    """Cosine schedule + warmup + grad clipping through the real trainer,
    including an Orbax save -> resume cycle (the chained optimizer's
    state tree must round-trip)."""
    import subprocess

    from conftest import CPU_ENV

    env = dict(os.environ)
    env.update(CPU_ENV)
    ckpt = str(tmp_path / "ckpt")
    base = [sys.executable, "-m", "kubedl_tpu.train.trainer",
            "--model", "tiny", "--steps", "6", "--batch", "4",
            "--seq-len", "33", "--lr-schedule", "cosine",
            "--warmup-steps", "2", "--grad-clip", "1.0",
            "--checkpoint-path", ckpt, "--checkpoint-interval", "2",
            "--log-every", "2"]
    p = subprocess.run(base, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-800:]
    assert "done: 6 steps" in p.stdout, p.stdout
    # resume: same flags, more steps — restores the chained opt state
    base[base.index("--steps") + 1] = "8"
    p = subprocess.run(base, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-800:]
    assert "resumed" in p.stdout or "restored" in p.stdout, p.stdout


@pytest.mark.slow
def test_trainer_eval_pass_reports_held_out_loss(tmp_path):
    """--eval-every through the real trainer with a TRUE held-out set
    (--eval-data-path, separate shards). The eval set is fixed: a rerun
    with identical args reproduces the same eval losses exactly."""
    import subprocess

    import numpy as np

    from conftest import CPU_ENV

    np.random.default_rng(0).integers(
        0, 256, 64 * 33 * 8, dtype=np.int32).tofile(tmp_path / "train0.bin")
    np.random.default_rng(1).integers(
        0, 256, 64 * 33 * 4, dtype=np.int32).tofile(tmp_path / "eval0.bin")
    env = dict(os.environ)
    env.update(CPU_ENV)
    cmd = [sys.executable, "-m", "kubedl_tpu.train.trainer",
           "--model", "tiny", "--steps", "4", "--batch", "4",
           "--seq-len", "33", "--eval-every", "2", "--eval-batches", "2",
           "--data-path", str(tmp_path / "train*.bin"),
           "--eval-data-path", str(tmp_path / "eval*.bin"),
           "--log-every", "2"]

    def run():
        p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                           timeout=240)
        assert p.returncode == 0, p.stderr[-800:]
        return [l for l in p.stdout.splitlines() if l.startswith("eval step")]

    evals = run()
    assert len(evals) == 2 and all("held-out" in l for l in evals), evals
    # fixed set + deterministic init: a rerun reproduces the losses
    assert run() == evals
