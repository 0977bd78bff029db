"""End-to-end tests on the mini cluster — the analogue of the reference's
``TestTonyE2E.java`` (11 scenarios on a 3-NM MiniYARNCluster): a real
coordinator with a real RPC server launching real executor subprocesses that
run Python fixture scripts asserting the env contract."""

import sys
from pathlib import Path

import pytest

from tony_tpu.conf import keys
from tony_tpu.coordinator.session import SessionStatus
from tony_tpu.history.writer import JobMetadata
from tony_tpu.mini import MiniTonyCluster

FIXTURES = Path(__file__).resolve().parent / "fixtures"


@pytest.fixture()
def cluster(tmp_path):
    return MiniTonyCluster(tmp_path)


def _job(cluster, fixture, workers=1, ps=0, framework="jax", **extra):
    conf = cluster.base_conf()
    conf.set(keys.K_FRAMEWORK, framework)
    conf.set(keys.K_EXECUTES, str(FIXTURES / fixture))
    conf.set(keys.K_PYTHON_BINARY, sys.executable)
    conf.set(keys.instances_key("worker"), workers)
    conf.set(keys.instances_key("ps"), ps)
    for k, v in extra.items():
        conf.set(k, v)
    return conf


def test_single_worker_succeeds(cluster):
    status, _ = cluster.run_job(_job(cluster, "exit_0.py"))
    assert status is SessionStatus.SUCCEEDED


def test_failing_worker_fails_job(cluster):
    status, coord = cluster.run_job(_job(cluster, "exit_1.py"))
    assert status is SessionStatus.FAILED
    assert "worker:0" in coord.session.diagnostics


def test_env_contract_and_shell_env(cluster):
    conf = _job(cluster, "check_env.py", workers=2)
    conf.set(keys.K_SHELL_ENV, "USER_SHELL_VAR=propagated")
    status, _ = cluster.run_job(conf)
    assert status is SessionStatus.SUCCEEDED


def test_jax_runtime_env(cluster):
    status, _ = cluster.run_job(_job(cluster, "check_jax_env.py", workers=2, ps=1))
    assert status is SessionStatus.SUCCEEDED


def test_pytorch_runtime_env(cluster):
    status, _ = cluster.run_job(
        _job(cluster, "check_pytorch_env.py", workers=2, framework="pytorch")
    )
    assert status is SessionStatus.SUCCEEDED


def test_gang_barrier_with_ps(cluster):
    # ps + 2 workers: everyone must pass the barrier; chief success ends the
    # job while ps (running exit_0 too, but untracked) cannot block it.
    status, coord = cluster.run_job(_job(cluster, "exit_0.py", workers=2, ps=1))
    assert status is SessionStatus.SUCCEEDED
    spec = coord.session.cluster_spec()
    assert spec is not None and len(spec["worker"]) == 2 and len(spec["ps"]) == 1


def test_slice_topology_reaches_user_script(cluster):
    """tony.worker.tpus=4 -> coordinator plans a v5litepod-4 slice and the
    user script reads it via tony_tpu.runtime.slice_topology()."""
    conf = _job(cluster, "check_slice_env.py")
    conf.set(keys.tpus_key("worker"), 4)
    status, coord = cluster.run_job(conf)
    assert status is SessionStatus.SUCCEEDED, coord.session.diagnostics
    assert coord.slice_plans["worker"].accelerator_type == "v5litepod-4"


def test_multislice_identity_reaches_user_script(cluster):
    """2 workers x tpus=8 pinned to v5litepod-8 => a 2-slice plan; each
    executor must see its slice index, in-slice process id, and the
    megascale/DCN env, while jax.distributed stays one flat process list
    (multi-slice must be driveable end to end)."""
    conf = _job(cluster, "check_multislice_env.py", workers=2)
    conf.set(keys.tpus_key("worker"), 8)
    conf.set(keys.K_TPU_ACCELERATOR_TYPE, "v5litepod-8")
    status, coord = cluster.run_job(conf)
    assert status is SessionStatus.SUCCEEDED, coord.session.diagnostics
    plan = coord.slice_plans["worker"]
    assert plan.num_slices == 2 and plan.hosts_per_slice == 1


def test_multihost_slice_identity_reaches_user_script(cluster):
    """4 workers x tpus=4 pinned to v4-16 (a 2-host slice shape) => 2
    slices x 2 hosts; each executor must see slice index task//2 and
    in-slice process id task%2 — the hosts_per_slice>1 placement path
    (one host per slice alone would not cover it)."""
    conf = _job(cluster, "check_multihost_slice_env.py", workers=4)
    conf.set(keys.tpus_key("worker"), 4)
    conf.set(keys.K_TPU_ACCELERATOR_TYPE, "v4-16")
    status, coord = cluster.run_job(conf)
    assert status is SessionStatus.SUCCEEDED, coord.session.diagnostics
    plan = coord.slice_plans["worker"]
    assert plan.num_slices == 2 and plan.hosts_per_slice == 2


def test_sharded_reader_handoff_exactly_once(cluster, tmp_path):
    """Data-plane handoff (the py4j analogue): two executor processes each
    build a reader via tony_tpu.runtime.sharded_reader; together their
    shards must cover every record exactly once."""
    import json as _json

    data = tmp_path / "corpus.jsonl"
    data.write_text("".join(
        _json.dumps({"id": i, "text": "x" * (i % 7)}) + "\n"
        for i in range(57)
    ))
    conf = _job(cluster, "reader_shard.py", workers=2)
    conf.set(keys.K_SHELL_ENV, f"READER_DATA={data}")
    status, coord = cluster.run_job(conf)
    assert status is SessionStatus.SUCCEEDED, coord.session.diagnostics
    shards = []
    for p in sorted((coord.app_dir / "logs").glob("reader-shard-*.json")):
        shards.append(_json.loads(p.read_text()))
    assert len(shards) == 2 and all(shards)
    combined = sorted(i for s in shards for i in s)
    assert combined == list(range(57))  # exact cover, nothing twice


def test_sharded_reader_over_gs_uris(cluster, tmp_path):
    """The remote-storage data plane end to end:
    executors stream a gs:// corpus via ranged reads — no staging, the way
    the reference's reader opens HDFS directly
    (HdfsAvroFileSplitReader.java:347-416). TONY_GCS_EMULATOR_DIR (the
    MiniDFS analogue) maps the bucket onto a local dir in every executor
    subprocess."""
    import json as _json

    from tony_tpu.cloud.gcs import FileObjectStorage

    store = FileObjectStorage(tmp_path / "objects")
    store.put_bytes("gs://corpus/part-0.jsonl", "".join(
        _json.dumps({"id": i, "text": "x" * (i % 7)}) + "\n"
        for i in range(39)
    ).encode())
    store.put_bytes("gs://corpus/part-1.jsonl", "".join(
        _json.dumps({"id": i, "text": "y" * (i % 5)}) + "\n"
        for i in range(39, 57)
    ).encode())
    conf = _job(cluster, "reader_shard.py", workers=2)
    conf.set(
        keys.K_SHELL_ENV,
        "READER_DATA=gs://corpus/part-0.jsonl;gs://corpus/part-1.jsonl,"
        f"TONY_GCS_EMULATOR_DIR={tmp_path / 'objects'}",
    )
    status, coord = cluster.run_job(conf)
    assert status is SessionStatus.SUCCEEDED, coord.session.diagnostics
    shards = []
    for p in sorted((coord.app_dir / "logs").glob("reader-shard-*.json")):
        shards.append(_json.loads(p.read_text()))
    assert len(shards) == 2 and all(shards)
    combined = sorted(i for s in shards for i in s)
    assert combined == list(range(57))


def test_cross_process_psum(cluster):
    """A REAL jax.distributed collective through the full stack: 2 executor
    subprocesses each call tony_tpu.runtime.initialize() and run a pmap psum
    whose value proves cross-process data movement."""
    status, coord = cluster.run_job(
        _job(cluster, "jax_psum.py", workers=2), timeout_s=300
    )
    assert status is SessionStatus.SUCCEEDED, coord.session.diagnostics


def test_succeeded_session_reaps_blocked_ps_processes(cluster):
    """A SUCCEEDED session must leave ZERO job processes behind — including
    an untracked ps whose user script blocks forever in Server.join() and
    the grandchildren it spawned (such orphans were once
    found on the build box). The reference kills whole containers on
    reset/stop (TonyApplicationMaster.java:526-542, 621-637); here the
    TERM->reap handshake between backend.kill and the executor's death
    handlers is the equivalent."""
    import json as _json
    import os as _os
    import time as _time

    status, coord = cluster.run_job(
        _job(cluster, "ps_block_forever.py", workers=1, ps=1)
    )
    assert status is SessionStatus.SUCCEEDED, coord.session.diagnostics
    pids = _json.loads(
        (coord.app_dir / "logs" / "ps-pids.json").read_text()
    )
    deadline = _time.time() + 30  # generous: 1-CPU box under suite load
    still_alive = dict(pids)
    while still_alive and _time.time() < deadline:
        for name, pid in list(still_alive.items()):
            try:
                _os.kill(pid, 0)
            except ProcessLookupError:
                del still_alive[name]
        _time.sleep(0.2)
    assert not still_alive, f"orphaned job processes: {still_alive}"


def test_exited_script_cannot_orphan_helpers(cluster):
    """A worker that spawns a background helper and exits 0: the helper
    (same user process group) must be reaped even though the direct child
    exited cleanly — group teardown, not child teardown."""
    import json as _json
    import os as _os
    import time as _time

    status, coord = cluster.run_job(_job(cluster, "spawn_helper_exit.py"))
    assert status is SessionStatus.SUCCEEDED, coord.session.diagnostics
    helper = _json.loads(
        (coord.app_dir / "logs" / "helper-0.json").read_text()
    )["helper"]
    deadline = _time.time() + 30
    while _time.time() < deadline:
        try:
            _os.kill(helper, 0)
        except ProcessLookupError:
            break
        _time.sleep(0.2)
    else:
        raise AssertionError(f"helper {helper} survived the job")


def test_backend_escalation_reaps_user_group_via_pgid_file(tmp_path):
    """The SIGKILL escalation path cannot rely on the executor's handlers
    (SIGKILL runs none): the backend must reap the user process group from
    the pgid file the executor advertised at spawn."""
    import os as _os
    import signal as _signal
    import subprocess as _subprocess
    import time as _time

    from tony_tpu.coordinator.backend import LocalProcessBackend, _ProcHandle

    backend = LocalProcessBackend(tmp_path / "logs")
    # a fake "user process" in its own session, advertised via pgid file
    user = _subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(3600)"],
        start_new_session=True,
    )
    (tmp_path / "logs" / ".worker-0.userpgid").write_text(str(user.pid))
    # a fake "wedged executor" that ignores SIGTERM; it prints once the
    # handler is installed so the TERM below cannot race the install
    wedged = _subprocess.Popen(
        [sys.executable, "-u", "-c",
         "import signal, time; signal.signal(signal.SIGTERM, "
         "signal.SIG_IGN); print('ready', flush=True); time.sleep(3600)"],
        start_new_session=True, stdout=_subprocess.PIPE,
    )
    assert wedged.stdout is not None and wedged.stdout.readline().strip() == b"ready"
    backend.KILL_GRACE_S = 1.0
    try:
        backend.kill(_ProcHandle(wedged, "worker:0"))
        assert wedged.poll() is not None  # escalated to SIGKILL
        deadline = _time.time() + 10
        while user.poll() is None and _time.time() < deadline:
            _time.sleep(0.1)
        assert user.poll() is not None, "user group survived escalation"
    finally:
        for p in (user, wedged):
            if p.poll() is None:
                _os.killpg(p.pid, _signal.SIGKILL)


def test_history_written(cluster):
    status, coord = cluster.run_job(_job(cluster, "exit_0.py"))
    assert status is SessionStatus.SUCCEEDED
    jhists = list(cluster.history_dir.rglob("*.jhist"))
    assert len(jhists) == 1
    meta = JobMetadata.parse_jhist_name(jhists[0].name)
    assert meta.status == "SUCCEEDED" and meta.app_id == coord.app_id
    assert (jhists[0].parent / "config.json").is_file()


def test_task_urls_point_at_logs(cluster):
    status, coord = cluster.run_job(_job(cluster, "exit_0.py", workers=2))
    urls = coord.session.task_urls()
    assert [u.index for u in urls] == [0, 1]
    assert all(u.url.startswith("file://") for u in urls)


def test_single_node_mode_succeeds(cluster):
    """K_IS_SINGLE_NODE: the user command runs inside the coordinator, no
    executors launch (doPreprocessingJob + early exit, reference :483-497)."""
    conf = _job(cluster, "exit_0.py", workers=2)
    conf.set(keys.K_IS_SINGLE_NODE, True)
    status, coord = cluster.run_job(conf)
    assert status is SessionStatus.SUCCEEDED
    # no executor ever launched (their logs would exist otherwise)
    logs = list((coord.app_dir / "logs").glob("worker-*.log"))
    assert logs == []
    assert list((coord.app_dir / "logs").glob("preprocess-*.log"))


def test_single_node_failure_never_retries(cluster):
    conf = _job(cluster, "exit_1.py")
    conf.set(keys.K_IS_SINGLE_NODE, True)
    conf.set(keys.K_AM_RETRY_COUNT, 3)
    status, coord = cluster.run_job(conf)
    assert status is SessionStatus.FAILED
    assert coord.session.session_id == 1  # reference :365: no single-node retry


def test_preprocess_gates_and_forwards_model_params(cluster):
    """K_ENABLE_PREPROCESS: same script runs first in the coordinator
    (emitting 'Model parameters: ...'), then as tasks that must see
    MODEL_PARAMS (reference :684-701)."""
    conf = _job(cluster, "preprocess_fixture.py", workers=2)
    conf.set(keys.K_ENABLE_PREPROCESS, True)
    status, coord = cluster.run_job(conf)
    assert status is SessionStatus.SUCCEEDED, coord.session.diagnostics


def test_preprocess_failure_blocks_scheduling(cluster):
    conf = _job(cluster, "preprocess_fixture.py", workers=2)
    conf.set(keys.K_ENABLE_PREPROCESS, True)
    conf.set(keys.K_SHELL_ENV, "PREPROCESS_SHOULD_FAIL=1")
    status, coord = cluster.run_job(conf)
    assert status is SessionStatus.FAILED
    assert "preprocess job exited with 3" in coord.session.diagnostics
    assert list((coord.app_dir / "logs").glob("worker-*.log")) == []


def test_application_timeout(cluster):
    conf = _job(cluster, "exit_0.py")
    # make the worker hang forever via a sleep command instead of the fixture
    conf.set(keys.K_EXECUTES, "-c 'import time; time.sleep(600)'")
    conf.set(keys.K_APPLICATION_TIMEOUT, 2000)
    status, coord = cluster.run_job(conf, timeout_s=60)
    assert status is SessionStatus.FAILED
    assert "timed out" in coord.session.diagnostics
