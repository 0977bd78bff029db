"""Client/CLI e2e: the full submission path — stage, spawn coordinator
subprocess, RPC monitor, finish signal — against fixture scripts, mirroring
the reference's client-driven e2e tier (TestTonyE2E.java runs TonyClient
against the mini-cluster, not the AM directly)."""

import socket
import sys
import threading
import time
from pathlib import Path

import pytest

from tony_tpu.client.cli import cluster_submit, local_submit
from tony_tpu.conf import keys
from tony_tpu.client.client import TonyClient
from tony_tpu.proxy import ProxyServer

FIXTURES = Path(__file__).parent / "fixtures"


def _base_argv(tmp_path, fixture, extra=()):
    return [
        "--executes", str(FIXTURES / fixture),
        "--framework", "jax",
        "--conf", f"{keys.K_STAGING_LOCATION}={tmp_path}/staging",
        "--conf", f"{keys.K_HISTORY_LOCATION}={tmp_path}/history",
        "--conf", "tony.application.python-binary-path=" + sys.executable,
        "--conf", "tony.am.stop-grace=0",
        *extra,
    ]


class TestClientE2E:
    def test_submit_succeeds_exit_0(self, tmp_path):
        rc = TonyClient().init(_base_argv(tmp_path, "exit_0.py")).run()
        assert rc == 0
        # History written through the client path too.
        hist = list((tmp_path / "history").rglob("*.jhist"))
        assert hist and "SUCCEEDED" in hist[0].name

    def test_submit_fails_exit_1(self, tmp_path):
        rc = TonyClient().init(_base_argv(tmp_path, "exit_1.py")).run()
        assert rc == 1

    def test_src_dir_packaging_relative_executes(self, tmp_path):
        # Job sources are zipped, shipped, unpacked by the coordinator, and
        # a *relative* entry point resolves in the unpacked workdir.
        src = tmp_path / "src"
        src.mkdir()
        (src / "main.py").write_text("import helper; helper.go()\n")
        (src / "helper.py").write_text(
            "def go():\n    print('packaged module ran')\n"
        )
        argv = [
            "--executes", "main.py",
            "--src_dir", str(src),
            "--conf", f"{keys.K_STAGING_LOCATION}={tmp_path}/staging",
            "--conf", "tony.application.python-binary-path=" + sys.executable,
            "--conf", "tony.am.stop-grace=0",
        ]
        rc = TonyClient().init(argv).run()
        assert rc == 0

    def test_multi_worker_via_cli_local(self, tmp_path):
        rc = local_submit(
            _base_argv(tmp_path, "check_jax_env.py",
                       extra=["--conf", "tony.worker.instances=2"])
        )
        assert rc == 0

    def test_cluster_submit_stages_and_cleans_framework(self, tmp_path):
        # The fixture exits nonzero unless tony_tpu resolved from a staged
        # lib-<uuid> dir, so rc==0 proves staging actually happened.
        rc = cluster_submit(_base_argv(tmp_path, "check_staged_framework.py"))
        assert rc == 0
        # Per-submission lib-<uuid> dir is owned and removed by this
        # submission only (ClusterSubmitter.java:74-80 cleanup analogue).
        assert not list((tmp_path / "staging").glob("lib-*"))

    def test_am_crash_fails_job(self, tmp_path, monkeypatch):
        """TEST_AM_CRASH makes the coordinator subprocess die mid-session;
        the client must observe the death and return nonzero — the analogue
        of TestTonyE2E.testAMCrashTonyShouldFail (:178-192). Runs through
        the client path because an in-process coordinator would os._exit
        the test runner."""
        from tony_tpu import constants

        monkeypatch.setenv(constants.TEST_AM_CRASH, "1")
        rc = TonyClient().init(_base_argv(tmp_path, "exit_0.py")).run()
        assert rc == 1

    def test_client_timeout_kills_job(self, tmp_path):
        argv = [
            "--executes", "-c 'import time; time.sleep(600)'",
            "--conf", f"{keys.K_STAGING_LOCATION}={tmp_path}/staging",
            "--conf", "tony.application.python-binary-path=" + sys.executable,
            "--conf", "tony.application.timeout=3000",
            "--conf", "tony.am.stop-grace=0",
        ]
        rc = TonyClient().init(argv).run()
        assert rc == 1


class TestProxy:
    def test_bidirectional_tunnel(self):
        # Echo server as the "notebook".
        server = socket.create_server(("127.0.0.1", 0))
        port = server.getsockname()[1]

        def echo_once():
            conn, _ = server.accept()
            data = conn.recv(1024)
            conn.sendall(b"echo:" + data)
            conn.close()

        t = threading.Thread(target=echo_once, daemon=True)
        t.start()

        proxy = ProxyServer("127.0.0.1", port, 0)
        lport = proxy.start()
        try:
            with socket.create_connection(("127.0.0.1", lport), timeout=5) as c:
                c.sendall(b"ping")
                assert c.recv(1024) == b"echo:ping"
        finally:
            proxy.stop()
            server.close()


class TestNotebookFlow:
    def test_notebook_tunnel_end_to_end(self, tmp_path):
        """Full notebook flow: submit -> executor reserves TB_PORT ->
        notebook fixture serves on it -> registered URL -> client proxy
        tunnel -> HTTP through the tunnel."""
        import logging
        import re as _re
        import urllib.request

        from tony_tpu.client import cli as cli_mod

        records = []

        class Capture(logging.Handler):
            def emit(self, record):
                records.append(record.getMessage())

        handler = Capture()
        old_level = cli_mod.log.level
        cli_mod.log.setLevel(logging.INFO)  # default effective level is
        cli_mod.log.addHandler(handler)     # WARNING under pytest
        results = []
        argv = _base_argv(tmp_path, "notebook_server.py",
                          extra=["--conf", "tony.application.timeout=90000"])
        t = threading.Thread(
            target=lambda: results.append(cli_mod.notebook_submit(argv))
        )
        t.start()
        try:
            deadline = time.time() + 60
            port = None
            while time.time() < deadline and port is None:
                for msg in records:
                    m = _re.search(r"notebook tunnel: http://localhost:(\d+)", msg)
                    if m:
                        port = int(m.group(1))
                time.sleep(0.2)
            assert port is not None, f"tunnel never appeared; logs: {records}"
            body = urllib.request.urlopen(
                f"http://localhost:{port}/", timeout=10
            ).read()
            assert body == b"notebook-alive"
            t.join(timeout=60)
            assert results == [0]
        finally:
            cli_mod.log.removeHandler(handler)
            cli_mod.log.setLevel(old_level)


class TestClusterNotebookUrl:
    """Cluster-notebook discovery: the tunnel
    must target the notebook TASK's registered http URL — on a TPU-VM
    backend that is the REMOTE executor's host:port — with the
    coordinator-status tensorboard_url only as fallback."""

    def test_prefers_registered_task_url(self):
        from tony_tpu.client.cli import _notebook_url
        from tony_tpu.rpc import TaskUrl

        class Rpc:
            def get_task_urls(self):
                return [
                    TaskUrl("worker", 0, "file:///log"),
                    TaskUrl("notebook", 0, "http://tpu-vm-7:41213"),
                ]

            def get_application_status(self):
                raise AssertionError("fallback must not be consulted")

        assert _notebook_url(Rpc()) == "http://tpu-vm-7:41213"

    def test_falls_back_to_status_and_skips_log_urls(self):
        from tony_tpu.client.cli import _notebook_url
        from tony_tpu.rpc import TaskUrl

        class Rpc:
            def get_task_urls(self):
                # local backend: the notebook task carries its LOG url
                return [TaskUrl("notebook", 0, "file:///notebook-0.log")]

            def get_application_status(self):
                return {"tensorboard_url": "http://127.0.0.1:9999"}

        assert _notebook_url(Rpc()) == "http://127.0.0.1:9999"

    def test_transient_rpc_failure_returns_none(self):
        from tony_tpu.client.cli import _notebook_url

        class Rpc:
            def get_task_urls(self):
                raise ConnectionError("AM not up yet")

        assert _notebook_url(Rpc()) is None

    def test_register_tensorboard_pins_urlless_task(self, tmp_path):
        """Coordinator handler: a remote (url-less) task that registers
        its service URL becomes visible through get_task_urls; a local
        task keeps its log URL (history links)."""
        from tony_tpu.conf.configuration import TonyConfiguration
        from tony_tpu.coordinator.app_master import _RpcForClient
        from tony_tpu.coordinator.session import TonySession

        conf = TonyConfiguration()
        conf.set("tony.notebook.instances", 1)
        conf.set("tony.worker.instances", 1)
        conf.set("tony.ps.instances", 0)
        session = TonySession(conf, session_id=1)

        class Coord:
            pass

        from tony_tpu.observability.events import EventLog

        coord = Coord()
        coord.session = session
        coord.tensorboard_url = None
        coord.events = EventLog()
        handlers = _RpcForClient(coord)
        local = session.get_task("worker", 0)
        local.url = "file:///worker-0.log"
        handlers.register_tensorboard_url(
            "notebook:0", "http://tpu-vm-3:40001"
        )
        handlers.register_tensorboard_url(
            "worker:0", "http://should-not-clobber:1"
        )
        urls = {(u.name, u.index): u.url for u in session.task_urls()}
        assert urls[("notebook", 0)] == "http://tpu-vm-3:40001"
        assert urls[("worker", 0)] == "file:///worker-0.log"


@pytest.mark.parametrize("module", [
    "tony_tpu.client.cli",
    "tony_tpu.coordinator.app_master",
    "tony_tpu.executor.task_executor",
    "tony_tpu.scheduler.service",
])
def test_control_plane_imports_no_jax(module):
    """A chip belongs to one process at a time, and that process is the
    user script: the client, coordinator, executor and scheduler must
    never import jax (let alone initialise a backend), or the chain that
    launches a job would hold the chip its own child needs."""
    import subprocess

    repo = Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, "-c",
         f"import sys, {module}; "
         "print(sorted(m for m in sys.modules if m.split('.')[0] == 'jax'))"],
        cwd=repo, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "[]"
