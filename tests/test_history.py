"""History read path + web server tests — the analogue of the reference's
history-server tier (TestParserUtils/TestHdfsUtils fixture-folder scans and
the WithBrowser smoke test, tony-history-server/test/**)."""

import json
import time
import urllib.error
import urllib.request

from tony_tpu.conf.configuration import TonyConfiguration
from tony_tpu.history import JobMetadata, setup_job_dir
from tony_tpu.history.reader import TtlCache, job_config, list_jobs
from tony_tpu.history.server import HistoryServer
from tony_tpu.history.writer import create_history_file, write_config_file


def _make_job(hist, app_id, started_ms, status="SUCCEEDED"):
    job_dir = setup_job_dir(str(hist), app_id, started_ms)
    conf = TonyConfiguration()
    conf.set("tony.application.name", f"name-of-{app_id}")
    write_config_file(job_dir, conf)
    create_history_file(job_dir, JobMetadata.new(app_id, started_ms, status))
    return job_dir


class TestReadPath:
    def test_list_jobs_newest_first_and_malformed_skipped(self, tmp_path):
        now = int(time.time() * 1000)
        _make_job(tmp_path, "application_1_0001", now - 60_000)
        _make_job(tmp_path, "application_1_0002", now, status="FAILED")
        # Malformed entries must be skipped, not crash the listing.
        bad = tmp_path / "2020" / "01" / "01" / "application_bad_x"
        bad.mkdir(parents=True)
        (bad / "nonsense.jhist").write_text("")
        (tmp_path / "2020" / "01" / "01" / "not-an-app").mkdir()

        jobs = list_jobs(tmp_path)
        assert [j.app_id for j in jobs] == [
            "application_1_0002", "application_1_0001",
        ]
        assert jobs[0].status == "FAILED"

    def test_job_config_roundtrip(self, tmp_path):
        now = int(time.time() * 1000)
        _make_job(tmp_path, "application_1_0003", now)
        cfg = job_config(tmp_path, "application_1_0003")
        assert cfg["tony.application.name"] == "name-of-application_1_0003"
        assert job_config(tmp_path, "application_9_9999") is None

    def test_malformed_jhist_variants_skipped(self, tmp_path):
        """Satellite coverage: every malformed-.jhist shape seen in the
        wild must be skipped, never raise — non-int timestamps, too few
        fields, empty stems, a .jhist that is a directory."""
        now = int(time.time() * 1000)
        _make_job(tmp_path, "application_1_0001", now)
        day = tmp_path / "2021" / "02" / "03"
        bad = day / "application_2_0001"
        bad.mkdir(parents=True)
        (bad / "application_2_0001-notanint-0-u-FAILED.jhist").write_text("")
        (bad / "too-few.jhist").write_text("")
        (bad / ".jhist").write_text("")
        (bad / "application_2_0001-1-2-u-OK.jhist.d").mkdir()
        jobs = list_jobs(tmp_path)
        assert [j.app_id for j in jobs] == ["application_1_0001"]

    def test_empty_day_directories_listed_clean(self, tmp_path):
        """Empty year/month/day trees (history locations are pre-created
        by provisioning) must list as zero jobs."""
        (tmp_path / "2024" / "01" / "01").mkdir(parents=True)
        (tmp_path / "2024" / "01" / "02").mkdir(parents=True)
        assert list_jobs(tmp_path) == []
        # an empty JOB dir (crashed before any write) is also clean
        (tmp_path / "2024" / "01" / "02" / "application_7_0001").mkdir()
        assert list_jobs(tmp_path) == []

    def test_config_without_final_status_lists_and_serves(self, tmp_path):
        """A job with config.json + .jhist but no final-status (crashed
        coordinator, or pre-observability writer) must list, serve its
        config, and 404 — not 500 — on the run-report views."""
        now = int(time.time() * 1000)
        _make_job(tmp_path, "application_5_0001", now, status="RUNNING")
        jobs = list_jobs(tmp_path)
        assert [j.app_id for j in jobs] == ["application_5_0001"]
        assert job_config(tmp_path, "application_5_0001") is not None
        from tony_tpu.history.reader import (
            job_events,
            job_final_status,
            job_trace,
        )

        assert job_final_status(tmp_path, "application_5_0001") is None
        assert job_events(tmp_path, "application_5_0001") is None
        assert job_trace(tmp_path, "application_5_0001") is None
        server = HistoryServer(str(tmp_path), port=0)
        port = server.serve_background()
        try:
            try:
                urllib.request.urlopen(
                    f"http://localhost:{port}/job/application_5_0001"
                )
                assert False, "expected 404"
            except urllib.error.HTTPError as e:
                assert e.code == 404
        finally:
            server.stop()

    def test_ttl_cache(self):
        clock = [0.0]
        cache = TtlCache(ttl_s=10.0, clock=lambda: clock[0])
        calls = []
        load = lambda: calls.append(1) or len(calls)
        assert cache.get_or_load("k", load) == 1
        assert cache.get_or_load("k", load) == 1  # cached
        clock[0] = 11.0
        assert cache.get_or_load("k", load) == 2  # expired


class TestHistoryServer:
    def test_pages_and_api(self, tmp_path):
        now = int(time.time() * 1000)
        _make_job(tmp_path, "application_2_0001", now)
        server = HistoryServer(str(tmp_path), port=0)
        port = server.serve_background()
        try:
            base = f"http://localhost:{port}"
            index = urllib.request.urlopen(f"{base}/").read().decode()
            assert "application_2_0001" in index and "SUCCEEDED" in index

            page = urllib.request.urlopen(
                f"{base}/config/application_2_0001"
            ).read().decode()
            assert "name-of-application_2_0001" in page

            jobs = json.loads(
                urllib.request.urlopen(f"{base}/api/jobs").read()
            )
            assert jobs[0]["app_id"] == "application_2_0001"

            cfg = json.loads(urllib.request.urlopen(
                f"{base}/api/config/application_2_0001"
            ).read())
            assert cfg["tony.application.name"] == "name-of-application_2_0001"

            try:
                urllib.request.urlopen(f"{base}/config/application_9_9")
                assert False, "expected 404"
            except urllib.error.HTTPError as e:
                assert e.code == 404
        finally:
            server.stop()

    def test_per_job_run_stats_page(self, tmp_path):
        """The /job/<id> page renders the coordinator's terminal record:
        state, run stats, slice plans, per-task exits;
        /api/job/<id> serves the raw record."""
        from tony_tpu.history.writer import write_final_status

        now = int(time.time() * 1000)
        job_dir = _make_job(tmp_path, "application_3_0001", now,
                            status="FAILED")
        write_final_status(job_dir, {
            "state": "FAILED",
            "stats": {
                "sessions_run": 2,
                "tasks_failed": 1,
                "heartbeat_missed_tasks": ["worker:1"],
                "wall_ms": 61_500,
            },
            "slices": {"worker": {
                "accelerator_type": "v5litepod-16", "num_slices": 2,
                "hosts_per_slice": 4, "chips_per_slice": 16,
            }},
            "tasks": [
                {"id": "worker:0", "exit_code": 0},
                {"id": "worker:1", "exit_code": 1},
            ],
        })
        server = HistoryServer(str(tmp_path), port=0)
        port = server.serve_background()
        try:
            base = f"http://localhost:{port}"
            page = urllib.request.urlopen(
                f"{base}/job/application_3_0001"
            ).read().decode()
            for needle in ("FAILED", "sessions run", ">2<", "tasks failed",
                           "worker:1", "61.5 s", "v5litepod-16",
                           "worker:0"):
                assert needle in page, needle
            # jobs table links to the per-job page
            index = urllib.request.urlopen(f"{base}/").read().decode()
            assert "/job/application_3_0001" in index

            api = json.loads(urllib.request.urlopen(
                f"{base}/api/job/application_3_0001"
            ).read())
            assert api["stats"]["sessions_run"] == 2
            try:
                urllib.request.urlopen(f"{base}/job/application_9_9")
                assert False, "expected 404"
            except urllib.error.HTTPError as e:
                assert e.code == 404
        finally:
            server.stop()

    def test_job_page_timeline_metrics_and_tensorboard(self, tmp_path):
        """The observability additions to the per-job page: the lifecycle
        timeline from events.jsonl, the final aggregated metric summary,
        and the persisted TensorBoard link (previously the URL lived only
        in coordinator memory); /api/events serves the raw timeline."""
        from tony_tpu.history.writer import (
            write_events_file,
            write_final_status,
        )

        now = int(time.time() * 1000)
        job_dir = _make_job(tmp_path, "application_6_0001", now)
        write_final_status(job_dir, {
            "state": "SUCCEEDED",
            "stats": {"sessions_run": 1, "tasks_failed": 0, "wall_ms": 100},
            "tensorboard_url": "http://tb-host:6006",
            "metrics": {
                "heartbeats": {"worker:0": 9},
                "tasks": {"worker:0": {
                    "counters": {"train_steps_total": 5},
                    "gauges": {"loss": 0.25},
                }},
            },
        })
        write_events_file(job_dir, [
            {"ts_ms": now, "kind": "task_registered", "task": "worker:0"},
            {"ts_ms": now + 10, "kind": "rendezvous_released", "tasks": 1},
            {"ts_ms": now + 20, "kind": "final_status",
             "state": "SUCCEEDED"},
        ])
        server = HistoryServer(str(tmp_path), port=0)
        port = server.serve_background()
        try:
            base = f"http://localhost:{port}"
            page = urllib.request.urlopen(
                f"{base}/job/application_6_0001"
            ).read().decode()
            for needle in ("Timeline", "rendezvous_released",
                           "Final metrics", "train_steps_total",
                           "http://tb-host:6006"):
                assert needle in page, needle
            api = json.loads(urllib.request.urlopen(
                f"{base}/api/events/application_6_0001"
            ).read())
            assert [e["kind"] for e in api] == [
                "task_registered", "rendezvous_released", "final_status",
            ]
            try:
                urllib.request.urlopen(f"{base}/api/events/application_9_9")
                assert False, "expected 404"
            except urllib.error.HTTPError as e:
                assert e.code == 404
        finally:
            server.stop()

    def test_job_supplied_tensorboard_url_scheme_gated(self, tmp_path):
        """register_tensorboard_url is job-controlled: a javascript: URL
        must render as text, never as a clickable link in the history
        server's origin."""
        from tony_tpu.history.writer import write_final_status

        now = int(time.time() * 1000)
        job_dir = _make_job(tmp_path, "application_6_0002", now)
        write_final_status(job_dir, {
            "state": "SUCCEEDED",
            "tensorboard_url": "javascript:alert(1)",
        })
        server = HistoryServer(str(tmp_path), port=0)
        port = server.serve_background()
        try:
            page = urllib.request.urlopen(
                f"http://localhost:{port}/job/application_6_0002"
            ).read().decode()
            assert "javascript:alert(1)" in page  # visible as text
            assert "href='javascript" not in page and \
                   'href="javascript' not in page
        finally:
            server.stop()

    def test_secrets_redacted_in_history_and_responses(self, tmp_path):
        """ADVICE r1 (medium): the history path must never expose
        tony.secret.key — anyone reading it could authenticate to a live
        job's RPC. Redacted at write time AND at serve time."""
        now = int(time.time() * 1000)
        job_dir = setup_job_dir(str(tmp_path), "application_3_0001", now)
        conf = TonyConfiguration()
        conf.set("tony.secret.key", "hunter2")
        write_config_file(job_dir, conf)
        create_history_file(
            job_dir, JobMetadata.new("application_3_0001", now, "SUCCEEDED")
        )
        on_disk = (job_dir / "config.json").read_text()
        assert "hunter2" not in on_disk

        # serve-time defense in depth: plant an unredacted legacy config
        legacy = json.loads(on_disk)
        legacy["tony.secret.key"] = "hunter2"
        (job_dir / "config.json").write_text(json.dumps(legacy))
        server = HistoryServer(str(tmp_path), port=0)
        port = server.serve_background()
        try:
            body = urllib.request.urlopen(
                f"http://localhost:{port}/api/config/application_3_0001"
            ).read().decode()
            assert "hunter2" not in body and "<redacted>" in body
        finally:
            server.stop()

    def test_shell_env_values_redacted_names_kept(self):
        """--shell_env values routinely carry tokens the key-name heuristic
        can't see (HF_TOKEN=...); names stay browsable, values do not."""
        from tony_tpu.history.writer import redact_config

        out = redact_config({
            "tony.application.shell-env": "HF_TOKEN=supersecret,MODE=fast",
            "tony.worker.env": "API_KEY=abc",
            "tony.application.name": "keepme",
        })
        assert "supersecret" not in str(out) and "abc" not in str(out)
        assert out["tony.application.shell-env"].startswith("HF_TOKEN=<redacted>")
        assert out["tony.application.name"] == "keepme"

    def test_binds_localhost_by_default(self, tmp_path):
        server = HistoryServer(str(tmp_path), port=0)
        assert server.httpd.server_address[0] == "127.0.0.1"
        server.stop()

    def test_from_conf_port_selection(self, tmp_path):
        from tony_tpu.conf import keys
        import pytest

        conf = TonyConfiguration()
        conf.set(keys.K_HISTORY_LOCATION, str(tmp_path))
        with pytest.raises(ValueError, match="disabled"):
            HistoryServer.from_conf(conf)  # default http.port=disabled
        conf.set(keys.K_HTTP_PORT, "0")
        server = HistoryServer.from_conf(conf)
        assert server.scheme == "http"
        server.stop()

    def test_https_with_pem_pair(self, tmp_path):
        """tony.https.cert/key serve TLS (keystore analogue,
        TonyConfigurationKeys.java:41-63)."""
        import ssl
        import subprocess

        from tony_tpu.conf import keys

        cert, key = tmp_path / "c.pem", tmp_path / "k.pem"
        subprocess.run(
            ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
             "-keyout", str(key), "-out", str(cert), "-days", "1",
             "-subj", "/CN=localhost"],
            check=True, capture_output=True,
        )
        now = int(time.time() * 1000)
        _make_job(tmp_path / "hist", "application_4_0001", now)
        conf = TonyConfiguration()
        conf.set(keys.K_HISTORY_LOCATION, str(tmp_path / "hist"))
        conf.set(keys.K_HTTPS_PORT, 0)
        conf.set(keys.K_HTTPS_CERT, str(cert))
        conf.set(keys.K_HTTPS_KEY, str(key))
        server = HistoryServer.from_conf(conf)
        assert server.scheme == "https"
        port = server.serve_background()
        try:
            ctx = ssl.create_default_context()
            ctx.check_hostname = False
            ctx.verify_mode = ssl.CERT_NONE
            body = urllib.request.urlopen(
                f"https://localhost:{port}/api/jobs", context=ctx
            ).read()
            assert b"application_4_0001" in body
        finally:
            server.stop()
