"""Observability subsystem tests: the metrics registry + Prometheus
rendering, the structured event log, trace spans + the per-job Chrome
trace merge, the coordinator-side aggregator, the heartbeat metrics
piggyback over real RPC, and the mini-cluster e2e that drives the whole
telemetry plane through a 2-task job (jax-free fixture)."""

import json
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import pytest

from tony_tpu import constants
from tony_tpu.conf import keys
from tony_tpu.coordinator.app_master import TonyCoordinator
from tony_tpu.coordinator.backend import LocalProcessBackend
from tony_tpu.coordinator.session import SessionStatus
from tony_tpu.mini import MiniTonyCluster
from tony_tpu.observability import events as obs_events
from tony_tpu.observability import metrics as obs_metrics
from tony_tpu.observability import trace as obs_trace
from tony_tpu.observability.aggregator import (
    MetricsAggregator,
    ObservabilityHttpServer,
)

FIXTURES = Path(__file__).resolve().parent / "fixtures"
REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# metrics.py
# ---------------------------------------------------------------------------
class TestMetricsRegistry:
    def test_counter_gauge_histogram_roundtrip(self):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("requests_total").inc()
        reg.counter("requests_total").inc(2)
        reg.gauge("loss").set(0.5)
        h = reg.histogram("step_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        snap = reg.snapshot()
        assert snap["counters"]["requests_total"] == 3
        assert snap["gauges"]["loss"] == 0.5
        hist = snap["histograms"]["step_seconds"]
        assert hist["count"] == 3 and hist["sum"] == pytest.approx(5.55)
        assert hist["buckets"] == [[0.1, 1], [1.0, 2]]  # cumulative

    def test_name_validation(self):
        reg = obs_metrics.MetricsRegistry()
        with pytest.raises(ValueError, match="snake_case"):
            reg.counter("Bad-Name")
        with pytest.raises(ValueError, match="_total"):
            reg.counter("requests")
        with pytest.raises(ValueError, match="unit suffix"):
            reg.gauge("step_time")  # time without _ms/_seconds
        with pytest.raises(ValueError, match="unit suffix"):
            reg.gauge("memory_used")
        reg.gauge("step_time_ms")  # legal
        reg.counter("ticks_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("ticks_total")  # kind conflict

    def test_counter_cannot_decrease(self):
        reg = obs_metrics.MetricsRegistry()
        with pytest.raises(ValueError, match="decrease"):
            reg.counter("ticks_total").inc(-1)

    def test_report_drives_step_counter_by_delta(self):
        reg = obs_metrics.MetricsRegistry()
        reg.report(step=3, loss=1.0)
        reg.report(step=5, loss=0.5)
        reg.report(step=5, loss=0.4)  # no progress: counter holds
        snap = reg.snapshot()
        assert snap["counters"]["train_steps_total"] == 5
        assert snap["gauges"]["train_step"] == 5
        assert snap["gauges"]["loss"] == 0.4

    def test_publish_and_load_snapshot(self, tmp_path):
        path = tmp_path / "m.json"
        reg = obs_metrics.MetricsRegistry(
            publish_path=path, publish_min_interval_s=0.0
        )
        reg.report(step=1, loss=2.0)
        snap = obs_metrics.load_snapshot_file(path)
        assert snap is not None and snap["gauges"]["loss"] == 2.0
        # corrupt file -> None, never raises (heartbeats must not fail)
        path.write_text("{not json")
        assert obs_metrics.load_snapshot_file(path) is None
        assert obs_metrics.load_snapshot_file(tmp_path / "nope") is None

    def test_prometheus_rendering(self):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("requests_total").inc(7)
        reg.gauge("loss").set(1.5)
        text = reg.to_prometheus()
        assert "# TYPE requests_total counter" in text
        assert "requests_total 7" in text
        assert "loss 1.5" in text
        labeled = obs_metrics.render_prometheus(
            reg.snapshot(), labels={"task": 'work"er'}
        )
        assert 'requests_total{task="work\\"er"} 7' in labeled

    def test_sanitize_metric_name(self):
        assert obs_metrics.sanitize_metric_name("%fusion.1") == "fusion_1"
        assert obs_metrics.sanitize_metric_name("") == "unnamed"


# ---------------------------------------------------------------------------
# events.py
# ---------------------------------------------------------------------------
class TestEventLog:
    def test_emit_order_and_sink(self, tmp_path):
        path = tmp_path / "events.jsonl"
        log = obs_events.EventLog(sink=obs_events.jsonl_file_sink(path))
        log.emit(obs_events.TASK_REGISTERED, task="worker:0", session=1)
        log.emit(obs_events.RENDEZVOUS_RELEASED, session=1, tasks=2)
        assert log.kinds() == ["task_registered", "rendezvous_released"]
        lines = path.read_text().splitlines()
        assert len(lines) == 2
        assert json.loads(lines[0])["task"] == "worker:0"

    def test_sink_failure_never_raises(self):
        def explode(event):
            raise OSError("disk gone")

        log = obs_events.EventLog(sink=explode)
        log.emit("task_finished")  # must not raise
        assert log.kinds() == ["task_finished"]

    def test_parse_jsonl_skips_torn_lines(self):
        text = '{"kind": "a"}\n{"kind": "b"\nnot json\n{"kind": "c"}\n'
        events = obs_events.parse_jsonl(text)
        assert [e["kind"] for e in events] == ["a", "c"]


# ---------------------------------------------------------------------------
# trace.py
# ---------------------------------------------------------------------------
class TestTrace:
    def test_span_exports_chrome_events(self):
        tracer = obs_trace.Tracer(trace_id="abc123", proc="coordinator")
        with tracer.span("prepare", session=1):
            pass
        events = tracer.to_chrome_events()
        # metadata row + the span
        assert events[0]["ph"] == "M"
        span = events[-1]
        assert span["ph"] == "X" and span["name"] == "prepare"
        assert span["args"]["trace_id"] == "abc123"
        assert span["args"]["proc"] == "coordinator"
        assert span["dur"] >= 1

    def test_span_end_idempotent_and_attrs(self):
        tracer = obs_trace.Tracer()
        span = tracer.begin("monitor")
        span.set(status="SUCCEEDED")
        span.end()
        span.end()
        spans = [e for e in tracer.to_chrome_events() if e["ph"] == "X"]
        assert len(spans) == 1
        assert spans[0]["args"]["status"] == "SUCCEEDED"

    def test_merge_job_trace_includes_executor_files(self, tmp_path):
        coord = obs_trace.Tracer(trace_id="t1", proc="coordinator")
        with coord.span("session"):
            pass
        ex = obs_trace.Tracer(trace_id="t1", proc="executor:worker:0")
        with ex.span("user_process"):
            pass
        ex.write_jsonl(tmp_path / "trace-worker-0.jsonl")
        # a torn tail must not hide the rest
        (tmp_path / "trace-broken.jsonl").write_text('{"name": "x"\n')
        doc = obs_trace.merge_job_trace(coord, tmp_path)
        procs = {
            e["args"]["proc"] for e in doc["traceEvents"]
            if e.get("ph") == "X"
        }
        assert procs == {"coordinator", "executor:worker:0"}
        assert doc["otherData"]["trace_id"] == "t1"

    def test_ambient_trace_id_env(self, monkeypatch):
        monkeypatch.setenv(constants.TONY_TRACE_ID, "feedbeef")
        assert obs_trace.Tracer().trace_id == "feedbeef"
        monkeypatch.delenv(constants.TONY_TRACE_ID)
        assert obs_trace.Tracer().trace_id != ""

    def test_span_names_its_parent_and_lies_inside_it(self):
        tracer = obs_trace.Tracer()
        with tracer.span("outer") as outer:
            with tracer.span("inner") as inner:
                begun = tracer.begin("begun")
            begun.end()             # ended outside where it began
        after = tracer.begin("after")
        after.end()
        assert outer.parent_id is None and after.parent_id is None
        assert inner.parent_id == outer.span_id
        assert begun.parent_id == inner.span_id
        assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
        assert inner.dur_ns == inner.end_ns - inner.start_ns
        args = {e["name"]: e["args"] for e in tracer.to_chrome_events()
                if e["ph"] == "X"}
        assert args["inner"]["parent_id"] == args["outer"]["span_id"]
        assert len({a["span_id"] for a in args.values()}) == 4

    def test_parent_is_per_thread(self):
        tracer = obs_trace.Tracer()
        seen = []

        def other():
            with tracer.span("elsewhere") as s:
                seen.append(s.parent_id)

        with tracer.span("here"):
            t = threading.Thread(target=other)
            t.start()
            t.join(timeout=10)
        assert seen == [None]

    def test_record_after_the_fact(self):
        tracer = obs_trace.Tracer()
        t0 = obs_trace.now_ns()
        with tracer.span("open"):
            span_id = tracer.record("tony:request.queue", t0 - 5_000_000,
                                    t0, request="req-7")
        event = next(e for e in tracer.to_chrome_events()
                     if e["name"] == "tony:request.queue")
        assert event["ts"] == (t0 - 5_000_000) // 1000
        assert event["dur"] == 5000
        assert event["args"]["span_id"] == span_id
        assert event["args"]["parent_id"] is None   # not the open span's
        assert event["args"]["request"] == "req-7"

    def test_ring_keeps_the_newest_within_its_bound(self, monkeypatch):
        monkeypatch.setattr(obs_trace, "RING_SPANS", 64)
        tracer = obs_trace.Tracer()
        for i in range(640):
            with tracer.span("tick", i=i):
                pass
        assert len(tracer) == 64
        spans = [e for e in tracer.to_chrome_events() if e["ph"] == "X"]
        assert [e["args"]["i"] for e in spans] == list(range(576, 640))

    def test_clock_is_monotonic_and_on_the_epoch(self):
        a = obs_trace.now_ns()
        wall = time.time_ns()
        perf = time.perf_counter()
        b = obs_trace.now_ns()
        assert a <= b
        # anchored once: off the wall clock only by its slew since import
        assert abs(a - wall) < 2_000_000_000
        assert a <= obs_trace.perf_counter_to_ns(perf) <= b

    def test_span_enters_the_profiler_annotation(self, monkeypatch):
        calls = []

        class Annotation:
            def __init__(self, name, **metadata):
                self.key = (name, metadata)

            def __enter__(self):
                calls.append(("enter", self.key))

            def __exit__(self, *exc):
                calls.append(("exit", self.key))

        monkeypatch.setattr(obs_trace, "_annotate", Annotation)
        tracer = obs_trace.Tracer()
        with tracer.span("tony:engine.step", iteration=3) as s:
            tracer.begin("not-entered").end()
        key = ("tony:engine.step", {"span_id": s.span_id})
        assert calls == [("enter", key), ("exit", key)]

    def test_imports_and_records_without_jax(self):
        code = (
            "import sys\n"
            "from tony_tpu import observability\n"
            "with observability.span('load') as s:\n"
            "    pass\n"
            "assert s.end_ns >= s.start_ns\n"
            "assert 'jax' not in sys.modules, 'observability pulled in jax'\n"
        )
        proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                              capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr


# ---------------------------------------------------------------------------
# aggregator.py
# ---------------------------------------------------------------------------
def _snap(loss, step=1, ts=None):
    return {
        "ts_ms": ts or int(time.time() * 1000),
        "counters": {"train_steps_total": step},
        "gauges": {"loss": loss},
        "histograms": {},
    }


class TestAggregator:
    def test_ingest_and_prometheus(self):
        agg = MetricsAggregator()
        agg.registry.counter("sessions_started_total").inc()
        agg.ingest("worker:0", _snap(0.5, ts=1))
        agg.ingest("worker:1", None)  # plain liveness ping
        text = agg.prometheus_text()
        assert "sessions_started_total 1" in text
        assert 'tony_task_heartbeats_total{task="worker:0"} 1' in text
        assert 'tony_task_heartbeats_total{task="worker:1"} 1' in text
        assert 'loss{task="worker:0"} 0.5' in text
        assert 'train_steps_total{task="worker:0"} 1' in text
        # TYPE headers are emitted once however many tasks share a name
        assert text.count("# TYPE tony_task_heartbeats_total counter") == 1

    def test_series_bounded_and_keyed(self):
        agg = MetricsAggregator(series_limit=3)
        for i in range(5):
            agg.ingest("worker:0", _snap(float(i), ts=i + 1))
        series = agg.to_json()["series"]["worker:0:loss"]
        assert [v for _, v in series] == [2.0, 3.0, 4.0]  # bounded

    def test_reset_tasks_keeps_heartbeat_totals(self):
        agg = MetricsAggregator()
        agg.ingest("worker:0", _snap(0.5))
        agg.reset_tasks()
        agg.ingest("worker:0", None)
        text = agg.prometheus_text()
        assert 'tony_task_heartbeats_total{task="worker:0"} 2' in text
        assert "loss{" not in text  # dead session's gauges dropped

    def test_summary_compact(self):
        agg = MetricsAggregator()
        agg.ingest("worker:0", _snap(0.25, step=4))
        summary = agg.summary()
        assert summary["tasks"]["worker:0"]["gauges"]["loss"] == 0.25
        assert summary["heartbeats"]["worker:0"] == 1

    def test_malformed_snapshot_families_normalized(self):
        """The snapshot crosses a trust boundary (user-writable file →
        executor → RPC): null/garbage families must not crash summary()
        in stop() (losing the terminal record) or the /metrics render."""
        agg = MetricsAggregator()
        agg.ingest("worker:0", {
            "ts_ms": "yesterday",
            "counters": None,
            "gauges": {"loss": "not-a-number", "ok_ratio": 0.5},
            "histograms": {"h_seconds": None,
                           "g_seconds": {"count": 1, "sum": 2.0,
                                         "buckets": [[1.0, 1], "junk"]}},
        })
        summary = agg.summary()
        assert summary["tasks"]["worker:0"]["counters"] == {}
        assert summary["tasks"]["worker:0"]["gauges"] == {"ok_ratio": 0.5}
        text = agg.prometheus_text()
        assert 'ok_ratio{task="worker:0"} 0.5' in text
        assert 'g_seconds_count{task="worker:0"} 1' in text

    def test_nan_loss_stays_valid_json(self):
        """A diverged loop reporting loss=nan is exactly when operators
        read these views: the JSON surfaces must stay strictly parseable
        (null, not the bare NaN token), while Prometheus keeps NaN."""
        agg = MetricsAggregator()
        agg.ingest("worker:0", _snap(float("nan")))
        summary = agg.summary()
        assert summary["tasks"]["worker:0"]["gauges"]["loss"] is None
        assert "NaN" not in json.dumps(summary)
        assert 'loss{task="worker:0"} NaN' in agg.prometheus_text()
        server = ObservabilityHttpServer(agg, host="127.0.0.1")
        port = server.serve_background()
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/api/metrics"
            ).read().decode()
            assert "NaN" not in body
            json.loads(body)  # strictly parseable
        finally:
            server.stop()

    def test_http_endpoints(self):
        agg = MetricsAggregator()
        agg.ingest("worker:0", _snap(0.5))
        events = obs_events.EventLog()
        events.emit(obs_events.TASK_REGISTERED, task="worker:0")
        tracer = obs_trace.Tracer(trace_id="t9", proc="coordinator")
        with tracer.span("prepare"):
            pass
        server = ObservabilityHttpServer(
            agg, events=events, tracer=tracer, host="127.0.0.1"
        )
        port = server.serve_background()
        base = f"http://127.0.0.1:{port}"
        try:
            text = urllib.request.urlopen(f"{base}/metrics").read().decode()
            assert 'loss{task="worker:0"} 0.5' in text
            api = json.loads(
                urllib.request.urlopen(f"{base}/api/metrics").read()
            )
            assert api["tasks"]["worker:0"]["gauges"]["loss"] == 0.5
            ev = json.loads(
                urllib.request.urlopen(f"{base}/api/events").read()
            )
            assert ev[0]["kind"] == "task_registered"
            tr = json.loads(
                urllib.request.urlopen(f"{base}/api/trace").read()
            )
            assert tr["otherData"]["trace_id"] == "t9"
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"{base}/nope")
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# heartbeat piggyback over real RPC
# ---------------------------------------------------------------------------
class _HbApp:
    """Heartbeat-only impl mirroring the coordinator's optional-metrics
    signature."""

    def __init__(self):
        self.pings = []

    def task_executor_heartbeat(self, task_id, session_id, metrics=None):
        self.pings.append((task_id, session_id, metrics))


class TestHeartbeatMetricsRpc:
    @pytest.fixture()
    def served(self):
        from tony_tpu.rpc.server import ApplicationRpcServer

        app = _HbApp()
        server = ApplicationRpcServer(
            app, host="127.0.0.1", port_range=(20000, 25000)
        )
        server.start()
        yield app, server
        server.stop()

    def test_metrics_ride_the_heartbeat(self, served):
        from tony_tpu.rpc.client import ApplicationRpcClient

        app, server = served
        c = ApplicationRpcClient("127.0.0.1", server.port)
        c.task_executor_heartbeat("worker:0", "1")
        c.task_executor_heartbeat("worker:0", "1", metrics=_snap(0.5))
        assert app.pings[0][2] is None  # optional arg stays off the wire
        assert app.pings[1][2]["gauges"]["loss"] == 0.5

    def test_dispatch_accepts_omitted_optional_arg(self, served):
        _, server = served
        ok = server.dispatch({
            "method": "task_executor_heartbeat",
            "args": {"task_id": "w:0", "session_id": "1"},
        })
        assert ok["ok"] is True
        bad = server.dispatch({
            "method": "task_executor_heartbeat",
            "args": {"metrics": {}},  # required args missing
        })
        assert bad["ok"] is False and "expects args" in bad["error"]

    def test_trace_metadata_reaches_handler(self, served):
        from tony_tpu.rpc.client import ApplicationRpcClient

        app, server = served
        seen = []
        orig = app.task_executor_heartbeat

        def spy(task_id, session_id, metrics=None):
            seen.append(obs_trace.current_rpc_trace())
            return orig(task_id, session_id, metrics)

        app.task_executor_heartbeat = spy
        c = ApplicationRpcClient(
            "127.0.0.1", server.port, trace_id="cafe01"
        )
        c.task_executor_heartbeat("worker:0", "1")
        assert seen == ["cafe01"]


# ---------------------------------------------------------------------------
# mini-cluster e2e: the acceptance scenario
# ---------------------------------------------------------------------------
def test_mini_cluster_observability_e2e(tmp_path):
    """2-task jax-free job: the coordinator's /metrics endpoint serves
    Prometheus text with per-task heartbeat and step counters WHILE the
    job runs; events.jsonl lands in history with the ordered lifecycle
    sequence; and the exported Chrome trace contains spans from the
    coordinator, an executor, and the user process sharing one trace
    id."""
    cluster = MiniTonyCluster(tmp_path)
    conf = cluster.base_conf()
    conf.set(keys.K_EXECUTES, str(FIXTURES / "report_metrics.py"))
    conf.set(keys.K_PYTHON_BINARY, sys.executable)
    conf.set(keys.instances_key("worker"), 2)
    conf.set(keys.instances_key("ps"), 0)
    conf.set(keys.K_TASK_HEARTBEAT_INTERVAL_MS, 150)
    conf.set(keys.K_SHELL_ENV, "LINGER_S=4.0")

    app_id = "application_mini_obs1"
    app_dir = cluster.staging_dir / app_id
    app_dir.mkdir(parents=True)
    conf.write_final(app_dir / constants.TONY_FINAL_CONF)
    coordinator = TonyCoordinator(
        conf, app_dir, app_id=app_id,
        backend=LocalProcessBackend(app_dir / "logs"),
    )
    result: list[SessionStatus] = []
    t = threading.Thread(
        target=lambda: result.append(coordinator.run()), daemon=True
    )
    cluster._live.append(coordinator)
    t.start()
    try:
        # -- live: scrape /metrics while the workers linger ---------------
        deadline = time.monotonic() + 60
        addr_file = app_dir / "coordinator.http"
        while not addr_file.is_file() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert addr_file.is_file(), "coordinator.http never advertised"
        addr = addr_file.read_text().strip()
        text = ""
        wanted = (
            'tony_task_heartbeats_total{task="worker:0"}',
            'tony_task_heartbeats_total{task="worker:1"}',
            'train_steps_total{task="worker:0"}',
            'train_steps_total{task="worker:1"}',
            'loss{task="worker:0"}',
        )
        while time.monotonic() < deadline:
            try:
                text = urllib.request.urlopen(
                    f"http://{addr}/metrics", timeout=5
                ).read().decode()
            except OSError:
                time.sleep(0.1)
                continue
            if all(n in text for n in wanted):
                break
            time.sleep(0.1)
        for needle in wanted:
            assert needle in text, f"{needle!r} never appeared in /metrics"
        assert "# TYPE train_steps_total counter" in text
    finally:
        t.join(timeout=120)
    assert result and result[0] is SessionStatus.SUCCEEDED, (
        coordinator.session.diagnostics if coordinator.session else "no run"
    )

    # -- events.jsonl in history: the ordered lifecycle sequence ----------
    event_files = list(cluster.history_dir.rglob("events.jsonl"))
    assert len(event_files) == 1
    events = obs_events.parse_jsonl(event_files[0].read_text())
    kinds = [e["kind"] for e in events]
    for kind in ("job_submitted", "session_started", "task_scheduled"):
        assert kind in kinds
    order = [
        kinds.index("task_registered"),
        kinds.index("rendezvous_released"),
        kinds.index("task_finished"),
        kinds.index("final_status"),
    ]
    assert order == sorted(order) and len(set(order)) == 4
    # RPC metadata propagation: the registration event carries the same
    # trace id the coordinator minted.
    reg_event = events[kinds.index("task_registered")]
    assert reg_event["trace_id"] == coordinator.tracer.trace_id

    # -- Chrome trace: coordinator + executor + user spans, one trace id --
    trace_files = list(cluster.history_dir.rglob("trace.json"))
    assert len(trace_files) == 1
    doc = json.loads(trace_files[0].read_text())
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    trace_ids = {s["args"]["trace_id"] for s in spans}
    assert trace_ids == {coordinator.tracer.trace_id}
    procs = {s["args"]["proc"] for s in spans}
    assert "coordinator" in procs
    assert any(p.startswith("executor:worker:") for p in procs)
    assert any(p.startswith("user:worker:") for p in procs)
    names = {s["name"] for s in spans}
    for name in ("prepare", "schedule_tasks", "rendezvous_wait",
                 "rendezvous", "user_process", "fixture_train"):
        assert name in names, f"span {name!r} missing from job trace"

    # -- final-status carries the aggregated metric summary ---------------
    final = json.loads((app_dir / "final-status.json").read_text())
    assert final["trace_id"] == coordinator.tracer.trace_id
    tasks = final["metrics"]["tasks"]
    assert tasks["worker:0"]["gauges"]["loss"] == pytest.approx(0.2)
    assert final["metrics"]["heartbeats"]["worker:0"] >= 1

    # -- CLI: tony events / tony metrics over the same artifacts ----------
    from tony_tpu.client import cli

    rc = cli.main([
        "events", app_id, "--staging-location", str(cluster.staging_dir),
        "--history-location", str(cluster.history_dir),
    ])
    assert rc == 0
    rc = cli.main([
        "metrics", app_id, "--staging-location", str(cluster.staging_dir),
        "--history-location", str(cluster.history_dir),
    ])
    assert rc == 0


def test_observability_port_can_be_disabled(tmp_path):
    cluster = MiniTonyCluster(tmp_path)
    conf = cluster.base_conf()
    conf.set(keys.K_EXECUTES, str(FIXTURES / "exit_0.py"))
    conf.set(keys.K_PYTHON_BINARY, sys.executable)
    conf.set(keys.instances_key("worker"), 1)
    conf.set(keys.instances_key("ps"), 0)
    conf.set(keys.K_AM_HTTP_PORT, "disabled")
    status, coord = cluster.run_job(conf)
    assert status is SessionStatus.SUCCEEDED
    assert coord.http_server is None
    assert not (coord.app_dir / "coordinator.http").exists()
    # The rest of the telemetry plane still runs: events + trace persist.
    assert (coord.app_dir / "events.jsonl").is_file()
    assert (coord.app_dir / "trace.json").is_file()


# ---------------------------------------------------------------------------
# histogram_quantile edge cases (the single-sample clamp)
# ---------------------------------------------------------------------------
class TestHistogramQuantileEdgeCases:
    def test_empty_histogram_is_none(self):
        assert obs_metrics.histogram_quantile(
            {"count": 0, "buckets": []}, 0.95
        ) is None
        assert obs_metrics.histogram_quantile({}, 0.5) is None

    def test_single_sample_clamps_to_observed_max(self):
        h = obs_metrics.Histogram("x_ms", buckets=(5.0, 10.0))
        h.observe(3.0)
        snap = h.snapshot()
        assert snap["max"] == 3.0
        # without the clamp this reads as the 5.0 bucket bound — a p95
        # over one 3 ms sample must be 3 ms, not 5 ms
        assert obs_metrics.histogram_quantile(snap, 0.95) == 3.0
        assert obs_metrics.histogram_quantile(snap, 0.5) == 3.0

    def test_all_in_overflow_bucket_reads_max_not_mean(self):
        h = obs_metrics.Histogram("x_ms", buckets=(5.0, 10.0))
        h.observe(50.0)
        h.observe(70.0)
        snap = h.snapshot()
        # both samples are past the last bound: the readout is the
        # observed max (70), not the mean (60) and not infinite
        assert obs_metrics.histogram_quantile(snap, 0.95) == 70.0

    def test_snapshot_without_max_keeps_bucket_bound(self):
        # aggregated/legacy snapshots that carry no "max" keep the
        # upper-bound behavior (and the mean fallback past the end)
        snap = {"count": 1, "sum": 3.0, "buckets": [[5.0, 1], [10.0, 1]]}
        assert obs_metrics.histogram_quantile(snap, 0.95) == 5.0
        snap = {"count": 2, "sum": 120.0, "buckets": [[5.0, 0], [10.0, 0]]}
        assert obs_metrics.histogram_quantile(snap, 0.95) == 60.0

    def test_max_rides_through_aggregator_normalization(self):
        agg = MetricsAggregator()
        h = obs_metrics.Histogram("x_ms", buckets=(5.0,))
        h.observe(3.0)
        agg.ingest("w:0", {"histograms": {"x_ms": h.snapshot()}})
        norm = agg.to_json()["tasks"]["w:0"]["histograms"]["x_ms"]
        assert norm["max"] == 3.0
        assert obs_metrics.histogram_quantile(norm, 0.95) == 3.0


# ---------------------------------------------------------------------------
# stepstats.py — the per-step anatomy recorder
# ---------------------------------------------------------------------------
from tony_tpu.observability import stepstats as stepstats_mod  # noqa: E402


class _TinyCfg:
    """Transformer-shaped config for the analytic flops model."""
    d_model = 64
    n_layers = 2
    vocab_size = 512
    n_heads = 4
    head_dim = 16
    n_kv_heads = 2
    d_ff = 256
    dtype = "float32"


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


class TestStepStats:
    def _stats(self, reg, clock, **kw):
        kw.setdefault("cfg", _TinyCfg())
        kw.setdefault("peak_flops", 1e12)
        kw.setdefault("calibrate", False)
        kw.setdefault("enabled", True)
        return stepstats_mod.StepStats(
            registry=reg, clock=clock, **kw
        )

    def test_phases_are_exclusive_and_sum_to_wall(self):
        reg = obs_metrics.MetricsRegistry()
        clock = _Clock()
        stats = self._stats(reg, clock)
        stats.step_begin((4, 33))       # dispatch 1 = trace + compile
        clock.advance(5.0)              # a 5 s compile wall...
        stats.step_begin((4, 33))       # ...dropped, never published
        stats.step_end(0.002)
        clock.advance(0.1)              # one 100 ms step
        stats.step_begin((4, 33))
        g = reg.snapshot()["gauges"]
        phases = {
            p: g[f'tony_step_phase_ms{{phase="{p}"}}']
            for p in stepstats_mod.PHASES
        }
        assert sum(phases.values()) == pytest.approx(100.0, rel=1e-6)
        assert phases["host"] == pytest.approx(2.0)       # the dispatch
        assert phases["compute"] == pytest.approx(98.0)   # residual, no plan
        assert phases["data_wait"] == 0.0 and phases["h2d"] == 0.0
        # MFU: analytic flops over wall × 1 device × pinned peak
        flops = stepstats_mod.model_flops_per_step(_TinyCfg(), 4, 32)
        assert g["tony_mfu"] == pytest.approx(
            flops / (0.1 * 1e12), abs=1e-5  # gauge rounds to 5 decimals
        )
        assert g["tony_model_flops_per_step"] == flops
        # report() rode along: the straggler detector's gauge is fed
        assert g["step_time_ms"] == pytest.approx(100.0)
        assert stats.steps_observed == 1

    def test_wrap_batches_attributes_input_wait(self):
        reg = obs_metrics.MetricsRegistry()
        clock = _Clock()
        stats = self._stats(reg, clock)

        def slow_batches():
            while True:
                clock.advance(0.03)    # 30 ms blocked in next()
                yield (4, 33)

        it = stats.wrap_batches(slow_batches())
        shape = next(it)
        stats.step_begin(shape)        # dispatch 1 = compile
        stats.step_end(0.0)
        shape = next(it)
        clock.advance(0.07)
        stats.step_begin(shape)        # compile interval dropped
        shape = next(it)               # +30 ms data wait
        clock.advance(0.07)            # +70 ms "device" work
        stats.step_begin(shape)
        g = reg.snapshot()["gauges"]
        assert g['tony_step_phase_ms{phase="data_wait"}'] == \
            pytest.approx(30.0, rel=1e-6)
        assert g['tony_step_phase_ms{phase="compute"}'] == \
            pytest.approx(70.0, rel=1e-6)

    def test_disabled_recorder_is_inert(self):
        reg = obs_metrics.MetricsRegistry()
        clock = _Clock()
        stats = self._stats(reg, clock, enabled=False)
        batches = iter([(4, 33)])
        assert stats.wrap_batches(batches) is batches
        stats.step_begin((4, 33))
        clock.advance(0.1)
        stats.step_begin((4, 33))
        assert reg.snapshot()["gauges"] == {}

    def test_classifier_workload_gets_phases_but_no_mfu(self):
        reg = obs_metrics.MetricsRegistry()
        clock = _Clock()
        stats = self._stats(reg, clock, cfg=None, tokens_workload=False,
                            steps_per_call=2)
        stats.step_begin((8, 28, 28, 1))
        clock.advance(0.2)              # compile call — dropped
        stats.step_begin((8, 28, 28, 1))
        clock.advance(0.2)              # 200 ms call = 2 fused steps
        stats.step_begin((8, 28, 28, 1))
        g = reg.snapshot()["gauges"]
        assert g['tony_step_phase_ms{phase="compute"}'] == \
            pytest.approx(100.0)        # per-step, not per-call
        assert "tony_mfu" not in g
        assert stats.steps_observed == 2

    def test_deferred_sizing_uses_builder_global_shape(self):
        """size_from_shapes=False: the dispatch hook's (local) shape is
        ignored — the builder sizes with the assembled GLOBAL batch, the
        multi-process contract make_train_step relies on (hook sees one
        process's shard; MFU/calibration must use the global work)."""
        reg = obs_metrics.MetricsRegistry()
        clock = _Clock()
        stats = self._stats(reg, clock, size_from_shapes=False)
        stats.step_begin((4, 33))       # hook: local shard [4, 33]
        stats.set_workload(8, 32)       # builder: global batch is 8
        clock.advance(0.1)
        stats.step_begin((4, 33))       # compile interval dropped
        clock.advance(0.1)
        stats.step_begin((4, 33))
        g = reg.snapshot()["gauges"]
        flops = stepstats_mod.model_flops_per_step(_TinyCfg(), 8, 32)
        assert g["tony_model_flops_per_step"] == flops
        assert g["tony_mfu"] == pytest.approx(
            flops / (0.1 * 1e12), abs=1e-5
        )

    def test_live_calibration_records_and_publishes_residual(
        self, tmp_path, monkeypatch,
    ):
        from tony_tpu.models import TransformerConfig
        from tony_tpu.parallel import plan as plan_lib

        monkeypatch.setattr(
            plan_lib, "active_cache_dir", lambda: str(tmp_path)
        )
        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=4, n_heads=4, head_dim=8,
            d_ff=64, max_seq=64, dtype="float32", n_kv_heads=2,
        )
        reg = obs_metrics.MetricsRegistry()
        clock = _Clock()
        stats = stepstats_mod.StepStats(
            cfg=cfg, plan=plan_lib.Plan(plan_lib.MeshSpec()),
            registry=reg, clock=clock, peak_flops=1e12,
            calibrate=True, window=2,
        )
        stats.step_begin((4, 17))       # compile
        for _ in range(4):
            clock.advance(0.05)
            stats.step_begin((4, 17))
        table = plan_lib.load_measurements(cache_dir=str(tmp_path))
        assert len(table) == 1
        (bucket,) = table.values()
        assert bucket == {"dp1.pp1.ep1.sp1.tp1":
            pytest.approx(50.0, rel=0.01)}
        g = reg.snapshot()["gauges"]
        assert g['tony_plan_residual{plan="dp1.pp1.ep1.sp1.tp1"}'] == pytest.approx(1.0)

    def test_calibration_failure_never_raises(self, monkeypatch):
        from tony_tpu.parallel import plan as plan_lib

        def boom(*a, **kw):
            raise OSError("cache dir gone")

        monkeypatch.setattr(plan_lib, "record_step_time", boom)
        reg = obs_metrics.MetricsRegistry()
        clock = _Clock()
        stats = stepstats_mod.StepStats(
            cfg=_TinyCfg(), plan=plan_lib.Plan(plan_lib.MeshSpec()),
            registry=reg, clock=clock, peak_flops=1e12,
            calibrate=True, window=1,
        )
        stats.step_begin((4, 33))
        for _ in range(5):
            clock.advance(0.05)
            stats.step_begin((4, 33))   # calibration is telemetry: no raise
        assert stats.steps_observed == 4

    def test_counter_rate_clamps_restart_resets(self):
        assert stepstats_mod.counter_rate(100.0, 110.0, 2.0) == 5.0
        # a task restart resets its process-local counters: the reset
        # must read as zero progress, never a negative rate
        assert stepstats_mod.counter_rate(100.0, 3.0, 2.0) == 0.0
        assert stepstats_mod.counter_rate(1.0, 2.0, 0.0) == 0.0

    def test_view_and_format_roundtrip(self):
        snap = {
            "counters": {
                "train_steps_total": 40,
                'tony_collective_bytes_total{axis="dp"}': 4096.0,
            },
            "gauges": {
                'tony_step_phase_ms{phase="data_wait"}': 60.0,
                'tony_step_phase_ms{phase="h2d"}': 5.0,
                'tony_step_phase_ms{phase="compute"}': 30.0,
                'tony_step_phase_ms{phase="collective"}': 4.0,
                'tony_step_phase_ms{phase="host"}': 1.0,
                "tony_mfu": 0.42,
                'tony_plan_residual{plan="dp2"}': 1.08,
            },
        }
        view = stepstats_mod.stepstats_view({"worker:0": snap,
                                             "worker:1": {"gauges": {}}})
        assert list(view["tasks"]) == ["worker:0"]
        t = view["tasks"]["worker:0"]
        assert t["dominant_phase"] == "data_wait"
        assert t["step_time_ms"] == pytest.approx(100.0)
        assert t["shares"]["data_wait"] == pytest.approx(0.6)
        assert t["mfu"] == 0.42
        assert t["collective_bytes"] == {"dp": 4096.0}
        assert t["residuals"] == {"dp2": 1.08}
        assert view["fleet"]["dominant_phase"] == "data_wait"
        assert view["fleet"]["mfu_median"] == pytest.approx(0.42)
        text = stepstats_mod.format_top("app_1", view, "final")
        assert "DATA_WAIT" in text and "worker:0" in text
        assert "0.4200" in text and "data_wait" in text


class TestAggregatorStepstats:
    def test_task_restart_resets_do_not_go_negative(self):
        """A task that restarts mid-session resets its process-local
        counters; the gauge series stays a monotonic-ts timeline and
        stepstats-derived rates clamp at zero instead of amplifying
        the drop."""
        agg = MetricsAggregator()
        agg.ingest("w:0", {"ts_ms": 1_000,
                           "counters": {"train_steps_total": 100},
                           "gauges": {"step_time_ms": 5.0}})
        # restart: counters reset, wall clock moved on
        agg.ingest("w:0", {"ts_ms": 3_000,
                           "counters": {"train_steps_total": 3},
                           "gauges": {"step_time_ms": 7.0}})
        doc = agg.to_json()
        series = doc["series"]["w:0:step_time_ms"]
        assert [ts for ts, _ in series] == sorted(
            ts for ts, _ in series
        )
        first = doc["tasks"]["w:0"]["counters"]["train_steps_total"]
        assert first == 3  # latest snapshot shows the reset plainly
        rate = stepstats_mod.counter_rate(100, 3, 2.0)
        assert rate == 0.0

    def test_stepstats_json_and_api_endpoint(self):
        agg = MetricsAggregator()
        agg.ingest("w:0", {"ts_ms": 1, "counters": {}, "gauges": {
            'tony_step_phase_ms{phase="data_wait"}': 1.0,
            'tony_step_phase_ms{phase="h2d"}': 0.0,
            'tony_step_phase_ms{phase="compute"}': 8.0,
            'tony_step_phase_ms{phase="collective"}': 0.5,
            'tony_step_phase_ms{phase="host"}': 0.5,
            "tony_mfu": 0.33,
        }})
        view = agg.stepstats_json()
        assert view["tasks"]["w:0"]["dominant_phase"] == "compute"
        assert view["fleet"]["mfu_median"] == pytest.approx(0.33)

        server = ObservabilityHttpServer(agg, port=0)
        server.serve_background()
        try:
            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/api/stepstats", timeout=5
            ).read())
            assert doc["tasks"]["w:0"]["mfu"] == pytest.approx(0.33)
            assert doc["fleet"]["tasks"] == 1
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# step-anatomy mini-cluster e2e (the PR-10 acceptance scenario)
# ---------------------------------------------------------------------------
def test_mini_cluster_stepstats_training_e2e(tmp_path, capsys):
    """A REAL training job (examples/lm_train.py through make_train_step)
    publishes its step anatomy end to end: tony_step_phase_ms{phase=}
    and a nonzero tony_mfu on the coordinator's live /metrics, phases
    summing to the step wall within 5% in the persisted snapshot, a
    plan-measurements.json entry recorded by the LIVE job (not bench),
    and `tony top` rendering the breakdown from job history after the
    job exits."""
    import re

    repo = FIXTURES.parent.parent
    cache_dir = tmp_path / "xla-cache"
    # The peak table has no entry for a CPU (a CPU run reports no MFU),
    # so this test names its own peak: a launcher that adds one to the
    # table and then runs the real example unchanged.
    lm_train = str(repo / "examples" / "lm_train.py")
    launcher = tmp_path / "lm_train_with_peak.py"
    launcher.write_text(
        "import runpy, sys\n"
        "from tony_tpu.observability import stepstats\n"
        "stepstats.PEAK_FLOPS['cpu'] = 1e11\n"
        f"sys.argv[0] = {lm_train!r}\n"
        f"runpy.run_path({lm_train!r}, run_name='__main__')\n"
    )
    cluster = MiniTonyCluster(tmp_path)
    conf = cluster.base_conf()
    conf.set(keys.K_EXECUTES, str(launcher))
    conf.set(keys.K_PYTHON_BINARY, sys.executable)
    conf.set(keys.instances_key("worker"), 1)
    conf.set(keys.instances_key("ps"), 0)
    conf.set(keys.K_TASK_HEARTBEAT_INTERVAL_MS, 150)
    conf.set(keys.K_COMPILE_CACHE_DIR, str(cache_dir))
    conf.set(
        keys.K_TASK_PARAMS,
        "--steps 160 --d-model 32 --n-layers 2 --n-heads 2 "
        "--n-kv-heads 1 --vocab 128 --batch 4 --seq 64 "
        "--checkpoint-every 100000",
    )

    app_id = "application_mini_anatomy1"
    app_dir = cluster.staging_dir / app_id
    app_dir.mkdir(parents=True)
    conf.write_final(app_dir / constants.TONY_FINAL_CONF)
    coordinator = TonyCoordinator(
        conf, app_dir, app_id=app_id,
        backend=LocalProcessBackend(app_dir / "logs"),
    )
    result = []
    t = threading.Thread(
        target=lambda: result.append(coordinator.run()), daemon=True
    )
    cluster._live.append(coordinator)
    t.start()
    live_mfu = None
    live_phases = False
    try:
        deadline = time.monotonic() + 180
        addr_file = app_dir / "coordinator.http"
        while not addr_file.is_file() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert addr_file.is_file(), "coordinator.http never advertised"
        addr = addr_file.read_text().strip()
        # Scrape /metrics WHILE the job trains: the anatomy gauges ride
        # the heartbeat piggyback onto the live endpoint.
        while time.monotonic() < deadline and t.is_alive():
            try:
                text = urllib.request.urlopen(
                    f"http://{addr}/metrics", timeout=5
                ).read().decode()
            except OSError:
                time.sleep(0.05)
                continue
            if not live_phases:
                live_phases = "tony_step_phase_ms" in text
            m = re.search(r"tony_mfu\{[^}]*\} ([0-9.eE+-]+)", text)
            if m:
                live_mfu = float(m.group(1))
                if live_mfu > 0 and live_phases:
                    break
            time.sleep(0.05)
    finally:
        t.join(timeout=240)
    assert result and result[0] is SessionStatus.SUCCEEDED, (
        coordinator.session.diagnostics if coordinator.session else "no run"
    )
    assert live_phases, "tony_step_phase_ms never appeared on live /metrics"
    assert live_mfu is not None and live_mfu > 0, (
        f"nonzero tony_mfu never appeared on live /metrics ({live_mfu})"
    )

    # -- persisted snapshot: exclusive phases summing to the step wall ----
    from tony_tpu.observability import stepstats as ss

    final = json.loads((app_dir / "final-status.json").read_text())
    entry = ss.task_stepstats(final["metrics"]["tasks"]["worker:0"])
    assert entry is not None
    assert set(entry["phases"]) == set(ss.PHASES)
    gauges = final["metrics"]["tasks"]["worker:0"]["gauges"]
    assert sum(entry["phases"].values()) == pytest.approx(
        gauges["step_time_ms"], rel=0.05
    )
    assert gauges["tony_mfu"] > 0

    # -- live calibration: the JOB recorded a measurement, not bench ------
    from tony_tpu.parallel import plan as plan_lib

    table = plan_lib.load_measurements(cache_dir=str(cache_dir))
    assert table, "plan-measurements.json not written by the live job"
    (bucket,) = table.values()
    assert any(v > 0 for v in bucket.values())

    # -- `tony top` renders the breakdown from job history ----------------
    from tony_tpu.client import cli

    empty = tmp_path / "empty-staging"
    empty.mkdir()
    rc = cli.main([
        "top", app_id,
        "--staging-location", str(empty),  # force the history leg
        "--history-location", str(cluster.history_dir),
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "(history)" in out and "worker:0" in out
    assert "DATA_WAIT" in out and "COLLECTIVE" in out


def test_mini_cluster_stepstats_chaos_io_throttle(tmp_path, capsys):
    """Seeded io-throttle chaos: a `throttle_io` fault-plan entry starves
    the input pipeline mid-run — the dominant phase flips to data_wait,
    the mfu_collapse detector fires a health_alert, and `tony doctor`
    surfaces the TONY-D012 step-anatomy finding."""
    cluster = MiniTonyCluster(tmp_path)
    conf = cluster.base_conf()
    conf.set(keys.K_EXECUTES, str(FIXTURES / "stepstats_train.py"))
    conf.set(keys.K_PYTHON_BINARY, sys.executable)
    conf.set(keys.instances_key("worker"), 1)
    conf.set(keys.instances_key("ps"), 0)
    conf.set(keys.K_TASK_HEARTBEAT_INTERVAL_MS, 100)
    conf.set(keys.K_SHELL_ENV,
             "FIXTURE_STEPS=82,FIXTURE_COMPUTE_S=0.012,LINGER_S=1.0")
    conf.set(keys.K_FAULT_PLAN, json.dumps({
        "seed": 3,
        "faults": [{"action": "throttle_io", "target": "worker:0",
                    "ms": 150, "after_batches": 68, "count": 100000}],
    }))
    status, coord = cluster.run_job(conf)
    assert status is SessionStatus.SUCCEEDED, (
        coord.session.diagnostics if coord.session else "no run"
    )

    # -- the throttle flipped the dominant phase to data_wait -------------
    from tony_tpu.observability import stepstats as ss

    final = json.loads((coord.app_dir / "final-status.json").read_text())
    entry = ss.task_stepstats(final["metrics"]["tasks"]["worker:0"])
    assert entry is not None
    assert entry["dominant_phase"] == "data_wait", entry

    # -- the detector fired into the lifecycle log ------------------------
    events = obs_events.parse_jsonl(
        (coord.app_dir / "events.jsonl").read_text()
    )
    alerts = [e for e in events if e["kind"] == "health_alert"]
    assert any(e.get("detector") == "mfu_collapse" for e in alerts), (
        [(e.get("detector"), e.get("reason")) for e in alerts]
    )

    # -- `tony doctor` surfaces the step-anatomy finding ------------------
    from tony_tpu.client import cli

    rc = cli.main([
        "doctor", coord.app_id,
        "--staging-location", str(cluster.staging_dir),
        "--history-location", str(cluster.history_dir),
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "TONY-D012" in out and "MFU collapsed" in out
