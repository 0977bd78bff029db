"""A builder's tool, like ``onchip.py``: one traced run of a serving cell
read by the PROGRAM's spans. The harness's ``xplane.extract`` keeps only
host spans named ``bench:*``; this reads the same ``.xplane.pb`` again,
takes the host events named ``tony:*`` (``ServingEngine``'s spans, which
enter a ``TraceAnnotation`` each) and hands them to the harness's own
``xplane.reduce`` as ``host_spans``, so device idle time is split by the
innermost program span open at the time. The job's user process also
leaves its span ring as ``trace-user-*.jsonl`` in the job's log directory,
which goes with the submitter: the run is made with the harness's ``Job``
extended to copy those files while they last. From the two records of the
same spans it reports the clock skew (profiler's start of a span less the
ring's, joined on ``span_id``) and the spans per working iteration.

    python3 perfbench/tests/progspans.py <workload> --seconds 51 --seed 5 \\
        [--out chiprun_out/progspans]
    python3 perfbench/tests/progspans.py --span-cost

``--span-cost`` times the span primitive alone, in this process: ns per
span with no profiler session and inside one (host tracer only). One line
of JSON per reading; never a cell's measurement, and run on no CPU."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE))
PROGRAM_SPAN_PREFIX = "tony:"


def program_spans(path: str) -> dict:
    """{"spans": [[name, start_ns, dur_ns, span_id], ...] of the host
    events named ``tony:*``, "start_epoch_ns": the session's start on the
    epoch (event times count from it) or None}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, start_epoch_ns = [], None
    for plane in data.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start_epoch_ns = int(stats["profile_start_time"])
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_SPAN_PREFIX):
                    span_id = dict(e.stats).get("span_id")
                    spans.append([e.name, int(e.start_ns), int(e.duration_ns),
                                  None if span_id is None else int(span_id)])
    return {"spans": spans, "start_epoch_ns": start_epoch_ns}


def keeping_job(job_class, keep_to: Path):
    """The harness's ``Job``, which also copies the ``trace-*.jsonl`` that
    the job's processes leave in its log directory as they exit."""

    class KeepingJob(job_class):
        def __init__(self, *args, **kwargs) -> None:
            super().__init__(*args, **kwargs)
            self._keeper = threading.Thread(target=self._keep, daemon=True)
            self._keeper.start()

        def _keep(self) -> None:
            seen: dict[Path, tuple] = {}
            keep_to.mkdir(parents=True, exist_ok=True)
            while self.proc.poll() is None:
                logs = self.app_dir / "logs" if self.app_dir else None
                for path in (sorted(logs.glob("trace-*.jsonl"))
                             if logs else ()):
                    try:
                        stat = path.stat()
                        mark = (stat.st_size, stat.st_mtime_ns)
                        if seen.get(path) != mark:
                            shutil.copy(path, keep_to / path.name)
                            seen[path] = mark
                    except OSError:
                        continue
                time.sleep(0.05)

        def close(self) -> None:
            self._keeper.join(timeout=5)
            super().close()

    return KeepingJob


def ring_spans(kept_traces: Path) -> list[dict]:
    """The ``tony:*`` spans the job's processes wrote (one Chrome event a
    line; a torn last line of a copy made mid-write is skipped)."""
    spans = []
    for path in sorted(kept_traces.glob("trace-*.jsonl")):
        for row in path.read_text().splitlines():
            try:
                e = json.loads(row)
            except ValueError:
                continue
            if e.get("ph") == "X" and e["name"].startswith(
                    PROGRAM_SPAN_PREFIX):
                spans.append(e)
    return spans


def clock_skew(profiled: dict, ring: list[dict], name: str) -> dict:
    """Profiler's start of each ``name`` span less the ring's (ns; the
    ring's export is in microseconds), joined on ``span_id``."""
    if profiled["start_epoch_ns"] is None:
        return {"span": name, "matched": 0,
                "why": "the trace has no profile_start_time"}
    by_id = {e["args"]["span_id"]: e for e in ring if e["name"] == name}
    skews, dur_gaps = [], []
    for n, start_ns, dur_ns, span_id in profiled["spans"]:
        mine = by_id.get(span_id) if n == name else None
        if mine is None:
            continue
        skews.append(profiled["start_epoch_ns"] + start_ns
                     - mine["ts"] * 1000)
        dur_gaps.append(dur_ns - mine["dur"] * 1000)
    if not skews:
        return {"span": name, "matched": 0}
    return {"span": name, "matched": len(skews),
            "skew_ns_median": statistics.median(skews),
            "skew_ns_min": min(skews), "skew_ns_max": max(skews),
            "duration_gap_ns_median": statistics.median(dur_gaps)}


def spans_per_iteration(ring: list[dict]) -> dict:
    steps = sum(1 for e in ring if e["name"] == "tony:engine.step")
    engine = sum(1 for e in ring if e["name"].startswith("tony:engine."))
    requests = sum(1 for e in ring if e["name"].startswith("tony:request."))
    return {"ring_step_spans": steps, "ring_engine_spans": engine,
            "ring_request_spans": requests,
            "engine_spans_per_working_iteration":
                engine / steps if steps else None}


def span_cost(n: int = 200_000) -> dict:
    """ns per ``with tracer.span(...)`` of the program's primitive."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")   # host tracer only
    sys.path.insert(0, str(HERE.parents[1]))          # the program
    import jax

    from tony_tpu.observability import trace

    def loop(count: int) -> float:
        tracer = trace.Tracer(proc="span-cost")
        t0 = time.perf_counter_ns()
        for i in range(count):
            with tracer.span("tony:engine.decode_device", slots=i, window=1):
                pass
        return (time.perf_counter_ns() - t0) / count

    loop(20_000)
    out = {"ns_per_span_no_session": min(loop(n) for _ in range(3))}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tmp = tempfile.mkdtemp(prefix="span-cost-")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            out["ns_per_span_in_session"] = loop(n // 4)
        finally:
            jax.profiler.stop_trace()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    out["ns_clock_read"] = min(
        _clock_read_ns(trace.now_ns) for _ in range(3))
    return out


def _clock_read_ns(now_ns, n: int = 200_000) -> float:
    t0 = time.perf_counter_ns()
    for _ in range(n):
        now_ns()
    return (time.perf_counter_ns() - t0) / n


def analyse(kept: Path, kept_traces: Path, result: dict) -> dict:
    """What one traced run left (its kept work directory, the copies of
    its processes' span files), read by program span."""
    from yardstick import xplane

    # the job is gone and the chip free: jax, held to the CPU, only
    # reads the trace file
    os.environ["JAX_PLATFORMS"] = "cpu"
    pb = sorted((kept / "trace").glob("plugins/profile/*/*.xplane.pb"))[-1]
    profiled = program_spans(str(pb))
    extracted = xplane.extract(str(pb))
    window = [s for s in extracted["host_spans"]
              if s[0] == xplane.TRACED_SPAN]
    by_program_span = xplane.reduce(dict(
        extracted, host_spans=window + [s[:3] for s in profiled["spans"]]))
    by_bench_span = xplane.reduce(dict(extracted, host_spans=[
        s for s in extracted["host_spans"] if s[0].startswith("bench:")]))
    ring = ring_spans(kept_traces)
    return {
        "correct": result["correct"], "failed": result["failed"],
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "window_s": by_program_span["window_s"],
        "busy_s": by_program_span["busy_s"],
        "idle_gaps_by_program_span": by_program_span["idle_gaps"],
        "idle_gaps_by_bench_span": by_bench_span["idle_gaps"],
        "profiled_program_spans": len(profiled["spans"]),
        "clock": [clock_skew(profiled, ring, name) for name in (
            "tony:engine.decode_device", "tony:engine.step")],
        **spans_per_iteration(ring),
        "engine_stats": json.loads(
            (kept / "window.json").read_text()).get("engine_stats"),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload", nargs="?")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--out", default="chiprun_out/progspans")
    ap.add_argument("--span-cost", action="store_true")
    args = ap.parse_args()
    if args.span_cost:
        print(json.dumps({"span_cost": span_cost()}), flush=True)
        return 0
    if not args.workload:
        ap.error("a workload, or --span-cost")

    import run as harness
    from yardstick import spec

    # a fixed path: the checkout's path is part of the compile cache's key
    tmp = Path(tempfile.gettempdir()) / "perfbench-progspans"
    shutil.rmtree(tmp, ignore_errors=True)
    tmp.mkdir(parents=True)
    try:
        kept, kept_traces = tmp / "work", tmp / "job-traces"
        harness.Job = keeping_job(harness.Job, kept_traces)
        done = harness.run_cell(spec.REPO, args.workload, args.seed,
                                args.seconds, True, keep_work=kept)
        line = {"workload": args.workload, "seed": args.seed,
                **analyse(kept, kept_traces, done["result"])}
        print(json.dumps(line), flush=True)
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        (out / f"{args.workload}.json").write_text(json.dumps(line, indent=1))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
