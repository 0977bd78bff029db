#!/usr/bin/env python3
"""The benchmark's one command:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of its standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``. Before
it, the stage log of set-up; as the last lines of standard error, every
number compared beside its limit.

This process imports no jax (it must not hold the chip). It submits the
cell's job through the program's normal path and, for a serving job,
drives it over HTTP. It fails, with no result line, when the job did not
run on a TPU with the cell's chip count."""

from __future__ import annotations

import time

T0 = time.time()          # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
from yardstick import compare, loadgen, spec, stats, traffic as traffic_gen  # noqa: E402
from yardstick.submit import Job  # noqa: E402

JOB_CONFS = {
    "train": ["tony.worker.instances=1", "tony.ps.instances=0"],
    "serve": ["tony.serving.instances=1", "tony.worker.instances=0",
              "tony.ps.instances=0", "tony.chief.name=serving"],
}
SETUP_LIMIT_S = 1100.0     # a first run compiles; the contract allows 1200


class BenchFailure(Exception):
    pass


class Stages:
    """Harness-clock times of the run's stages, the job's own merged in."""

    def __init__(self) -> None:
        self.rows: list[tuple[float, str, str]] = [(T0, "harness",
                                                    "process_start")]

    def mark(self, name: str, who: str = "harness", at: float | None = None):
        self.rows.append((time.time() if at is None else at, who, name))

    def merge_job(self, work: Path) -> None:
        path = work / "stages.log"
        if path.is_file():
            for line in path.read_text().splitlines():
                name, at = line.split()
                self.rows.append((float(at), "job", name))

    def at(self, name: str) -> float | None:
        for t, _, n in self.rows:
            if n == name:
                return t
        return None

    def print(self) -> None:
        for t, who, name in sorted(self.rows):
            print(f"stage {t - T0:9.3f}s {who:8s} {name}", flush=True)


def wait_for(predicate, limit_s: float, what: str, job: Job, poll_s=0.01):
    deadline = time.monotonic() + limit_s
    while time.monotonic() < deadline:
        value = predicate()
        if value:
            return value
        if job.proc.poll() is not None:
            raise BenchFailure(f"the job ended before {what}")
        time.sleep(poll_s)
    raise BenchFailure(f"no {what} after {limit_s:.0f}s")


def read_json(path: Path):
    if not path.is_file():
        return None
    with open(path) as f:
        return json.load(f)


def check_device(device: dict, cell: spec.Cell, require_tpu: bool) -> None:
    if not require_tpu:
        return
    if device["platform"] != "tpu":
        raise BenchFailure(f"the job ran on {device['platform']!r}, "
                           f"not on a TPU")
    if device["count"] != cell.chips:
        raise BenchFailure(f"the job saw {device['count']} chips, the cell "
                           f"asks for {cell.chips}")


def drive_serving(cell, job, work, stages, seed, seconds, trace) -> dict:
    """Warm up, then offer the cell's load; returns the requests' records
    and the window on the wall clock."""
    tr = cell.traffic
    vocab = cell.config["vocab_size"]
    addr = wait_for(lambda: read_json(work / "addr.json"), SETUP_LIMIT_S,
                    "a serving address", job)
    host, port = addr["host"], addr["port"]

    def healthy():
        try:
            return loadgen.get_json(host, port, "/healthz")
        except OSError:
            return None

    wait_for(healthy, 60.0, "an answer from /healthz", job)
    stages.mark("server_answering")
    warm = traffic_gen.warmup_requests(tr, vocab)
    warm_run = loadgen.LoadRun(host, port, [
        dict(r, due=0.0, index=i) for i, r in enumerate(warm)])
    warm_run.start(keep_tokens=False)
    if not warm_run.drain(lambda r: True, SETUP_LIMIT_S):
        raise BenchFailure("warm-up requests did not finish")
    bad = [r for r in warm_run.requests if not r.ok]
    if bad:
        raise BenchFailure(f"warm-up request failed: {bad[0].error}")
    stages.mark("warmup_done")

    sched = traffic_gen.serving_schedule(tr, seed, seconds, vocab)
    w0, w1 = sched["window"]
    run = loadgen.LoadRun(host, port, sched["requests"])
    run.start()
    wall0 = time.time() - run.now()          # wall clock at schedule zero
    stages.mark("schedule_start", at=wall0)
    stages.mark("window_start", at=wall0 + w0)
    traced = None
    if trace:
        # the last seconds of the window: the profiler's stop, which blocks
        # the job for seconds, then falls after it
        span = min(3.0, 0.5 * seconds)
        at = (seconds - span, span)
        traced = (w0 + at[0], w0 + at[0] + at[1])
        tmp = work / ".trace_request.tmp"
        tmp.write_text(json.dumps({"start": wall0 + w0 + at[0],
                                   "len_s": at[1]}))
        os.replace(tmp, work / "trace_request.json")
    run.wait_until(w1)
    stages.mark("window_end", at=wall0 + w1)
    run.stop()
    in_window = lambda r: w0 <= r.due < w1   # noqa: E731
    drained = run.drain(in_window, float(tr["drain_s"]))
    stages.mark("drain_end")
    return {"run": run, "warm": warm_run, "window": (w0, w1), "wall0": wall0,
            "addr": (host, port), "drained": drained, "in_window": in_window,
            "traced": traced}


def pick_check_sample(requests, in_window, seed: int, n: int) -> list:
    """The sample the reference runs over: drawn from the seed among the
    requests of the window that finished, the longest always in it."""
    import random

    done = [r for r in requests if in_window(r) and r.ok and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: (-(r.prompt_len + r.length), r.index))
    longest, rest = done[0], done[1:]
    rng = random.Random(seed)
    sample = [longest] + rng.sample(rest, min(n - 1, len(rest)))
    while len(sample) < n:                   # keep the reference's shapes
        sample.append(sample[len(sample) % max(len(done), 1)])
    return sample


def run_cell(repo: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, require_tpu: bool = True, fault: str | None = None,
             control: str | None = None, keep_work: Path | None = None) -> dict:
    """One run of one cell. ``fault`` and ``control`` are for the tests and
    the builder's control readings (the command has no such option);
    ``keep_work`` keeps a copy of the run's work directory."""
    bench = spec.load_benchmark(repo)
    cell = spec.Cell(bench, workload, repo)
    if not (repo / "tony_tpu").is_dir():
        raise BenchFailure("no tony_tpu beside the benchmark: nothing to run")
    work = Path(tempfile.mkdtemp(prefix="perfbench-"))
    try:
        return run_in(work, repo, bench, cell, seed, seconds, trace,
                      require_tpu, fault, control)
    finally:
        if keep_work is not None:
            shutil.copytree(work, keep_work, dirs_exist_ok=True)
        shutil.rmtree(work, ignore_errors=True)


def run_in(work, repo, bench, cell, seed, seconds, trace, require_tpu, fault,
           control) -> dict:
    stages = Stages()
    params = {
        "workload": cell.name, "seed": seed, "seconds": seconds,
        "trace": bool(trace), "work": str(work), "config": cell.config,
        "config_path": str(cell.config_path), "traffic": cell.traffic,
        "fault": fault, "control": control,
    }
    (work / "params.json").write_text(json.dumps(params))
    confs = JOB_CONFS[cell.job] + [
        f"{k}={v}" for k, v in cell.config.get("conf", {}).items()]
    job = Job(repo, cell.job_script, confs,
              f"--params {work / 'params.json'}", work / "client.log",
              stages.mark)
    stages.mark("job_submitted", at=job.submitted_at)
    serving = None
    try:
        device = wait_for(lambda: read_json(work / "device.json"),
                          SETUP_LIMIT_S, "a device report", job)
        check_device(device, cell, require_tpu)
        if cell.job == "serve":
            serving = drive_serving(cell, job, work, stages, seed, seconds,
                                    trace)
            run = serving["run"]
            sample = pick_check_sample(
                run.requests, serving["in_window"], seed,
                int(cell.traffic["check_requests"]))
            if sample:
                tmp = work / ".check.tmp"
                tmp.write_text(json.dumps({
                    "pad_to": int(cell.traffic["prompt_len"]["max"])
                    + int(cell.traffic["output_len"]["max"]),
                    "requests": [{"prompt": r.prompt, "tokens": r.tokens}
                                 for r in sample]}))
                os.replace(tmp, work / "check.json")
            serving["shutdown_at"] = run.now()
            loadgen.post_json(*serving["addr"], "/shutdown", {},
                              timeout=30.0)
            stages.mark("shutdown_sent")
            run.join(30.0)
        rc = job.wait_exit(SETUP_LIMIT_S)
        if rc is None:
            raise BenchFailure("the job did not end")
        if not job.wait_gone():
            raise BenchFailure(f"processes left: {job.marked_pids()}")
        stages.mark("job_gone")
        if rc != 0 or job.final_state != "SUCCEEDED":
            raise BenchFailure(
                f"submitter exit {rc}, final state {job.final_state}; "
                f"task log tail: {job.task_lines[-15:]}")
    except BaseException:
        job.stop_all()
        raise
    finally:
        job.close()
        stages.merge_job(work)
    result = read_json(work / "result.json")
    if result is None:
        raise BenchFailure("the job left no result")
    return assemble(cell, stages, work, result, serving, seconds, trace)


def reduce_trace(work: Path):
    """In a child held to the CPU, once the job is gone: this process
    never imports jax."""
    from yardstick import xplane

    found = sorted((work / "trace").glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise BenchFailure("the traced run left no .xplane.pb")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(spec.ROOT))
    events = work / "trace_events.json"
    subprocess.run([sys.executable, "-m", "yardstick.xplane", str(found[-1]),
                    str(events)], check=True, env=env, cwd=spec.ROOT,
                   timeout=300)
    return xplane.reduce(read_json(events))


def assemble(cell, stages, work, result, serving, seconds, trace) -> dict:
    """From the job's and the load generator's records to the result."""
    device = dict(result["device"])
    device["memory_peak_bytes"] = result.get("memory_peak_bytes")
    window_start = stages.at("window_start")
    numbers = dict(result.get("numbers") or {})
    values: dict[str, float] = {"setup_s": window_start - T0}
    ctx = {"cell": cell, "config": cell.config, "traffic": cell.traffic,
           "job": result, "seconds": seconds, "stages": stages,
           "device": device, "trace": None, "serving": None}
    if cell.job == "train":
        values["train_tokens_per_s_per_chip"] = stats.train_rate(
            result["step_ends"], result["tokens_per_step"],
            result["window_s"], cell.chips)
        attempted, failed = result["steps_started"], result["steps_failed"]
        numbers["compiles_in_window"] = result["compiles_in_window"]
        numbers["foreign_rows"] = result["foreign_rows"]
        numbers["steps_failed"] = failed
    else:
        run, (w0, w1) = serving["run"], serving["window"]
        sm = stats.serving_metrics(run.requests, w0, w1)
        ctx["serving"] = sm
        ctx["requests"] = run.requests
        ctx["traced_span_client"] = serving["traced"]
        values.update({k: v for k, v in sm.items()
                       if k.startswith("serve_") and v is not None})
        wall0 = serving["wall0"]
        # harness and job share the machine's clock: the job's samples of
        # the engine's counter are read at the window's two edges
        samples = result["occupancy"]
        try:
            values["serve_tokens_per_s"] = stats.generated_rate(
                samples, wall0 + w0, wall0 + w1)
        except ValueError as exc:
            raise BenchFailure(f"tokens generated in the window: {exc}")
        numbers["compiles_in_window"] = sum(
            1 for t in result["compile_times"]
            if wall0 + w0 <= t < wall0 + w1)
        # A request cut by the shutdown (503 after it was sent, or no
        # answer) is neither attempted nor failed where the cell's file
        # says so (above the knee the queue never empties); elsewhere
        # every request due in the window must end well.
        due = [r for r in run.requests if serving["in_window"](r)]
        cut_ok = cell.traffic["in_flight_at_end"] == "cut"
        cut = [r for r in due if not r.ok and (
            r.status is None or (r.answered is not None
                                 and r.answered >= serving["shutdown_at"]))]
        if cut_ok:
            due = [r for r in due if r not in cut]
        attempted = len(due)
        failed = sum(1 for r in due if not r.ok)
        numbers["requests_failed"] = failed
        # The counter is held to what clients received, over the engine's
        # life in the job: every token it counted reached a client, but
        # for those of requests that the shutdown cut.
        sent = [r for r in serving["warm"].requests + run.requests
                if r.sent is not None]
        answered = sum(r.length for r in sent if r.status == 200)
        allowance = sum(r.max_new for r in sent if r.status != 200) \
            if cut_ok else 0
        numbers["tokens_unaccounted"] = stats.tokens_unaccounted(
            result["tokens_generated"], answered, allowance)
        ctx["serving"].update(
            cut_at_shutdown=len(cut), attempted=attempted,
            generator_late_max_ms=sm["lateness_max_ms"],
            tokens_generated=result["tokens_generated"],
            tokens_answered=answered, tokens_cut_allowance=allowance,
            counter_sample_gap_max_ms=1000.0 * max(
                b[0] - a[0] for a, b in zip(samples, samples[1:])
                if a[0] < wall0 + w1 and b[0] > wall0 + w0))
    limits = dict(cell.config["correct"]["limits"])
    if cell.job == "serve":      # exact, whatever the configuration
        limits.setdefault("tokens_unaccounted", 0)
    correct, table = compare.judge(numbers, limits)

    if trace:
        reduced = reduce_trace(work)
        ctx["trace"] = reduced
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        metrics = {}
        for m in cell.per_layer:
            value = cell.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": finite(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": ctx["trace"]["device_ops"][:10],
                            "idle_gaps": ctx["trace"]["idle_gaps"][:10]}
    out["info"] = info(ctx, result)
    out["compared"] = {
        k: dict(v, value=(v["value"] if isinstance(v["value"], (int, str))
                          or v["value"] is None or math.isfinite(v["value"])
                          else str(v["value"])))
        for k, v in table.items()}
    return {"result": out, "stages": stages}


def finite(value: float) -> float:
    """A tail over requests of which too many are missing is infinite;
    the result line holds numbers, so it reads as a thousand seconds."""
    return value if math.isfinite(value) else 1.0e9


def info(ctx, result) -> dict:
    """Readings that decide nothing, for whoever reads a run's line."""
    extra = {"reference_s": result.get("reference_s")}
    if ctx["serving"]:
        extra.update(ctx["serving"])
        extra.update(prefill_chunk=result["prefill_chunk"],
                     decode_window=result["decode_window"])
    else:
        extra["steps_finished"] = len(result["step_ends"])
    if result.get("control"):     # the numbers compared are the control's
        extra["control_in_program_place"] = result["control"]
    return extra


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        done = run_cell(spec.REPO, args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except (BenchFailure, spec.SpecError, FileNotFoundError) as exc:
        print(f"perfbench: {exc}", file=sys.stderr)
        return 2
    done["stages"].print()
    result = done["result"]
    for name, row in result["compared"].items():
        print(f"compared {name}: {row['value']} (limit {row['limit']}) "
              f"{'ok' if row['ok'] else 'NOT MET'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
