"""Every device operation of one traced step, not the top ten.

A builder's tool for the chip, beside ``perfbench/tests/onchip.py``: one
traced run of a cell through the benchmark's own harness (``run_cell`` of
the checkout named by ``--repo``, so a parent unpacked under
``.chip_scratch/`` is read the same way as the working tree), then the
harness's own reduction of the kept ``trace_events.json`` printed IN FULL:
each ``<op>:<result shape>`` with its calls and self time per call of the
cell's main program (a train step, or a serving dispatch). The result
line's ``breakdown`` stops at ten operations, which is how a third of the
train step stayed unnamed until ISSUE 33.

    python3 tools/step_ops.py --workload mistral7b.train.b4x2048 \\
        --seed 2147600123 --seconds 51 [--repo .chip_scratch/parent] \\
        [--out chiprun_out/step_ops/parent.json]

Run on no CPU: the harness refuses one."""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path


def table(reduced: dict) -> dict:
    """{"program", "calls", "program_median_ms", "window_s", "busy_s",
    "programs": every traced program's {calls, total_s, median_s},
    "ops": [[name, calls per program call, self ms per program call],
    ...] by time}, the program being the one with most total time (a
    serving cell has two: a decode iteration and a prefill round). The
    window cuts its first and last call, so where one program is all that
    ran (a train step) the calls are counted as busy time over the
    program's median, not as the whole calls that started inside."""
    programs = reduced["programs"]
    if not programs:
        raise SystemExit("the trace holds no program")
    name = max(programs, key=lambda n: programs[n]["total_s"])
    calls = float(programs[name]["calls"])
    if len(programs) == 1:
        calls = reduced["busy_s"] / programs[name]["median_s"]
    ops = [[op, reduced["device_op_calls"].get(op, 0) / calls,
            1000.0 * seconds / calls]
           for op, seconds in reduced["device_ops"]]
    return {"program": name, "calls": calls,
            "program_median_ms": 1000.0 * programs[name]["median_s"],
            "window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
            "programs": programs, "ops": ops}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repo", default=str(Path(__file__).resolve().parents[1]))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args()
    repo = Path(args.repo).resolve()
    sys.path.insert(0, str(repo / "perfbench"))
    import run as harness  # noqa: E402  (the named checkout's harness)
    from yardstick import xplane  # noqa: E402

    kept = Path(tempfile.mkdtemp(prefix="step-ops-"))
    try:
        done = harness.run_cell(repo, args.workload, args.seed, args.seconds,
                                True, keep_work=kept)
        reduced = xplane.reduce(
            json.loads((kept / "trace_events.json").read_text()))
        window = kept / "window.json"      # a serving job's own report
        engine_stats = (json.loads(window.read_text()).get("engine_stats", {})
                        if window.exists() else {})
    finally:
        shutil.rmtree(kept, ignore_errors=True)
    result = done["result"]
    out = table(reduced)
    out.update(repo=str(repo), workload=args.workload, seed=args.seed,
               result=result)
    print(f"{out['program']}: {out['calls']:.2f} calls in the traced window "
          f"of {out['window_s']:.3f} s (busy {out['busy_s']:.3f} s), median "
          f"{out['program_median_ms']:.3f} ms a call")
    for name, program in sorted(out["programs"].items()):
        print(f"  {name}: {program['calls']} calls, median "
              f"{1000.0 * program['median_s']:.3f} ms, "
              f"{program['total_s']:.3f} s in all")
    for name, calls, ms in out["ops"]:
        print(f"{ms:9.3f} ms {calls:7.2f} x  {name}")
    # what the engine counted over its life: key positions its attention
    # read against what the slots reserve (stats()["prefill_keys"], PR 31;
    # ["decode_keys"], PR 39)
    out["keys"] = {k: engine_stats[k] for k in ("prefill_keys", "decode_keys")
                   if k in engine_stats}
    print(json.dumps({**{k: result[k] for k in
                         ("correct", "metrics", "device", "compared")},
                      "keys": out["keys"]}))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
