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

For a serving cell, below the operations, the ANATOMY OF A DISPATCH
(``dispatches``): the kept trace holds the engine's host spans and the
``XLA Modules`` line on one clock, so each ``tony:engine.*_device`` span
is joined with the program that started inside it and the device's idle
time is put down to where in the dispatch it lies (ISSUE 40): before the
program (``lead``, and ``return_to_start`` against the launch span's
end), inside it (``bubbles``), after it (``tail``) and between two
dispatches (``between``); a prefill round launched without a readback
(ISSUE 41) has no ``tail`` and no ``between``, and the dispatch behind it
measures its ``lead`` from the end of the round's program; beside them the
engine's own ``stats()["dispatch"]`` over its life.

Run on no CPU: the harness refuses one."""

from __future__ import annotations

import argparse
import bisect
import json
import re
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


ENGINE_SPAN = re.compile(r"^tony:engine\.(decode|prefill)_"
                         r"(device|launch|readback)$")
PARTS = ("lead", "return_to_start", "bubbles", "tail", "between")
# How far before its device span a program may seem to start: a trace puts
# the device's line on the host's clock to within about a millisecond, and
# not the same way in every trace (ISSUE 41's traced run of chat-saturated
# read every program 0.9 ms early beside the parent's run of the same call:
# a fenced round's lead 0.3 and tail 2.6 ms against 1.26 and 1.51, the sums
# equal). What moves with it is the split between ``lead`` and ``tail``,
# never their sum.
CLOCK_SLACK_NS = 1_500_000


def _rank(values: list, pct: int) -> float:
    """The value at rank ceil(pct/100 x n) (nearest rank)."""
    return sorted(values)[-(-pct * len(values) // 100) - 1]


def dispatches(trace: dict, reduced: dict, xplane) -> dict:
    """The traced window's dispatches, one per ``tony:engine.*_device``
    span that lies inside it with its launch half, at most one readback
    half and a program of its own, in nanoseconds on the trace's clock. A
    span's program is the first of its kind (``decode`` / ``prefill`` in
    the module's name) that no earlier span took and that starts after the
    span does (``CLOCK_SLACK_NS`` allowed) and before its fence returns:
    its own readback's end, or, for a prefill round launched without one
    (ISSUE 41), that of the next span that has one. The parts:

    - ``lead``: program start - the LATER of the launch span's start and
      the end of the program before it (the device idles through all of
      it; behind an unfenced round the program before still runs when the
      launch begins);
    - ``return_to_start``: program start - launch span END, negative
      where the device began before the jitted call returned;
    - ``bubbles``: program duration - the union of its operations;
    - ``tail``: readback span end - program end (None: no readback);
    - ``between``: the next dispatch's launch span start - this readback
      span's end (host work: emit, publish, admit, assemble; None: no
      readback, the device works through the host's time).

    ``lead + bubbles + tail + between`` tile the time from the first
    launch to the last readback but for the programs' busy time, so their
    sum is the window's idle time less what lies at its two edges and
    under no engine span (``remainder_s``). ``programs``: per program
    {n, unfenced, and per part {p50_ms, p90_ms, sum_s}}; ``rows``: every
    dispatch. ``xplane``: the harness's reduction module, ``reduced`` its
    reduction of ``trace`` (the window's idle time is its)."""
    lo, hi = xplane.window_of(trace)
    dev = trace["devices"][min(trace["devices"], key=int)]
    ops = sorted((s, s + d) for _, s, d in dev["ops"] if d > 0)
    starts = [s for s, _ in ops]
    modules = sorted((s, s + d, re.sub(r"\(\d+\)$", "", n))
                     for n, s, d in dev["modules"])
    ends = sorted(m[1] for m in modules)
    spans: dict[str, list] = {"device": [], "launch": [], "readback": []}
    for name, s, d in trace["host_spans"]:
        m = ENGINE_SPAN.match(name)
        if m and lo <= s and s + d <= hi:
            spans[m.group(2)].append((s, s + d, m.group(1)))
    found = []     # (device span, its launch, its readback or None) or None
    for s, e, program in sorted(spans["device"]):
        launch, readback = ([h for h in spans[k] if s <= h[0] and h[1] <= e
                             and h[2] == program]
                            for k in ("launch", "readback"))
        found.append(((s, program), launch[0], readback[0] if readback
                      else None)
                     if len(launch) == 1 and len(readback) <= 1 else None)
    rows, unmatched, taken = [], 0, set()
    for i, dispatch in enumerate(found):
        mine = None
        if dispatch:
            (s, program), launch, readback = dispatch
            # the fence that waits for this dispatch's program
            fence = next((d[2][1] for d in found[i:] if d and d[2]), s)
            mine = next((m for m in modules if m not in taken
                         and program in m[2]
                         and s - CLOCK_SLACK_NS <= m[0] < fence), None)
        if mine is None:
            unmatched += 1
            continue
        taken.add(mine)
        p0, p1, name = mine
        # a program's operations are those that start inside it
        busy = xplane.total(xplane.union(
            [[a, min(b, p1)] for a, b in ops[bisect.bisect_left(starts, p0):
                                             bisect.bisect_left(starts, p1)]]))
        before = bisect.bisect_right(ends, p0)
        rows.append({"program": name, "launch_start": launch[0],
                     "readback_end": readback[1] if readback else None,
                     "lead": p0 - max(launch[0],
                                      ends[before - 1] if before else lo),
                     "return_to_start": p0 - launch[1],
                     "bubbles": (p1 - p0) - busy,
                     "tail": readback[1] - p1 if readback else None,
                     "between": None})
    for row, nxt in zip(rows, rows[1:]):
        if row["readback_end"] is not None:
            row["between"] = nxt["launch_start"] - row["readback_end"]
    idle_s = reduced["window_s"] - reduced["busy_s"]
    accounted_s = sum(row[k] or 0 for row in rows
                      for k in ("lead", "bubbles", "tail", "between")) / 1e9
    programs = {}
    for name in sorted({row["program"] for row in rows}):
        mine = [row for row in rows if row["program"] == name]
        programs[name] = {"n": len(mine), "unfenced": sum(
            row["readback_end"] is None for row in mine)}
        for part in PARTS:
            values = [row[part] for row in mine if row[part] is not None]
            programs[name][part] = {
                "p50_ms": _rank(values, 50) / 1e6,
                "p90_ms": _rank(values, 90) / 1e6,
                "sum_s": sum(values) / 1e9} if values else None
    return {"window_s": reduced["window_s"], "idle_s": idle_s,
            "accounted_s": accounted_s, "remainder_s": idle_s - accounted_s,
            "unmatched_device_spans": unmatched, "programs": programs,
            "rows": rows}


def print_dispatches(table: dict, engine_dispatch: dict | None) -> None:
    print(f"dispatches: idle {table['idle_s']:.4f} s of the window's "
          f"{table['window_s']:.3f} s; lead + bubbles + tail + between "
          f"{table['accounted_s']:.4f} s; remainder (the window's edges, no "
          f"engine span) {table['remainder_s']:.4f} s = "
          f"{100.0 * table['remainder_s'] / max(table['idle_s'], 1e-12):.1f}%"
          f" of idle; {table['unmatched_device_spans']} device spans "
          f"without a launch half and a program of their own")
    for name, row in table["programs"].items():
        print(f"  {name}: {row['n']} dispatches, {row['unfenced']} of them "
              f"without a readback   (p50 ms / p90 ms / sum s)")
        for part in PARTS:
            if row[part]:
                print(f"    {part:16s} {row[part]['p50_ms']:8.3f} "
                      f"{row[part]['p90_ms']:8.3f} {row[part]['sum_s']:8.4f}")
    if engine_dispatch:
        print(f"  stats()[\"dispatch\"] over the engine's life: "
              f"{json.dumps(engine_dispatch)}")


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
        trace = json.loads((kept / "trace_events.json").read_text())
        reduced = xplane.reduce(trace)
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
    if trace["devices"] and any(n.endswith("_launch") and ENGINE_SPAN.match(n)
                                for n, _, _ in trace["host_spans"]):
        out["dispatches"] = dispatches(trace, reduced, xplane)
        print_dispatches(out["dispatches"], engine_stats.get("dispatch"))
        out["engine_dispatch"] = engine_stats.get("dispatch")
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
