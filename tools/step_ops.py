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
measures its ``lead`` from the end of the round's program; a decode
iteration's readback lies in the NEXT decode span (ISSUE 44: iteration k+1
is launched before iteration k is read back), so its ``tail`` and
``between`` are idle only where nothing was launched behind it, and
``home`` says when its tokens reached the host; beside them the engine's
own ``stats()["dispatch"]`` over its life (``pipelined`` and
``discarded_tokens`` among the decode program's).

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
PARTS = ("lead", "return_to_start", "bubbles", "tail", "between", "home")
# A trace puts the device's line on the host's clock to within about a
# millisecond, and not the same way in every trace (ISSUE 41's traced run of
# chat-saturated read every program 0.9 ms early beside the parent's run of
# the same call: a fenced round's lead 0.3 and tail 2.6 ms against 1.26 and
# 1.51, the sums equal). What moves with it is the split between ``lead``
# and ``tail``, never their sum; a program goes to its launch by order, so
# one that seems to start before its span does is still its own.


def _rank(values: list, pct: int) -> float:
    """The value at rank ceil(pct/100 x n) (nearest rank)."""
    return sorted(values)[-(-pct * len(values) // 100) - 1]


def _offset(launches: list, modules: list, fences: list) -> int:
    """How many of the trace's first programs were launched before it
    began. The device runs its programs in the order they were launched,
    so launch i's program is program ``offset + i``, and one point where
    the queue is known to be empty fixes the offset: the return of a
    fenced prefill round's readback, which waits for the round and so for
    everything launched before it (a decode readback does not say so: it
    may return with the next iteration queued). Every program that
    started before that moment belongs to a launch made before it. A
    trace without such a readback: the programs that start before its
    first launch does."""
    if not launches:
        return 0
    at = min(fences, default=launches[0]["launch"][0])
    return max(0, sum(m[0] < at for m in modules)
               - sum(row["launch"][0] < at for row in launches))


def dispatches(trace: dict, reduced: dict, xplane) -> dict:
    """The traced window's dispatches, one per ``tony:engine.*_device``
    span that lies inside it with a launch half and a program of its own,
    in nanoseconds on the trace's clock. Programs go to launches by ORDER
    (``_offset``), kinds agreeing (``decode`` / ``prefill`` in the
    module's name); a launch whose turn holds another kind has no program.
    The readback that FENCES a program: a prefill round's own, if its span
    has one (ISSUE 41: a round launched without one has none); a decode
    iteration's is the first decode readback that returns after the
    program ended, which since ISSUE 44 lies in the NEXT decode span (an
    iteration is launched before the one ahead of it is read back;
    ``pipelined`` counts the rows fenced so), and in a span without a
    launch where the pipeline drains. The parts put the device's idle time
    behind a program, up to the start of the next one, down to what the
    host was doing:

    - ``lead``: program start - the LATER of the launch span's start and
      the end of the program before it (the device idles through all of
      it; behind a program that still runs when the launch begins, as an
      unfenced round or the iteration in flight does, only the gap
      between the two);
    - ``return_to_start``: program start - launch span END, negative
      where the device began before the jitted call returned;
    - ``bubbles``: program duration - the union of its operations;
    - ``tail``: the fence's return - program end, as far as it lies
      before the next dispatch's launch began (None: no fence; 0 where
      the next program was launched before this one ended);
    - ``between``: from there to the next dispatch's launch span start
      (host work: emit, publish, admit, assemble; None: no fence);
    - ``home``: the fence's return - program end, idle or not: how long
      after a program's end the host holds its result.

    ``lead + bubbles + tail + between`` tile the time from the first
    launch to the last readback but for the programs' busy time, so their
    sum is the window's idle time less what lies at its two edges and
    under no engine span (``remainder_s``). ``programs``: per program
    {n, unfenced, pipelined, and per part {p50_ms, p90_ms, sum_s}};
    ``rows``: every dispatch. ``xplane``: the harness's reduction module,
    ``reduced`` its reduction of ``trace`` (the window's idle time is
    its)."""
    lo, hi = xplane.window_of(trace)
    dev = trace["devices"][min(trace["devices"], key=int)]
    ops = sorted((s, s + d) for _, s, d in dev["ops"] if d > 0)
    starts = [s for s, _ in ops]
    modules = sorted((s, s + d, re.sub(r"\(\d+\)$", "", n))
                     for n, s, d in dev["modules"]
                     if "decode" in n or "prefill" in n)
    spans: dict[str, list] = {"device": [], "launch": [], "readback": []}
    for name, s, d in trace["host_spans"]:
        m = ENGINE_SPAN.match(name)
        if m:
            spans[m.group(2)].append((s, s + d, m.group(1)))
    launches, unmatched = [], 0
    for s, e, program in sorted(spans["device"]):
        launch, readback = ([h for h in spans[k] if s <= h[0] and h[1] <= e
                             and h[2] == program]
                            for k in ("launch", "readback"))
        inside = lo <= s and e <= hi
        if len(launch) == 1 and len(readback) <= 1:
            launches.append({"end": e, "program": program,
                             "inside": inside, "launch": launch[0],
                             "readback": readback[0] if readback else None})
        elif launch or len(readback) > 1:
            unmatched += inside
    returns = {k: sorted(h[1] for h in spans["readback"] if h[2] == k)
               for k in ("decode", "prefill")}
    turn = _offset(launches, modules, returns["prefill"])
    for row in launches:        # a program's turn passes only to its kind
        row["turn"] = None
        if turn < len(modules) and row["program"] in modules[turn][2]:
            row["turn"], turn = turn, turn + 1
    rows = []
    for i, row in enumerate(launches):
        if not row["inside"]:
            continue
        if row["turn"] is None:
            unmatched += 1
            continue
        p0, p1, name = modules[row["turn"]]
        fence = row["readback"][1] if row["readback"] else None
        if row["program"] == "decode":
            after = bisect.bisect_left(returns["decode"], p1)
            fence = (returns["decode"][after]
                     if after < len(returns["decode"]) else None)
        # a program's operations are those that start inside it
        busy = xplane.total(xplane.union(
            [[a, min(b, p1)] for a, b in ops[bisect.bisect_left(starts, p0):
                                             bisect.bisect_left(starts, p1)]]))
        before = modules[row["turn"] - 1][1] if row["turn"] else lo
        nxt = next((r for r in launches[i + 1:]
                    if r["turn"] is not None and r["inside"]), None)
        tail = between = None
        if fence is not None and nxt:
            # the device's idle time behind this program, before the next
            # launch began: the host waits for the fence, then works
            begun = max(nxt["launch"][0], p1)
            tail = min(max(fence, p1), begun) - p1
            between = begun - p1 - tail
        elif fence is not None:
            tail = fence - p1
        rows.append({"program": name, "launch_start": row["launch"][0],
                     "readback_end": fence,
                     "pipelined": fence is not None and fence > row["end"],
                     "lead": p0 - max(row["launch"][0], before),
                     "return_to_start": p0 - row["launch"][1],
                     "bubbles": (p1 - p0) - busy,
                     "tail": tail, "between": between,
                     "home": None if fence is None else fence - p1})
    idle_s = reduced["window_s"] - reduced["busy_s"]
    accounted_s = sum(row[k] or 0 for row in rows
                      for k in ("lead", "bubbles", "tail", "between")) / 1e9
    programs = {}
    for name in sorted({row["program"] for row in rows}):
        mine = [row for row in rows if row["program"] == name]
        programs[name] = {
            "n": len(mine),
            "unfenced": sum(row["readback_end"] is None for row in mine),
            "pipelined": sum(row["pipelined"] for row in mine)}
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
              f"without a readback, {row['pipelined']} read back in a later "
              f"span   (p50 ms / p90 ms / sum s)")
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
