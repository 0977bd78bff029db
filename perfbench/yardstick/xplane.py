"""Reduction of a profiler trace to the numbers the per-layer metrics
read: device busy and idle time, time by operation and by program, idle
gaps by the host span open at the time, exposed collective time.

Two steps, so that the arithmetic can be checked without a chip:
``extract`` reads an ``.xplane.pb`` (through ``jax.profiler.ProfileData``,
the only use of jax here, and never on the device) into plain lists of
``[name, start_ns, duration_ns]``; ``reduce`` works on those lists, and on
the small recorded ones kept under ``tests/data``."""

from __future__ import annotations

import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# Host spans that idle gaps are named after: the job scripts' own and the
# program's (``observability/trace.py`` enters a profiler annotation per
# span, so they sit in the trace on the device operations' clock).
HOST_SPAN_PREFIX = ("bench:", "tony:")
TRACED_SPAN = "bench:traced"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|AllReduce|AllGather|ReduceScatter|AllToAll|CollectivePermute")
NO_SPAN = "_no_host_span_"


def extract(path: str) -> dict:
    """{"devices": {"0": {"ops": [...], "modules": [...]}},
    "host_spans": [[name, start_ns, dur_ns], ...]}; an op's name carries
    its HLO category and shape where the trace gives them."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host_spans": [], "planes": []}
    for plane in data.planes:
        lines = list(plane.lines)
        out["planes"].append([plane.name, [ln.name for ln in lines]])
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [[_op_name(e), e.start_ns, e.duration_ns]
                                  for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events]
            out["devices"][m.group(1)] = dev
        elif plane.name.startswith("/host:"):
            for line in lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        out["host_spans"].append(
                            [e.name, e.start_ns, e.duration_ns])
    return out


HLO_TEXT = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)* = (.*)$", re.S)
SHAPE = re.compile(r"([a-z]+[0-9]*\[[0-9,]*\])")


def _op_name(event) -> str:
    """``<op>:<first result shape>``, as in ``fusion:bf16[32,1,28672]``.
    The trace names a device operation by its whole HLO text
    (``fusion.174 = bf16[32,1,28672]{...} fusion(...)``); the counter after
    the op's name changes with every compile, so it is dropped, and the
    result's shape tells equal-named fusions apart."""
    m = HLO_TEXT.match(event.name)
    if not m:
        return event.name[:64]
    if 'custom_call_target="tpu_custom_call"' in event.name:
        # A Pallas (Mosaic) kernel: the trace names it after whatever jax
        # scope enclosed it ("checkpoint", "pallas_call"), so its result
        # shapes are its name here: mosaic:(bf16[128,2048,128],f32[...]).
        results = m.group(2).split(" custom-call(", 1)[0]
        shapes = SHAPE.findall(results)
        sig = shapes[0] if len(shapes) == 1 else "(" + ",".join(shapes) + ")"
        return f"mosaic:{sig}"[:64]
    shape = SHAPE.search(m.group(2).split(" ", 1)[0] + " ")
    if shape is None:
        shape = SHAPE.search(m.group(2)[:200])
    return (f"{m.group(1)}:{shape.group(1)}" if shape else m.group(1))[:64]


def self_times(ops) -> list:
    """(name, self_ns) of every operation: its duration less that of the
    operations nested directly inside it (a ``while`` holds its body's
    operations on the same line; ranking by duration would count them
    twice)."""
    out, stack = [], []

    def close(frame):
        out.append((frame[0], (frame[2] - frame[1]) - frame[3]))

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0])
    while stack:
        close(stack.pop())
    return out


def union(intervals) -> list:
    """Merge [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(intervals, holes) -> list:
    """Parts of merged ``intervals`` not covered by merged ``holes``."""
    out = []
    for s, e in intervals:
        cur = s
        for hs, he in holes:
            if he <= cur or hs >= e:
                continue
            if hs > cur:
                out.append([cur, hs])
            cur = max(cur, he)
        if cur < e:
            out.append([cur, e])
    return out


def window_of(trace: dict) -> tuple:
    """The traced window: the ``bench:traced`` host span where the job
    wrote one, else the extent of the device operations."""
    spans = [(s, s + d) for n, s, d in trace["host_spans"]
             if n == TRACED_SPAN]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    ops = [(s, s + d) for dev in trace["devices"].values()
           for _, s, d in dev["ops"] if d > 0]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return min(s for s, _ in ops), max(e for _, e in ops)


def _innermost(spans, lo, hi):
    """Cut [lo, hi) at span boundaries; each piece goes to the covering
    span that started last (the innermost), else to NO_SPAN."""
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2.0
        covering = [(s, n) for n, s, e in spans if s <= mid < e]
        yield (max(covering)[1] if covering else NO_SPAN), b - a


def _gaps_by_span(spans, idle):
    """``_innermost`` over every idle interval (sorted, disjoint), given
    only the spans that can cover it: a span a program writes per
    iteration comes by the thousand, the idle intervals by the ten
    thousand, and all but a few of their pairs are far apart."""
    open_spans, nxt = [], 0
    for s, e in idle:
        while nxt < len(spans) and spans[nxt][1] < e:
            open_spans.append(spans[nxt])
            nxt += 1
        open_spans = [sp for sp in open_spans if sp[2] > s]
        yield from _innermost(open_spans, s, e)


def reduce(trace: dict) -> dict:
    """Seconds, not nanoseconds, in everything returned. Times by
    operation and by span are means over the devices."""
    lo, hi = window_of(trace)
    spans = sorted(((n, s, s + d) for n, s, d in trace["host_spans"]
                    if n != TRACED_SPAN), key=lambda sp: sp[1])
    devices = {}
    op_time: dict[str, float] = {}
    op_calls: dict[str, int] = {}
    gaps_by_span: dict[str, float] = {}
    modules: dict[str, list] = {}
    for dev_id, dev in trace["devices"].items():
        ops = [(n, max(s, lo), min(s + d, hi)) for n, s, d in dev["ops"]
               if min(s + d, hi) > max(s, lo)]
        busy = union([[s, e] for _, s, e in ops])
        idle = subtract([[lo, hi]], busy)
        coll = union([[s, e] for n, s, e in ops if COLLECTIVE.search(n)])
        compute = union([[s, e] for n, s, e in ops
                         if not COLLECTIVE.search(n)])
        devices[dev_id] = {
            "busy_s": total(busy) / 1e9,
            "idle_s": total(idle) / 1e9,
            "collective_s": total(coll) / 1e9,
            "collective_exposed_s": total(subtract(coll, compute)) / 1e9,
        }
        for n, self_ns in self_times(ops):
            op_time[n] = op_time.get(n, 0.0) + self_ns / 1e9
            op_calls[n] = op_calls.get(n, 0) + 1
        for name, dur in _gaps_by_span(spans, idle):
            gaps_by_span[name] = gaps_by_span.get(name, 0.0) + dur / 1e9
        for n, s, d in dev["modules"]:
            if lo <= s < hi:      # a program belongs where it started
                modules.setdefault(re.sub(r"\(\d+\)$", "", n), []
                                   ).append(d / 1e9)
    n_dev = max(len(devices), 1)

    def ranked(table):
        return [[n, t / n_dev]
                for n, t in sorted(table.items(), key=lambda kv: -kv[1])]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(d["busy_s"] for d in devices.values()) / n_dev,
        "devices": devices,
        "device_ops": ranked(op_time),
        "device_op_calls": {n: c / n_dev for n, c in op_calls.items()},
        "idle_gaps": ranked(gaps_by_span),
        "programs": {n: {"calls": len(v), "total_s": sum(v),
                         "median_s": statistics.median(v)}
                     for n, v in modules.items()},
    }


def program(reduced: dict, needle: str):
    """The traced programs whose name holds ``needle`` taken as one, or
    None when there is none."""
    hits = [v for n, v in reduced["programs"].items() if needle in n]
    if not hits:
        return None
    return {"calls": sum(h["calls"] for h in hits),
            "total_s": sum(h["total_s"] for h in hits),
            "median_s": statistics.median([h["median_s"] for h in hits])}


if __name__ == "__main__":
    import json
    import sys

    with open(sys.argv[2], "w") as out:
        json.dump(extract(sys.argv[1]), out)
