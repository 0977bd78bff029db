"""Shared arithmetic of the per-layer metrics that split the device's idle
time inside a dispatch by the host span open at the time: the program
divides ``tony:engine.decode_device`` and ``tony:engine.prefill_device``
at the moment the jitted call returns (``tony_tpu/serving/scheduler.py``),
into a ``*_launch`` span (argument handling, the host arrays' copies up,
the enqueue) and a ``*_readback`` span (the fenced ``jax.device_get``).
``run["trace"]["idle_gaps"]`` puts every idle interval of the traced
window down to the innermost such span, so the seconds under these names
are the device's idle time while the host was still handing a program
over, and while the host waited on the device.

These are the benchmark's first readers that match a span BY NAME. A
trace in which none of the four names holds an idle gap (the commit before
the split, or a run with no device plane) gives None, and the metric is
left out of the line."""

from __future__ import annotations

LAUNCH_SPANS = ("tony:engine.decode_launch", "tony:engine.prefill_launch")
READBACK_SPANS = ("tony:engine.decode_readback",
                  "tony:engine.prefill_readback")


def _idle_pct(run, spans):
    t = run.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    gaps = dict(t["idle_gaps"])
    if not any(name in gaps for name in LAUNCH_SPANS + READBACK_SPANS):
        return None
    return 100.0 * sum(gaps.get(name, 0.0) for name in spans) / t["window_s"]


def launch_idle_pct(run):
    """100 x idle seconds inside the two launch spans over the traced
    window: the device has nothing to run while the host is still handing
    the next program over."""
    return _idle_pct(run, LAUNCH_SPANS)


def readback_idle_pct(run):
    """100 x idle seconds inside the two readback spans over the traced
    window: arguments still on their way up, gaps between a running
    program's operations, and the result's way back."""
    return _idle_pct(run, READBACK_SPANS)
