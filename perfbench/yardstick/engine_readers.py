"""Shared arithmetic of the per-layer metrics that read the program's own
counters: ``ServingEngine.stats()`` as the job script took it after
``engine.close()`` (``run["job"]["engine_stats"]``). The engine counts
them at its spans' boundaries (``tony:engine.*``, ``tony:request.*``; see
``tony_tpu/serving/scheduler.py``).

They cover the engine's LIFE in the job — three warm-up requests, the
10 s pre-roll, the window and the drain, all but the warm-up the cell's
own traffic — not the window alone. A program that has no such counter
(the commit before they were added) gives None, and the metric is left
out of the line."""

from __future__ import annotations


def engine_stats(run) -> dict:
    return (run.get("job") or {}).get("engine_stats") or {}


def latency_p90(run, key: str):
    """``p90`` of one of the engine's latency rings (its last 512 retired
    requests): ``queue_wait_ms`` or ``prefill_span_ms``."""
    ring = engine_stats(run).get(key)
    if not ring or ring.get("p90") is None:
        return None
    return float(ring["p90"])


def host_share_pct(run):
    """Of the wall time of the iterations that did work, the share not
    spent between a dispatch and its readback's return: 100 x
    (working_wall_ms - prefill_device - decode_device) / working_wall_ms.
    The engine is synchronous, so the device idles through all of it."""
    stats = engine_stats(run)
    wall, phases = stats.get("working_wall_ms"), stats.get("phase_ms")
    if not wall or not phases:
        return None
    prefill, decode = phases.get("prefill_device"), phases.get("decode_device")
    if prefill is None or decode is None:
        return None
    return 100.0 * (wall - prefill - decode) / wall


def kv_live_pct(run):
    """KV positions written in occupied slots over positions reserved
    (slots x max_len), weighted by each working iteration's wall."""
    stats = engine_stats(run)
    kv, wall = stats.get("kv"), stats.get("working_wall_ms")
    if not kv or not wall or not kv.get("reserved_positions"):
        return None
    return (100.0 * kv["live_position_ms"]
            / (kv["reserved_positions"] * wall))
