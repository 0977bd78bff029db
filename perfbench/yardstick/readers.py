"""Shared arithmetic of the per-layer metrics' readers. A reader is a file
under ``metrics/`` named after its metric, with ``read(run)``; ``run``
holds what one run left: ``job`` (the job script's result), ``trace`` (the
reduced device trace, traced runs only), ``serving`` (the load generator's
numbers), ``stages``, ``config``, ``traffic``, ``seconds``, ``device``,
``cell``. What follows from the architecture (a decode iteration's bytes, a
trained token's FLOPs, the attention call's cost) is counted by the cell's
model module, ``run["cell"].model``. A reader that finds nothing to read
returns None, and the metric is left out of the line; it never returns 0
for a share of a peak."""

from __future__ import annotations

import re
import statistics

from yardstick import counts, spec, stats, xplane


def device_idle_pct(run):
    t = run["trace"]
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def peaks_of(run) -> dict:
    return spec.peaks(run["device"]["kind"], run["cell"].root)


def train_tokens_per_s_per_chip(run) -> float:
    job = run["job"]
    return stats.train_rate(job["step_ends"], job["tokens_per_step"],
                            job["window_s"], run["cell"].chips)


def median_ms(values):
    return statistics.median(values) if values else None


def flash_roofline(run, patterns: dict):
    """Every flash-attention Mosaic call of the traced window: the least
    time the chip could take for what each call needs (the larger of its
    FLOPs over the peak and its bytes over the bandwidth), summed, over
    the device time the calls took. ``patterns``: call kind -> regex on
    the traced operation's name."""
    t = run["trace"]
    if not t:
        return None
    cfg, job, model = run["config"], run["job"], run["cell"].model
    peaks = peaks_of(run)
    mesh = cfg["run"].get("mesh", {})
    # One call sees one device's shard: batch over dp, heads over tp.
    batch = job["batch"] // int(mesh.get("dp", 1))
    least, took = 0.0, 0.0
    for kind, pattern in patterns.items():
        rx = re.compile(pattern)
        cost = model.attention_call_cost(cfg, kind, batch, job["seq"],
                                         tp=int(mesh.get("tp", 1)))
        floor, _ = counts.roofline_seconds(cost["flops"], cost["bytes"],
                                           peaks)
        for name, seconds in t["device_ops"]:
            if rx.search(name):
                least += floor * t["device_op_calls"][name]
                took += seconds
    return 100.0 * least / took if took > 0 else None


def decode_hbm_roofline(run):
    """Bytes one decode iteration needs (weights once, K and V of the live
    positions of the slots in decode, the rows written) over the HBM
    bandwidth, over the device time of one ``decode_window`` program."""
    t = run["trace"]
    prog = xplane.program(t, "decode_window") if t else None
    if not prog:
        return None
    lo, hi = run["traced_span_client"]
    slots, positions = stats.live_load(run["requests"], lo, hi)
    if slots <= 0:
        return None
    need = run["cell"].model.decode_iter_bytes(run["config"], positions,
                                               slots)
    floor = need / peaks_of(run)["hbm_bytes_per_s"]
    steps = int(run["job"]["decode_window"])
    return 100.0 * floor * steps / prog["median_s"]
