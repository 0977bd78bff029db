"""The five readers of the engine's own counters, on a hand-written
``engine_stats``: each gives the hand-computed value, and None where the
program has no such counter (the parent commit's ``stats()``)."""

from __future__ import annotations

import pytest

from yardstick import spec

ENGINE_STATS = {
    # what the parent commit's stats() already had
    "slots": 32, "active_slots": 0, "queue_depth": 0, "prefilling": 0,
    "iterations": 1400, "requests": 150, "retired": 150,
    # what the spans' boundaries count
    "working_iterations": 1000,
    "working_wall_ms": 80000.0,
    "phase_ms": {"admit": 100.0, "prefill_assemble": 300.0,
                 "prefill_device": 30000.0, "decode_device": 46000.0,
                 "emit": 2000.0, "publish": 1000.0},
    "kv": {"reserved_positions": 65536, "bytes_per_position": 65536,
           "live_position_ms": 65536 * 80000.0 * 0.1875},
    "queue_wait_ms": {"n": 150, "mean": 40.0, "p50": 31.0, "p90": 88.5,
                      "max": 140.0},
    "prefill_span_ms": {"n": 147, "mean": 900.0, "p50": 700.0, "p90": 2050.25,
                        "max": 3100.0},
}
PARENT_STATS = {k: ENGINE_STATS[k] for k in (
    "slots", "active_slots", "queue_depth", "prefilling", "iterations",
    "requests", "retired")}
# 100 x (80,000 - 30,000 - 46,000) / 80,000
HOST_SHARE = 5.0
WANT = {
    "queue_wait_p90_ms": 88.5,
    "prefill_span_p90_ms": 2050.25,
    "engine_host_share_pct.serve-steady": HOST_SHARE,
    "engine_host_share_pct.serve-saturated": HOST_SHARE,
    "kv_live_pct": 18.75,
}


def reader(name: str):
    bench = spec.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["source"] == "program_counter"
    return spec.Cell(bench, entry["workloads"][0]).reader(name)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_the_hand_computed_value(name):
    value = reader(name)({"job": {"engine_stats": ENGINE_STATS}})
    assert value == pytest.approx(WANT[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize("job", [
    {"engine_stats": PARENT_STATS}, {"engine_stats": {}}, {},
    # an engine that never worked, or retired nothing, has no reading
    {"engine_stats": dict(ENGINE_STATS, working_wall_ms=0.0,
                          queue_wait_ms={"n": 0, "p90": None},
                          prefill_span_ms={"n": 0, "p90": None})},
], ids=["parent", "empty", "absent", "idle"])
def test_reader_gives_none_without_its_counter(name, job):
    assert reader(name)({"job": job}) is None


def test_the_five_are_in_the_benchmark_with_their_cells():
    """What each of the five is (unit, direction, layer, the end-to-end
    metric it moves) and that the cells PR 25 gave it are IN its list: a
    later configuration's cell is appended to a list, and a later metric
    to ``per_layer``, without an edit here."""
    bench = spec.load_benchmark()
    steady, saturated = ("mistral7b.serve.chat-steady",
                         "mistral7b.serve.chat-saturated")
    want = {
        "queue_wait_p90_ms":
            ("ms", "lower", "engine", "serve_ttft_p90_ms", steady),
        "prefill_span_p90_ms":
            ("ms", "lower", "engine", "serve_ttft_p90_ms", steady),
        "engine_host_share_pct.serve-steady":
            ("%", "lower", "engine", "serve_tpot_p90_ms", steady),
        "engine_host_share_pct.serve-saturated":
            ("%", "lower", "engine", "serve_tokens_per_s", saturated),
        "kv_live_pct":
            ("%", "higher", "decode", "serve_tokens_per_s", saturated),
    }
    got = {m["name"]: m for m in bench["per_layer"] if m["name"] in want}
    assert sorted(got) == sorted(want)
    moved = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    for name, (unit, better, layer, moves, cell) in want.items():
        m = got[name]
        assert (m["unit"], m["better"], m["layer"], m["moves"]) == (
            unit, better, layer, moves), name
        assert cell in m["workloads"], name
        # every cell that reads it reports the metric it moves
        assert set(m["workloads"]) <= set(moved[moves]), name
