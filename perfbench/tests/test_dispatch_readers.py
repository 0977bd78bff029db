"""The four readers of the split dispatch spans, on a hand-written reduced
trace: each gives the hand-computed share, and None where the trace names
none of the four spans (the parent commit's, whose idle gaps lie under the
two undivided device spans). Then a toy serving cell through the whole
traced command on the CPU: no TPU plane, so the four leave their metrics
out, the line still forms, and the engine's own ``stats()["dispatch"]``
is in the job's report. Counts, never times."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

import tinyrepo
from yardstick import spec

DISPATCH = Path(__file__).resolve().parent / "data" / "dispatch"
STEADY = ["mistral7b.serve.chat-steady", "mimo-v2-flash.serve.mixed-steady"]
SATURATED = ["mistral7b.serve.chat-saturated",
             "mimo-v2-flash.serve.mixed-saturated",
             "minicpm-sala.serve.longdoc-saturated"]
# a window of 2.5 s with 0.4 s idle: 0.05 + 0.025 s while a program was
# handed over, 0.2 + 0.1 s while the host waited on its readback, the rest
# in the parents' own remainder, other spans and none
TRACE = {
    "window_s": 2.5, "busy_s": 2.1,
    "idle_gaps": [["tony:engine.decode_readback", 0.2],
                  ["tony:engine.prefill_readback", 0.1],
                  ["tony:engine.decode_launch", 0.05],
                  ["tony:engine.prefill_launch", 0.025],
                  ["tony:engine.emit", 0.0125],
                  ["tony:engine.decode_device", 0.005],
                  ["tony:engine.prefill_device", 0.005],
                  ["_no_host_span_", 0.0025]],
}
PARENT_TRACE = dict(TRACE, idle_gaps=[["tony:engine.decode_device", 0.25],
                                      ["tony:engine.prefill_device", 0.13],
                                      ["tony:engine.emit", 0.02]])
WANT = {
    "dispatch_launch_idle_pct.serve-steady": 3.0,       # 0.075 / 2.5
    "dispatch_launch_idle_pct.serve-saturated": 3.0,
    "dispatch_readback_idle_pct.serve-steady": 12.0,    # 0.3 / 2.5
    "dispatch_readback_idle_pct.serve-saturated": 12.0,
}


def reader(name: str):
    bench = spec.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    return spec.Cell(bench, entry["workloads"][0]).reader(name)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_the_hand_computed_share(name):
    assert reader(name)({"trace": TRACE}) == pytest.approx(WANT[name],
                                                           rel=1e-12)


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_span_with_no_idle_inside_counts_as_nothing(name):
    """One kind of the four is enough to read: the other's share is 0."""
    kind = "launch" if "launch" in name else "readback"
    trace = dict(TRACE, idle_gaps=[g for g in TRACE["idle_gaps"]
                                   if kind not in g[0]])
    assert reader(name)({"trace": trace}) == 0.0


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize("trace", [
    PARENT_TRACE, None, dict(TRACE, window_s=0.0),
    dict(TRACE, idle_gaps=[])], ids=["parent", "untraced", "no-window",
                                     "no-device-plane"])
def test_reader_gives_none_without_the_split_spans(name, trace):
    assert reader(name)({"trace": trace}) is None


def test_the_four_are_in_the_benchmark_with_their_cells():
    bench = spec.load_benchmark()
    want = {
        "dispatch_launch_idle_pct.serve-steady":
            ("serve_tpot_p90_ms", STEADY),
        "dispatch_launch_idle_pct.serve-saturated":
            ("serve_tokens_per_s", SATURATED),
        "dispatch_readback_idle_pct.serve-steady":
            ("serve_tpot_p90_ms", STEADY),
        "dispatch_readback_idle_pct.serve-saturated":
            ("serve_tokens_per_s", SATURATED),
    }
    got = {m["name"]: m for m in bench["per_layer"] if m["name"] in want}
    assert sorted(got) == sorted(want)
    assert [m["name"] for m in bench["per_layer"]][-4:] == [
        "dispatch_launch_idle_pct.serve-steady",
        "dispatch_launch_idle_pct.serve-saturated",
        "dispatch_readback_idle_pct.serve-steady",
        "dispatch_readback_idle_pct.serve-saturated"], "appended, in order"
    moved = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    for name, (moves, cells) in want.items():
        m = got[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == ("%", "lower", "device_trace", "engine",
                                moves), name
        assert set(cells) <= set(m["workloads"]), name
        # every cell that reads it reports the metric it moves
        assert set(m["workloads"]) <= set(moved[moves]), name
    train = spec.Cell(bench, "mistral7b.train.b4x2048")
    assert not set(want) & {m["name"] for m in train.per_layer}


def test_a_toy_cell_runs_traced_and_reports_its_dispatches(tmp_path):
    import run as harness

    repo = tinyrepo.make(tmp_path / "repo", tinyrepo.TINY, DISPATCH)
    cell = spec.Cell(spec.load_benchmark(repo), "tiny.serve", repo)
    assert set(WANT) <= {m["name"] for m in cell.per_layer}
    kept = tmp_path / "work"
    result = harness.run_cell(repo, "tiny.serve", 2**31 + 40, 2.0, True,
                              require_tpu=False, keep_work=kept)["result"]
    assert result["correct"] is True and result["failed"] == 0
    # the CPU's trace has no TPU plane: no idle gap, nothing to read
    assert not set(WANT) & set(result["metrics"])
    assert "slot_occupancy_pct" in result["metrics"]
    stats = json.loads((kept / "window.json").read_text())["engine_stats"]
    decode, prefill = stats["dispatch"]["decode"], stats["dispatch"]["prefill"]
    assert decode["calls"] == stats["decode_iterations"] > 0
    assert prefill["calls"] == stats["prefill_rounds"] > 0
    assert 0 <= prefill["rounds_without_first_token"] < prefill["calls"]
    for program, row in stats["dispatch"].items():
        assert row["h2d_bytes"] > 0 < row["d2h_bytes"]
        assert 0 < row["launch_ms"] + row["readback_ms"] <= \
            stats["phase_ms"][f"{program}_device"]
