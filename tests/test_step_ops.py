"""``tools/step_ops.py``'s anatomy of a dispatch on a hand-written event
list, in the form the harness keeps (``trace_events.json``: host spans and
one device's operations and programs on one clock, nanoseconds): a launch
that returns before its program starts and one that returns after, a
program with a gap between two operations, a ``while`` that holds its
body, a device span cut by the window's edge and one with no program."""

from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def modules():
    return (_load(REPO / "tools" / "step_ops.py", "tools_step_ops"),
            _load(REPO / "perfbench" / "yardstick" / "xplane.py",
                  "perfbench_xplane"))


def span(name, s, e):
    return [f"tony:engine.{name}", s, e - s]


@pytest.fixture(scope="module")
def table(modules):
    step_ops, xplane = modules
    trace = {
        "host_spans": [
            ["bench:traced", 500, 10500],
            # A, decode: the call returns at 1400, the program starts at 1500
            span("step", 900, 3100),
            span("decode_device", 1000, 3000),
            span("decode_launch", 1010, 1400),
            span("decode_readback", 1450, 2800),
            span("emit", 3000, 3100),
            # B, prefill: the device began 400 before the call returned
            span("prefill_device", 3190, 6000),
            span("prefill_launch", 3200, 4000),
            span("prefill_readback", 4010, 5900),
            # C, decode
            span("decode_device", 6500, 9000),
            span("decode_launch", 6500, 6700),
            span("decode_readback", 6750, 8950),
            # both halves and no program; then one cut by the window's end
            span("decode_device", 9500, 9600),
            span("decode_launch", 9500, 9540),
            span("decode_readback", 9550, 9590),
            span("prefill_device", 10500, 12000),
            span("prefill_launch", 10500, 10550),
            span("prefill_readback", 10560, 11900),
        ],
        "devices": {"0": {
            "modules": [["jit_decode_window(17)", 1500, 1000],
                        ["jit_prefill_chunks(23)", 3600, 2000],
                        ["jit_decode_window(17)", 6800, 2000],
                        ["jit_prefill_chunks(23)", 10600, 1200]],
            "ops": [["fusion:f32[8]", 1500, 400], ["fusion:f32[8]", 2000, 500],
                    ["while:s32[]", 3600, 2000], ["fusion:f32[4]", 3700, 300],
                    ["fusion:f32[8]", 6800, 2000],
                    ["fusion:f32[4]", 10600, 600]],
        }},
    }
    return step_ops.dispatches(trace, xplane.reduce(trace), xplane)


def test_each_dispatch_is_put_down_to_its_parts(table):
    rows = [{k: r[k] for k in ("program", "lead", "return_to_start",
                               "bubbles", "tail", "between")}
            for r in table["rows"]]
    assert rows == [
        {"program": "jit_decode_window", "lead": 490, "return_to_start": 100,
         "bubbles": 100, "tail": 300, "between": 400},
        {"program": "jit_prefill_chunks", "lead": 400,
         "return_to_start": -400, "bubbles": 0, "tail": 300, "between": 600},
        {"program": "jit_decode_window", "lead": 300, "return_to_start": 100,
         "bubbles": 0, "tail": 150, "between": None},
    ]
    assert table["unmatched_device_spans"] == 1


def test_the_parts_account_for_the_windows_idle_time(table):
    # busy 900 + 2000 + 2000 and 400 of the cut program, of 10,500
    assert table["window_s"] == pytest.approx(10500e-9)
    assert table["idle_s"] == pytest.approx(5200e-9)
    # lead 1190 + bubbles 100 + tail 750 + between 1000
    assert table["accounted_s"] == pytest.approx(3040e-9)
    # the edges: 500 -> 1010 before A's launch, 8950 -> 10600 after C
    assert table["remainder_s"] == pytest.approx((510 + 1650) * 1e-9)


def test_programs_are_summed_by_nearest_rank(table):
    decode = table["programs"]["jit_decode_window"]
    assert decode["n"] == 2
    assert decode["lead"] == {"p50_ms": pytest.approx(300e-6),
                              "p90_ms": pytest.approx(490e-6),
                              "sum_s": pytest.approx(790e-9)}
    assert decode["between"]["sum_s"] == pytest.approx(400e-9)   # A's only
    prefill = table["programs"]["jit_prefill_chunks"]
    assert prefill["n"] == 1
    assert prefill["return_to_start"]["p50_ms"] == pytest.approx(-400e-6)
    assert prefill["bubbles"]["sum_s"] == 0.0


def test_a_round_without_a_readback_is_a_dispatch_of_its_own(modules):
    """Two prefill rounds launched and left (ISSUE 41: a device span with
    its launch half alone) behind a fenced decode iteration, then the
    iteration that fences them and a fenced round: a program is its
    span's by ORDER (the second round's starts after its span ended, inside
    the iteration's), an unfenced round has no ``tail`` and no ``between``,
    and a program that queued behind another has the gap between the two as
    its ``lead``. The last round's program seems to start before its span
    does (the device's line sits on the host's clock to a millisecond or
    so): still its own, with a negative ``lead`` that its ``tail`` makes
    up."""
    step_ops, xplane = modules
    trace = {
        "host_spans": [
            ["bench:traced", 500, 13500],
            span("decode_device", 1000, 3000),
            span("decode_launch", 1000, 1400),
            span("decode_readback", 1450, 2900),
            # R1: the device is idle, its program starts before the return
            span("prefill_device", 3200, 3700),
            span("prefill_launch", 3200, 3700),
            # R2: launched while R1 runs, its program queues behind R1's
            span("prefill_device", 3800, 4300),
            span("prefill_launch", 3800, 4300),
            # the iteration behind them waits for all three programs
            span("decode_device", 4500, 10500),
            span("decode_launch", 4500, 5000),
            span("decode_readback", 5050, 10400),
            span("prefill_device", 10900, 13500),
            span("prefill_launch", 10900, 11400),
            span("prefill_readback", 11450, 13400),
        ],
        "devices": {"0": {
            "modules": [["jit_decode_window(17)", 1300, 1300],
                        ["jit_prefill_chunks(23)", 3600, 2400],
                        ["jit_prefill_chunks(23)", 6010, 1990],
                        ["jit_decode_window(17)", 8020, 1980],
                        ["jit_prefill_chunks(23)", 10850, 1700]],
            "ops": [["fusion:f32[8]", 1300, 1300], ["fusion:f32[4]", 3600, 2400],
                    ["fusion:f32[4]", 6010, 1990], ["fusion:f32[8]", 8020, 1980],
                    ["fusion:f32[4]", 10850, 1700]],
        }},
    }
    table = step_ops.dispatches(trace, xplane.reduce(trace), xplane)
    rows = [[r[k] for k in ("program", "lead", "return_to_start", "bubbles",
                            "tail", "between")] for r in table["rows"]]
    assert rows == [
        ["jit_decode_window", 300, -100, 0, 300, 300],
        ["jit_prefill_chunks", 400, -100, 0, None, None],
        ["jit_prefill_chunks", 10, 1710, 0, None, None],
        ["jit_decode_window", 20, 3020, 0, 400, 500],
        ["jit_prefill_chunks", -50, -550, 0, 850, None],
    ]
    assert table["unmatched_device_spans"] == 0
    prefill = table["programs"]["jit_prefill_chunks"]
    assert (prefill["n"], prefill["unfenced"]) == (3, 2)
    assert prefill["tail"]["sum_s"] == pytest.approx(850e-9)
    assert prefill["between"] is None
    assert table["programs"]["jit_decode_window"]["unfenced"] == 0
    # busy 9,370 of 13,500; the edges 500 -> 1000 and 13,400 -> 14,000
    assert table["idle_s"] == pytest.approx(4130e-9)
    # lead 680 + tail 1550 + between 800
    assert table["accounted_s"] == pytest.approx(3030e-9)
    assert table["remainder_s"] == pytest.approx(1100e-9)
