"""``tools/step_ops.py``'s anatomy of a dispatch on a hand-written event
list, in the form the harness keeps (``trace_events.json``: host spans and
one device's operations and programs on one clock, nanoseconds): a launch
that returns before its program starts and one that returns after, a
program with a gap between two operations, a ``while`` that holds its
body, a device span cut by the window's edge and one with no program; rounds
launched without a readback (ISSUE 41); iterations launched before the one
ahead of them is read back (ISSUE 44)."""

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


def test_a_pipelined_iteration_is_fenced_by_the_next_spans_readback(modules):
    """ISSUE 44: a decode span holds the launch of one iteration and the
    readback of the one BEFORE. The trace begins with an iteration k0 on
    the device that was launched before it (no launch span): programs go
    to launches by order from the one moment the queue is known empty (a
    fenced round's readback returns), so k0 is nobody's; each iteration
    is fenced by the first decode readback that returns after it ended
    (the last one's lies in a span that launches nothing); the host's wait
    and work behind a program are idle only as far as no program was
    launched behind it, so an iteration's ``tail`` and ``between`` are 0
    and ``home`` says when its tokens came; what stays exposed is the
    fenced round's ``tail``, the work behind it and the ``lead`` of the
    iteration launched then."""
    step_ops, xplane = modules
    trace = {
        "host_spans": [
            ["bench:traced", 500, 8000],
            # launches k1 behind k0, which still runs; reads k0 back
            span("decode_device", 700, 2100),
            span("decode_launch", 700, 1100),
            span("decode_readback", 1150, 2000),
            # R1, launched and left, then k2; k1 is read back
            span("prefill_device", 2200, 2500),
            span("prefill_launch", 2200, 2500),
            span("decode_device", 2600, 3100),
            span("decode_launch", 2600, 2900),
            span("decode_readback", 2950, 3050),
            # R2 holds a first token: fenced, behind k2
            span("prefill_device", 3200, 6000),
            span("prefill_launch", 3200, 3500),
            span("prefill_readback", 3550, 5900),
            span("emit", 6000, 6100),
            span("decode_device", 6200, 6900),
            span("decode_launch", 6200, 6500),
            span("decode_readback", 6550, 6800),
            # no lane is left: nothing launched, k3 read back
            span("decode_device", 7000, 8000),
            span("decode_readback", 7000, 7900),
        ],
        "devices": {"0": {
            "modules": [["jit_decode_window(17)", 600, 1000],     # k0
                        ["jit_decode_window(17)", 1600, 1000],    # k1
                        ["jit_prefill_chunks(23)", 2600, 1000],   # R1
                        ["jit_decode_window(17)", 3600, 1000],    # k2
                        ["jit_prefill_chunks(23)", 4600, 1000],   # R2
                        ["jit_decode_window(17)", 6700, 1000]],   # k3
            "ops": [["fusion:f32[8]", start, 1000]
                    for start in (600, 1600, 2600, 3600, 4600, 6700)],
        }},
    }
    table = step_ops.dispatches(trace, xplane.reduce(trace), xplane)
    rows = [[r[k] for k in ("program", "pipelined", "lead",
                            "return_to_start", "tail", "between", "home")]
            for r in table["rows"]]
    assert rows == [
        ["jit_decode_window", True, 0, 500, 0, 0, 450],
        ["jit_prefill_chunks", False, 0, 100, None, None, None],
        ["jit_decode_window", True, 0, 700, 0, 0, 2200],
        ["jit_prefill_chunks", False, 0, 1100, 300, 300, 300],
        ["jit_decode_window", True, 500, 200, 200, None, 200],
    ]
    assert [r["launch_start"] for r in table["rows"]] == \
        [700, 2200, 2600, 3200, 6200]
    assert table["unmatched_device_spans"] == 0
    decode = table["programs"]["jit_decode_window"]
    assert (decode["n"], decode["unfenced"], decode["pipelined"]) == (3, 0, 3)
    prefill = table["programs"]["jit_prefill_chunks"]
    assert (prefill["n"], prefill["unfenced"], prefill["pipelined"]) == \
        (2, 1, 0)
    # busy 600 -> 5600 and 6700 -> 7700 of 500 -> 8500
    assert table["idle_s"] == pytest.approx(2000e-9)
    # lead 500 + tail 500 + between 300; the edges 500 -> 600, 7900 -> 8500
    assert table["accounted_s"] == pytest.approx(1300e-9)
    assert table["remainder_s"] == pytest.approx(700e-9)
