"""A prefill round that yields no first token is launched and left
(``serving/scheduler.py``'s docstring): the decode iteration behind it, or
an earlier round that does hold a first token, brings its counts home in
its own readback, and a ``step()`` returns with nothing in flight. The
programs, their arguments and the order of dispatches are those of an
engine that fences every round, so every token is; ``step()`` is driven by
hand on toy models, so the counts are exact.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from test_engine_spans import _drive, _engine, _layered_engine, _spans

MAKE = {"uniform": _engine, "layered": _layered_engine}


def _fence_every_round(eng) -> None:
    """The engine as it was before rounds went unfenced: no round is told
    that a fence follows it."""
    eng._fence_follows = lambda last: False


def _record_dispatches(eng) -> list:
    """Every jitted call's program and the host values among its
    arguments (copies: the engine updates ``_pos`` and ``_last`` in
    place), in launch order."""
    calls = []
    for program in ("_prefill", "_decode"):
        jitted = getattr(eng, program)

        def recorded(*args, _program=program, _jitted=jitted):
            calls.append((_program, [np.array(a) for a in args
                                     if isinstance(a, (np.ndarray,
                                                       np.generic))]))
            return _jitted(*args)

        setattr(eng, program, recorded)
    return calls


def _serve(model: str, fence_all: bool, window: int = 1,
           temperature: float = 0.0):
    eng = MAKE[model](slots=3, prefill_chunk=4, prefill_batch=2, max_len=64,
                      decode_window=window, kv_quant="none")
    if fence_all:
        _fence_every_round(eng)
    calls = _record_dispatches(eng)
    rng = np.random.default_rng(3)
    reqs = [eng.submit(rng.integers(0, 64, n).astype(np.int32), 7,
                       temperature=temperature)
            for n in (5, 9, 13, 17, 3, 22)]
    for _ in range(500):
        if all(r.done() for r in reqs):
            break
        eng.step()
        assert eng._in_flight == []
    else:
        raise AssertionError("requests did not retire")
    eng.close()
    return eng, reqs, calls


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "t0.8"])
@pytest.mark.parametrize("window", [1, 4], ids=["window1", "window4"])
@pytest.mark.parametrize("model", ["uniform", "layered"])
def test_tokens_and_dispatches_are_those_of_a_fence_on_every_round(
        model, window, temperature):
    """Same programs with the same host arguments (positions, last tokens,
    temperatures, draw counters) in the same order, so the same tokens."""
    eng, reqs, calls = _serve(model, False, window, temperature)
    ref, ref_reqs, ref_calls = _serve(model, True, window, temperature)
    assert [r.tokens for r in reqs] == [r.tokens for r in ref_reqs]
    assert all(len(r.tokens) == 7 for r in reqs)
    assert [p for p, _ in calls] == [p for p, _ in ref_calls]
    for (_, mine), (_, theirs) in zip(calls, ref_calls):
        assert len(mine) == len(theirs)
        for a, b in zip(mine, theirs):
            assert a.dtype == b.dtype and np.array_equal(a, b)
    unfenced = eng.stats()["dispatch"]["prefill"]["unfenced"]
    assert 0 < unfenced <= \
        eng.stats()["dispatch"]["prefill"]["rounds_without_first_token"]
    assert ref.stats()["dispatch"]["prefill"]["unfenced"] == 0


def _count_device_gets(monkeypatch) -> list:
    gets = []
    real = jax.device_get

    def counted(x):
        out = real(x)
        gets.append(out)
        return out

    monkeypatch.setattr(jax, "device_get", counted)
    return gets


def test_one_readback_brings_the_window_and_the_round_home(monkeypatch):
    """A step with one round without a first token and an active slot
    calls ``jax.device_get`` once: the window's tokens, the iteration's
    counts and the round's counts come back together."""
    eng = _layered_engine(slots=2, prefill_chunk=4, prefill_batch=2,
                          max_len=64, decode_window=2, kv_quant="none")
    eng.submit(np.arange(3, dtype=np.int32), 9)
    eng.step()                       # its one chunk, fenced; then it decodes
    assert eng._active.sum() == 1
    eng.submit(np.arange(13, dtype=np.int32), 2)     # four chunks
    before = eng.stats()
    gets = _count_device_gets(monkeypatch)
    eng.step()
    assert len(gets) == 1 and eng._in_flight == []
    (toks, counts), flown = gets[0]
    assert np.asarray(toks).shape == (2, 2)
    assert len(flown) == 1
    assert set(flown[0]) == set(counts) == {"pairs", "passes"}
    after = eng.stats()
    assert after["prefill_rounds"] == before["prefill_rounds"] + 1
    assert after["dispatch"]["prefill"]["unfenced"] == 1
    assert after["experts"]["dispatches"] == \
        before["experts"]["dispatches"] + 2
    # the round's 4 tokens and the iteration's 2, through 2 expert layers
    # of 3 choices
    assert after["experts"]["pairs_total"] == \
        before["experts"]["pairs_total"] + (4 + 2) * 2 * 3
    assert after["experts"]["pairs_held"] == \
        before["experts"]["pairs_held"] + int(
            flown[0]["pairs"].sum() + counts["pairs"].sum())
    # the round's counts are prefill's bytes, though decode's readback
    # carried them
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(flown))
    assert nbytes > 0
    assert after["dispatch"]["prefill"]["d2h_bytes"] == \
        before["dispatch"]["prefill"]["d2h_bytes"] + nbytes
    readback = [e for e in _spans(eng)
                if e["name"] == "tony:engine.decode_readback"][-1]
    assert readback["args"]["d2h_bytes"] == nbytes + sum(
        x.nbytes for x in jax.tree_util.tree_leaves((toks, counts)))


@pytest.mark.parametrize("model", ["uniform", "layered"])
def test_a_step_that_decodes_nothing_fences_its_last_round(monkeypatch,
                                                           model):
    """Two long prompts and no active slot: each step's first round is
    launched and left, its second closes the step and is fenced, so
    nothing is in flight when ``step()`` returns."""
    eng = MAKE[model](slots=2, prefill_chunk=4, prefill_batch=1, max_len=64,
                      kv_quant="none")
    reqs = [eng.submit(np.arange(n, dtype=np.int32), 2) for n in (13, 14)]
    gets = _count_device_gets(monkeypatch)
    for step in range(3):            # chunks 1..3 of 4: no first token
        eng.step()
        assert eng._in_flight == []
        assert len(gets) == step + 1
        # the first round's counts, brought home by the second's readback
        assert len(gets[-1][1]) == (model == "layered")
    st = eng.stats()
    assert st["decode_iterations"] == 0 and st["prefill_rounds"] == 6
    assert st["dispatch"]["prefill"]["rounds_without_first_token"] == 6
    assert st["dispatch"]["prefill"]["unfenced"] == 3
    assert [e["args"]["fenced"] for e in _spans(eng)
            if e["name"] == "tony:engine.prefill_device"] == [False, True] * 3
    if model == "layered":
        assert st["experts"]["dispatches"] == 6
        assert st["experts"]["pairs_total"] == 6 * 4 * 2 * 3
    _drive(eng, reqs)
    assert eng._in_flight == []


@pytest.mark.parametrize("prompts,batch,rounds", [
    # A (one chunk, decodes for eight steps) beside B (four chunks), one
    # entry a round: A1* ; B1, B2, B3 behind A's decode iterations ; B4*
    ([(3, 9), (14, 2)], 1,
     [(1, True), (0, False), (0, False), (0, False), (1, True)]),
    # one prompt of four chunks alone: every round closes a step that
    # decodes nothing
    ([(14, 2)], 1, [(0, True), (0, True), (0, True), (1, True)]),
    # two prompts of three chunks, one entry a round: D1 E1 ; D2 E2 ; D3*
    # E3* (E3 follows D's first token, but holds its own)
    ([(9, 2), (9, 2)], 1,
     [(0, False), (0, True), (0, False), (0, True), (1, True), (1, True)]),
    # the same two in one round a step
    ([(9, 2), (9, 2)], 2, [(0, True), (0, True), (2, True)]),
], ids=["behind-decode", "alone", "two-rounds-a-step", "one-round-a-step"])
def test_unfenced_and_fenced_rounds_are_the_calls(prompts, batch, rounds):
    """``unfenced`` + the rounds with a readback of their own = ``calls``,
    and ``unfenced <= rounds_without_first_token``, on chunk plans by
    hand: (first tokens, fenced) a round."""
    eng = _engine(slots=2, prefill_chunk=4, prefill_batch=batch, max_len=64)
    reqs = [eng.submit(np.arange(n, dtype=np.int32), new)
            for n, new in prompts]
    _drive(eng, reqs)
    spans = _spans(eng)
    assert [e["args"]["fenced"] for e in spans
            if e["name"] == "tony:engine.prefill_device"] == \
        [fenced for _, fenced in rounds]
    readbacks = [e["args"]["first_tokens"] for e in spans
                 if e["name"] == "tony:engine.prefill_readback"]
    assert readbacks == [f for f, fenced in rounds if fenced]
    prefill = eng.stats()["dispatch"]["prefill"]
    assert prefill["calls"] == len(rounds)
    assert prefill["unfenced"] == sum(not fenced for _, fenced in rounds)
    assert prefill["unfenced"] + len(readbacks) == prefill["calls"]
    assert prefill["rounds_without_first_token"] == \
        sum(f == 0 for f, _ in rounds) >= prefill["unfenced"]


@pytest.mark.parametrize("window", [1, 4], ids=["window1", "window4"])
def test_expert_stats_are_those_of_a_fence_on_every_round(window):
    eng, _, _ = _serve("layered", False, window)
    ref, _, _ = _serve("layered", True, window)
    mine, theirs = eng.stats()["experts"], ref.stats()["experts"]
    assert set(mine) == {"held", "pairs_per_expert", "pairs_held",
                         "decode_pairs", "prefill_pairs", "pairs_total",
                         "dispatches", "passes"}
    assert mine == theirs
    assert mine["decode_pairs"] + mine["prefill_pairs"] == mine["pairs_held"]
    assert mine["dispatches"] == (eng.stats()["prefill_rounds"]
                                  + eng.stats()["decode_iterations"])


def test_an_unfenced_round_has_its_launch_alone():
    """The span tree: ``fenced=False`` on the device span, one launch
    child and no readback child; the wait for it
    lies in the readback that fenced it, and each program's two halves
    stay inside its device phase. ``expert_pairs`` summed over the device
    spans is still ``pairs_held``."""
    eng, _, _ = _serve("layered", False)
    spans = [e for e in _spans(eng) if e["name"].startswith("tony:engine.")]
    rounds = [e for e in spans if e["name"] == "tony:engine.prefill_device"]
    unfenced = [e for e in rounds if not e["args"]["fenced"]]
    st = eng.stats()
    assert len(unfenced) == st["dispatch"]["prefill"]["unfenced"] > 0
    for dev in unfenced:
        mine = [e for e in spans
                if e["args"]["parent_id"] == dev["args"]["span_id"]]
        assert [e["name"] for e in mine] == ["tony:engine.prefill_launch"]
        # microsecond export: a stamp may round one tick either way
        assert dev["ts"] <= mine[0]["ts"]
        assert mine[0]["ts"] + mine[0]["dur"] <= dev["ts"] + dev["dur"] + 1
        assert "expert_pairs" not in dev["args"]
    assert len([e for e in spans
                if e["name"] == "tony:engine.prefill_readback"]) == \
        len(rounds) - len(unfenced)
    for program, row in st["dispatch"].items():
        assert (row["launch_ms"] + row["readback_ms"]
                <= st["phase_ms"][f"{program}_device"])
    devices = rounds + [e for e in spans
                        if e["name"] == "tony:engine.decode_device"]
    assert sum(e["args"].get("expert_pairs", 0) for e in devices) == \
        st["experts"]["pairs_held"]


@pytest.mark.parametrize("fails_at,batch,unfenced", [
    # one entry a round: the step's first round is launched and left, and
    # the readback of its second brings the first one's results home
    ("unfenced", 1, 1),
    # both entries in one round, which closes the step: its own readback
    ("fenced", 2, 0),
])
def test_a_readback_that_raises_fails_every_pending_request(
        monkeypatch, fails_at, batch, unfenced):
    """A device error of an unfenced round surfaces in the readback that
    brings its results home, inside the step that launched it: the loop
    ends and every pending request fails with the error, as where a
    fenced round's own readback raises."""
    eng = _layered_engine(slots=2, prefill_chunk=4, prefill_batch=batch,
                          max_len=64, max_queue=8, kv_quant="none")
    real = jax.device_get

    def failing(x):
        _own, flights = x
        if fails_at == "fenced" or flights:
            raise RuntimeError("the device fell over")
        return real(x)

    monkeypatch.setattr(jax, "device_get", failing)
    # two in slots, one queued behind them
    reqs = [eng.submit(np.arange(n, dtype=np.int32), 3) for n in (13, 14, 5)]
    eng.start()
    try:
        for req in reqs:
            with pytest.raises(RuntimeError, match="engine loop failed: "
                                                   "the device fell over"):
                req.result(timeout=60)
        assert eng._stop.is_set()
        assert eng.stats()["dispatch"]["prefill"]["unfenced"] == unfenced
        with pytest.raises(RuntimeError, match="shut down"):
            eng.submit(np.arange(3, dtype=np.int32), 2)
    finally:
        eng.close()
