"""What the engine leaves on the device past a dispatch
(``serving/scheduler.py``'s docstring). A prefill round that yields no
first token is launched and left: the step's decode dispatch, or a later
round that does hold a first token, brings its counts home in its own
readback. A decode iteration is launched before the one ahead of it is
read back, and stays in flight past a ``step()`` that leaves a lane
active; an engine with no lane active has nothing in flight. The
programs, their arguments and the order of dispatches are those of an
engine that fences every round, and the tokens those of one that reads
every iteration back before it launches the next; ``step()`` is driven by
hand on toy models, so the counts are exact.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from tony_tpu.models import init_params
from tony_tpu.observability.metrics import MetricsRegistry
from tony_tpu.serving import ServingEngine

from test_engine_spans import _drive, _engine, _layered_engine, _spans

MAKE = {"uniform": _engine, "layered": _layered_engine}


def _fence_every_round(eng) -> None:
    """The engine as it was before rounds went unfenced: no round is told
    that a fence follows it."""
    eng._fence_follows = lambda last: False


def _drain_every_step(eng) -> None:
    """The engine as it was before iterations were pipelined: every
    iteration is read back in the step that launched it, so the next one
    is launched knowing every token."""
    step = eng.step

    def drained() -> bool:
        working = step()
        if eng._flight is not None:
            eng._decode_dispatch(0, np.zeros(eng.slots, bool))
        return working

    eng.step = drained


def _nothing_left_behind(eng) -> None:
    """The invariant between steps: an iteration is in flight only with a
    lane active (the next step reads it back), and unfenced rounds'
    counts wait only for such an iteration's readback."""
    if not eng._active.any():
        assert eng._flight is None
    if eng._flight is None:
        assert eng._in_flight == []
        assert not eng._ahead.any()


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
        _nothing_left_behind(eng)
    else:
        raise AssertionError("requests did not retire")
    assert eng.step() is False and eng._flight is None
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
    calls ``jax.device_get`` once, after it has launched its iteration:
    the tokens and counts of the iteration launched the step before and
    the round's counts come back together."""
    eng = _layered_engine(slots=2, prefill_chunk=4, prefill_batch=2,
                          max_len=64, decode_window=2, kv_quant="none")
    eng.submit(np.arange(3, dtype=np.int32), 9)
    eng.step()          # its one chunk, fenced; its first iteration launched
    assert eng._active.sum() == 1 and eng._flight is not None
    eng.submit(np.arange(13, dtype=np.int32), 2)     # four chunks
    before = eng.stats()
    gets = _count_device_gets(monkeypatch)
    eng.step()
    assert len(gets) == 1 and eng._in_flight == []
    assert eng._flight is not None and eng.stats()["decode_iterations"] == 2
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
        assert eng._in_flight == [] and eng._flight is None
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
    _nothing_left_behind(eng)
    assert not eng._active.any()


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


# -- decode iterations pipelined one deep --------------------------------------
PROMPT_LENS = (5, 9, 13, 17, 3, 22)
BUDGETS = (7, 2, 5, 1, 9, 4)


def serve_pipelined_and_drained(make, *, window: int,
                                temperature: float = 0.0, eos=None,
                                prompt_lens=PROMPT_LENS, budgets=BUDGETS,
                                vocab: int = 64):
    """The same requests through an engine as it is and through one
    drained after every step; ``make(decode_window=)`` builds one. Returns
    both engines (closed) and both lists of requests."""
    served = []
    for drained in (False, True):
        eng = make(decode_window=window)
        if drained:
            _drain_every_step(eng)
        rng = np.random.default_rng(3)
        reqs = [eng.submit(rng.integers(0, vocab, n).astype(np.int32), new,
                           temperature=temperature,
                           eos_id=None if eos is None else eos[i])
                for i, (n, new) in enumerate(zip(prompt_lens, budgets))]
        for _ in range(500):
            if all(r.done() for r in reqs):
                break
            eng.step()
            _nothing_left_behind(eng)
        else:
            raise AssertionError("requests did not retire")
        assert eng.step() is False and eng._flight is None
        eng.close()
        served.append((eng, reqs))
    (eng, reqs), (ref, ref_reqs) = served
    return eng, reqs, ref, ref_reqs


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "t0.8"])
@pytest.mark.parametrize("window", [1, 2], ids=["window1", "window2"])
@pytest.mark.parametrize("model", ["uniform", "layered"])
def test_tokens_are_those_of_an_engine_drained_after_every_step(
        model, window, temperature):
    """Mixed prompt and output lengths: every request's tokens are those
    of the engine that reads each iteration back before it launches the
    next (the order before the pipeline). Greedy requests share three
    slots, so slots are reused; sampled ones have a slot each, since a
    slot freed a step later would shift the draws of those behind it."""
    eng, reqs, ref, ref_reqs = serve_pipelined_and_drained(
        lambda **kw: MAKE[model](slots=6 if temperature else 3,
                                 prefill_chunk=4, prefill_batch=2,
                                 max_len=64, kv_quant="none", **kw),
        window=window, temperature=temperature)
    assert [r.tokens for r in reqs] == [r.tokens for r in ref_reqs]
    assert [len(r.tokens) for r in reqs] == list(BUDGETS)
    decode, theirs = (e.stats()["dispatch"]["decode"] for e in (eng, ref))
    assert 0 < decode["pipelined"] <= decode["calls"]
    assert theirs["pipelined"] == 0
    assert decode["discarded_tokens"] == theirs["discarded_tokens"] == 0
    assert eng.tokens_generated == sum(BUDGETS)
    if temperature:      # a slot each: the same launches step for step
        assert decode["calls"] == theirs["calls"]


def _eos_in_the_middle(make, window: int):
    """``eos_id`` per request of PROMPT_LENS / BUDGETS taken from its own
    greedy continuation (its middle token; None where it makes fewer than
    three), and where that token FIRST occurs in it."""
    eng = make(decode_window=window)
    rng = np.random.default_rng(3)
    reqs = [eng.submit(rng.integers(0, 64, n).astype(np.int32), new)
            for n, new in zip(PROMPT_LENS, BUDGETS)]
    _drive(eng, reqs)
    eng.close()
    eos = [r.tokens[len(r.tokens) // 2] if len(r.tokens) >= 3 else None
           for r in reqs]
    ends = [None if e is None else r.tokens.index(e)
            for r, e in zip(reqs, eos)]
    return reqs, eos, ends


@pytest.mark.parametrize("window", [1, 2], ids=["window1", "window2"])
@pytest.mark.parametrize("model", ["uniform", "layered"])
def test_a_request_that_ends_by_eos_is_found_one_iteration_late(model,
                                                                window):
    """Its tokens are the drained engine's (its continuation up to the
    EOS), the window launched for it meanwhile is counted in
    ``discarded_tokens`` and in nothing else, and ``tokens_generated`` is
    what the clients received."""
    def make(**kw):
        return MAKE[model](slots=3, prefill_chunk=4, prefill_batch=2,
                           max_len=64, kv_quant="none", **kw)

    plain, eos, ends = _eos_in_the_middle(make, window)
    assert sum(e is not None for e in eos) >= 3
    eng, reqs, ref, ref_reqs = serve_pipelined_and_drained(
        make, window=window, eos=eos)
    assert [r.tokens for r in reqs] == [r.tokens for r in ref_reqs] == \
        [p.tokens if at is None else p.tokens[:at + 1]
         for p, at in zip(plain, ends)]
    # Token ``at`` (0 is the prompt's first token) comes home in window
    # ceil(at / w) of ceil((budget - 1) / w) that the count allows: one
    # more was launched unless it was the last, or the first token itself.
    late = sum(at is not None and 0 < at
               and -(-at // window) < -(-(new - 1) // window)
               for at, new in zip(ends, BUDGETS))
    assert late > 0
    decode = eng.stats()["dispatch"]["decode"]
    assert decode["discarded_tokens"] == late * window
    assert ref.stats()["dispatch"]["decode"]["discarded_tokens"] == 0
    assert eng.tokens_generated == ref.tokens_generated == \
        sum(len(r.tokens) for r in reqs)
    assert eng.stats()["retired"] == len(reqs)


def reused_slot_after_a_late_eos(make, prompts, new: int = 12):
    """Two slots: A decodes for long, B ends by an EOS from its own
    continuation while its next window is already launched, and C takes
    B's slot with that window still in flight. Returns C's tokens, C's
    from an engine that served nothing else, and the engine."""
    a, b, c = prompts
    alone = []
    for prompt in (b, c):
        eng = make()
        req = eng.submit(prompt, new)
        _drive(eng, [req])
        eng.close()
        alone.append(req.tokens)
    eos = alone[0][3]
    assert alone[0].index(eos) > 0           # not the prompt's first token
    eng = make()
    ra = eng.submit(a, 4 * new)
    rb = eng.submit(b, new, eos_id=eos)
    rc = eng.submit(c, new)
    seen_in_flight = False
    for _ in range(500):
        if rc.done():
            break
        eng.step()
        _nothing_left_behind(eng)
        if rb.done() and not rc.tokens and eng._flight is not None:
            # B's window is on its way while its slot is C's or free
            seen_in_flight |= any(req is rb for _, req in eng._flight.lanes)
    assert seen_in_flight and not ra.done()
    assert rb.tokens == alone[0][:alone[0].index(eos) + 1]
    assert eng.stats()["dispatch"]["decode"]["discarded_tokens"] > 0
    eng.close()
    return rc.tokens, alone[1], eng


@pytest.mark.parametrize("window", [1, 2], ids=["window1", "window2"])
@pytest.mark.parametrize("model", ["uniform", "layered"])
def test_a_slot_freed_by_a_late_eos_is_clean_for_its_next_tenant(model,
                                                                 window):
    """Full rows and ring rows: the window launched for a lane that had
    ended wrote into its own slot, and the next tenant's chunks overwrite
    before they read."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 64, n).astype(np.int32) for n in (6, 21, 9)]
    got, fresh, _ = reused_slot_after_a_late_eos(
        lambda: MAKE[model](slots=2, prefill_chunk=4, prefill_batch=2,
                            max_len=64, decode_window=window,
                            kv_quant="none"), prompts)
    assert got == fresh


def test_close_drops_the_iteration_in_flight():
    eng = _engine(slots=2, prefill_chunk=4, max_len=64)
    req = eng.submit(np.arange(3, dtype=np.int32), 9)
    eng.step()
    eng.step()
    assert eng._flight is not None and not req.done()
    eng.close()
    assert eng._flight is None and eng._in_flight == []
    assert not eng._ahead.any()
    with pytest.raises(RuntimeError, match="engine shut down"):
        req.result(timeout=1)


def test_drain_waits_for_the_last_iteration():
    eng = _layered_engine(slots=2, prefill_chunk=4, prefill_batch=2,
                          max_len=64, kv_quant="none")
    eng.start()
    try:
        reqs = [eng.submit(np.arange(n, dtype=np.int32), 5) for n in (3, 14)]
        assert eng.drain(timeout=120)
        assert all(len(r.result(timeout=1)["tokens"]) == 5 for r in reqs)
        assert eng._flight is None and eng._in_flight == []
        decode = eng.stats()["dispatch"]["decode"]
        assert 0 < decode["pipelined"] <= decode["calls"]
    finally:
        eng.close()


def test_a_model_is_switched_with_nothing_in_flight(monkeypatch):
    """Every lane ends by EOS with its next window launched: the engine
    reads that window back in the same step, so the swap at the idle
    boundary that follows finds nothing of the old weights on its way."""
    eng = _engine(slots=2, prefill_chunk=4, max_len=64)
    other = init_params(jax.random.key(5), eng.cfg)
    eng.add_model("other", other)
    prompt = np.arange(5, dtype=np.int32)
    plain = eng.submit(prompt, 6)
    _drive(eng, [plain])
    switched = []
    real = eng._switch_model

    def checked(name):
        switched.append((name, eng._flight, list(eng._in_flight)))
        real(name)

    monkeypatch.setattr(eng, "_switch_model", checked)
    first = eng.submit(prompt, 6, eos_id=plain.tokens[2])
    second = eng.submit(prompt, 4, model="other")
    _drive(eng, [first, second])
    assert first.tokens == plain.tokens[:plain.tokens.index(plain.tokens[2])
                                        + 1]
    assert switched == [("other", None, [])]
    assert eng.stats()["dispatch"]["decode"]["discarded_tokens"] == 1
    fresh = ServingEngine(other, eng.cfg, slots=2, prefill_chunk=4,
                          max_len=64, registry=MetricsRegistry())
    want = fresh.submit(prompt, 4)
    _drive(fresh, [want])
    assert second.tokens == want.tokens


def _fail_the_decode_readback(monkeypatch) -> None:
    real = jax.device_get

    def failing(x):
        own, _flights = x
        if getattr(own[0], "ndim", 0) == 2:      # a window's [S, w] tokens
            raise RuntimeError("the device fell over")
        return real(x)

    monkeypatch.setattr(jax, "device_get", failing)


def test_a_failing_iteration_raises_in_the_step_after_its_launch(
        monkeypatch):
    eng = _engine(slots=2, prefill_chunk=4, max_len=64)
    eng.submit(np.arange(3, dtype=np.int32), 9)
    _fail_the_decode_readback(monkeypatch)
    eng.step()                  # the prompt's one chunk; iteration 1 launched
    assert eng.stats()["decode_iterations"] == 1 and eng._flight is not None
    with pytest.raises(RuntimeError, match="the device fell over"):
        eng.step()              # launches iteration 2, reads iteration 1 back
    assert eng.stats()["decode_iterations"] == 2
    eng.close()
    assert eng._flight is None


def test_a_dead_loop_leaves_nothing_in_flight(monkeypatch):
    """The iteration's error reaches every pending request through the
    loop-death path, which drops what was launched behind it."""
    eng = _layered_engine(slots=2, prefill_chunk=4, prefill_batch=2,
                          max_len=64, max_queue=8, kv_quant="none")
    _fail_the_decode_readback(monkeypatch)
    reqs = [eng.submit(np.arange(n, dtype=np.int32), 6) for n in (3, 14, 5)]
    eng.start()
    try:
        for req in reqs:
            with pytest.raises(RuntimeError, match="engine loop failed: "
                                                   "the device fell over"):
                req.result(timeout=60)
        eng._thread.join(timeout=30)
        assert not eng._thread.is_alive()
        assert eng._stop.is_set()
        assert eng._flight is None and eng._in_flight == []
        assert eng.stats()["decode_iterations"] == 2
    finally:
        eng.close()
