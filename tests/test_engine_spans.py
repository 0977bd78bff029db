"""Spans and counters inside ``ServingEngine`` (the names in
``serving/scheduler.py``'s docstring are a contract the benchmark's
readers match on). ``step()`` is driven by hand on a tiny model, so the
counts are exact; the times are only held to their orderings and sums.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from tony_tpu.models import TransformerConfig, init_params
from tony_tpu.observability import trace as obs_trace
from tony_tpu.observability.metrics import MetricsRegistry
from tony_tpu.serving import ServingEngine
from tony_tpu.serving.scheduler import _PHASES, _chunk_plan, _summary

DISPATCH_SPANS = {"tony:engine.prefill_launch", "tony:engine.prefill_readback",
                  "tony:engine.decode_launch", "tony:engine.decode_readback"}
ENGINE_SPANS = {"tony:engine.admit", "tony:engine.prefill_round",
                "tony:engine.prefill_assemble", "tony:engine.prefill_device",
                "tony:engine.decode_device", "tony:engine.emit",
                "tony:engine.publish"} | DISPATCH_SPANS
PROMPT_LENS = (5, 9, 13, 17, 3, 22)
# The fixture's prefill rounds by hand, as (entries, of them at their last
# chunk, fenced). Prompts a..f of PROMPT_LENS have 2, 3, 4, 5, 1 and 6
# chunks of 4; three slots, two entries a round, one chunk a pending slot an
# iteration, six tokens a request (the first from its last chunk, then five
# decode steps: five iterations at window 1, two at window 3). An iteration
# is launched in one step and read back in the next, so a request's slot is
# free for the step after the one that launched its last iteration, and
# both windows make the same rounds: a1 b1 | c1 ; a2* b2 | c2 (a's first
# iteration is launched) ; b3* c3 ; c4* ; steps of decode alone, in which a
# is read to its end ; d1 in a's slot ; d2 e1* ; d3 f1 ; d4 f2 ; d5* f3 ;
# f4 ; f5 ; f6*. A round is fenced for a first token (*), or where it
# closes a step that decodes nothing: c1 (nothing decodes yet). Every other
# step has a lane active, so its decode dispatch fences: at window 3 the
# steps of d4 f2 and f5 launch nothing (e and d have their last iteration in
# flight) and only read it back.
ROUNDS = [(2, 0, False), (1, 0, True), (2, 1, True), (1, 0, False),
          (2, 1, True), (1, 1, True), (1, 0, False), (2, 1, True),
          (2, 0, False), (2, 0, False), (2, 1, True), (1, 0, False),
          (1, 0, False), (1, 1, True)]
# Iterations launched, and of them those launched while the one before was
# not read back: all but the first at window 1; at window 3 the pipeline
# fills three times (a's first, then d's and f's after a step that only
# read back).
DECODE_ITERATIONS = {1: 18, 3: 10}
PIPELINED = {1: 17, 3: 7}


def _engine(**kw) -> ServingEngine:
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, head_dim=16,
        d_ff=64, max_seq=96, dtype="float32", remat=False,
    )
    params = init_params(jax.random.key(0), cfg)
    eng = ServingEngine(params, cfg, registry=MetricsRegistry(), **kw)
    eng._tracer = obs_trace.Tracer(proc="test-engine")
    return eng


def _drive(eng: ServingEngine, reqs, limit: int = 500) -> None:
    for _ in range(limit):
        if all(r.done() for r in reqs):
            return
        eng.step()
    raise AssertionError("requests did not retire")


def _spans(eng: ServingEngine) -> list[dict]:
    return [e for e in eng._tracer.to_chrome_events() if e["ph"] == "X"]


@pytest.fixture(scope="module", params=[1, 3], ids=["window1", "window3"])
def served(request):
    """Six mixed-length requests through three slots (so some queue and
    slots are reused), retired and the engine closed."""
    eng = _engine(slots=3, prefill_chunk=4, prefill_batch=2, max_len=64,
                  decode_window=request.param)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, 64, n).astype(np.int32), 6)
            for n in PROMPT_LENS]
    _drive(eng, reqs)
    before_close = eng.stats()
    eng.close()
    return eng, reqs, before_close


def test_request_stamps_are_ordered(served):
    _, reqs, _ = served
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_first_token <= r.t_done


def test_request_spans_share_the_request_id(served):
    eng, reqs, _ = served
    by_request: dict[str, dict] = {}
    for e in _spans(eng):
        if e["name"].startswith("tony:request."):
            by_request.setdefault(e["args"]["request"], {})[e["name"]] = e
    assert set(by_request) == {r.id for r in reqs}
    for r in reqs:
        mine = by_request[r.id]
        assert set(mine) == {"tony:request.queue", "tony:request.prefill",
                             "tony:request.decode"}
        queue, prefill, decode = (mine[f"tony:request.{k}"]
                                  for k in ("queue", "prefill", "decode"))
        # one life, end to end on one clock (microseconds in the export)
        assert abs(queue["ts"] + queue["dur"] - prefill["ts"]) <= 2
        assert abs(prefill["ts"] + prefill["dur"] - decode["ts"]) <= 2
        assert prefill["args"]["rounds"] == len(
            _chunk_plan(r.prompt.size, eng.prefill_chunk))
        assert decode["args"]["tokens"] == len(r.tokens) == 6
        assert len({e["args"]["span_id"] for e in mine.values()}) == 3


def test_engine_spans_descend_from_their_step_and_lie_inside_it(served):
    eng, _, _ = served
    spans = [e for e in _spans(eng) if e["name"].startswith("tony:engine.")]
    by_id = {e["args"]["span_id"]: e for e in spans}
    steps = [e for e in spans if e["name"] == "tony:engine.step"]
    assert steps and all(e["args"]["parent_id"] is None for e in steps)
    assert ([e["args"]["iteration"] for e in steps]
            == sorted(e["args"]["iteration"] for e in steps))
    children = [e for e in spans if e["name"] != "tony:engine.step"]
    assert {e["name"] for e in children} == ENGINE_SPANS
    for e in children:
        parent = by_id[e["args"]["parent_id"]]
        if e["name"] in ("tony:engine.prefill_assemble",
                         "tony:engine.prefill_device"):
            assert parent["name"] == "tony:engine.prefill_round"
        elif e["name"] in DISPATCH_SPANS:        # a device span's halves
            assert parent["name"] == e["name"].rsplit("_", 1)[0] + "_device"
        elif e["name"] != "tony:engine.emit":    # emit: round's or step's
            assert parent["name"] == "tony:engine.step"
        while parent["name"] != "tony:engine.step":
            parent = by_id[parent["args"]["parent_id"]]
        # microsecond export: a child may round one tick past its parent
        assert parent["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1
    rounds = [e for e in spans if e["name"] == "tony:engine.prefill_round"]
    assert all(1 <= e["args"]["batch"] <= eng.prefill_batch
               and e["args"]["chunk"] == eng.prefill_chunk for e in rounds)
    decodes = [e for e in spans if e["name"] == "tony:engine.decode_device"]
    assert all(0 <= e["args"]["slots"] <= eng.slots
               and e["args"]["window"] == eng.decode_window for e in decodes)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_a_dispatch_is_split_where_the_jitted_call_returns(served, program):
    """Every fenced prefill span has one ``*_launch`` and one ``*_readback``
    child: both lie inside it, the launch ends before the readback starts,
    and the bookkeeping after the readback is the parent's own. A prefill
    round that is not fenced (``fenced=False``) has its launch alone. A
    decode span holds the launch of one iteration and the readback of the
    one before (``pipelined``); the span that fills the pipeline has a
    launch alone, one that launches nothing (``slots`` 0) a readback
    alone."""
    eng, _, _ = served
    spans = [e for e in _spans(eng) if e["name"].startswith("tony:engine.")]
    devices = [e for e in spans
               if e["name"] == f"tony:engine.{program}_device"]
    assert devices
    if program == "prefill":
        assert [e["args"]["fenced"] for e in devices] == \
            [fenced for _, _, fenced in ROUNDS]
    else:
        assert not any("fenced" in e["args"] for e in devices)
    halves = []
    for dev in devices:
        mine = {e["name"].rsplit("_", 1)[1]: e for e in spans
                if e["args"]["parent_id"] == dev["args"]["span_id"]}
        halves.append(sorted(mine))
        if program == "decode":
            assert dev["args"]["pipelined"] == (len(mine) == 2)
            assert ("launch" in mine) == (dev["args"]["slots"] > 0)
        else:
            assert sorted(mine) == (["launch", "readback"]
                                    if dev["args"]["fenced"] else ["launch"])
        launch, readback = mine.get("launch"), mine.get("readback")
        if launch:
            assert launch["name"] == f"tony:engine.{program}_launch"
            assert dev["ts"] <= launch["ts"]
            assert launch["args"]["h2d_arrays"] == (5 if program == "decode"
                                                    else 6)
            assert launch["args"]["h2d_bytes"] > 0
        if readback:
            assert readback["name"] == f"tony:engine.{program}_readback"
            # microsecond export: a stamp may round one tick either way
            assert dev["ts"] <= readback["ts"] + 1
            assert (readback["ts"] + readback["dur"]
                    <= dev["ts"] + dev["dur"] + 1)
            assert readback["args"]["d2h_bytes"] > 0
        if launch and readback:
            assert launch["ts"] + launch["dur"] <= readback["ts"] + 1
    if program == "decode":
        # the pipeline fills with a launch and drains with a readback
        assert halves[0] == ["launch"] and halves[-1] == ["readback"]
        assert sum("launch" in h for h in halves) == \
            sum("readback" in h for h in halves) == \
            DECODE_ITERATIONS[eng.decode_window]
        assert sum(len(h) == 2 for h in halves) == \
            PIPELINED[eng.decode_window]
    firsts = [e["args"].get("first_tokens") for e in spans
              if e["name"] == f"tony:engine.{program}_readback"]
    if program == "prefill":
        assert firsts == [f for _, f, fenced in ROUNDS
                          if fenced]
        assert sum(firsts) == len(PROMPT_LENS)
    else:
        assert firsts == [None] * DECODE_ITERATIONS[eng.decode_window]


def test_dispatch_counters_count_exactly(served):
    """``stats()["dispatch"]``: calls, the bytes a call's host arguments
    take up and its readback brings home, the rounds that held no first
    token and those of them launched without a readback of their own, the
    iterations launched before the one ahead of them was read back, all
    by hand; the two halves' times lie inside their device phase."""
    eng, _, _ = served
    w = eng.decode_window
    st = eng.stats()
    decode, prefill = st["dispatch"]["decode"], st["dispatch"]["prefill"]
    assert decode["calls"] == st["decode_iterations"] == DECODE_ITERATIONS[w]
    assert prefill["calls"] == st["prefill_rounds"] == len(ROUNDS)
    assert [e["args"]["batch"] for e in _spans(eng)
            if e["name"] == "tony:engine.prefill_round"] == \
        [n for n, _, _ in ROUNDS]
    assert prefill["rounds_without_first_token"] == \
        sum(f == 0 for _, f, _ in ROUNDS) == \
        len(ROUNDS) - len(PROMPT_LENS)
    fenced = sum(fenced for _, _, fenced in ROUNDS)
    assert prefill["unfenced"] == len(ROUNDS) - fenced == 7
    assert prefill["unfenced"] <= prefill["rounds_without_first_token"]
    # up: positions, wpos, _last (int32) and _temp (float32) of 3 slots and
    # the draw counter (the last window's tokens are on the device
    # already); 2 rows x 4 tokens, four arrays of 2 and the counter
    assert decode["h2d_bytes"] == decode["calls"] * (4 * 3 * 4 + 4)
    assert prefill["h2d_bytes"] == prefill["calls"] * (2 * 4 * 4 + 4 * 2 * 4
                                                       + 4)
    # back: int32 tokens of 3 slots x the window; a fenced round's 2 rows'
    # first tokens, and nothing of an unfenced round (no experts here)
    assert decode["d2h_bytes"] == decode["calls"] * 3 * w * 4
    assert prefill["d2h_bytes"] == fenced * 2 * 4
    both = {"calls", "launch_ms", "readback_ms", "h2d_bytes", "d2h_bytes"}
    assert set(decode) == both | {"pipelined", "discarded_tokens"}
    assert set(prefill) == both | {"rounds_without_first_token", "unfenced"}
    assert decode["pipelined"] == PIPELINED[w] <= decode["calls"]
    assert decode["discarded_tokens"] == 0       # no request ends by EOS
    for program, row in st["dispatch"].items():
        assert row["launch_ms"] > 0 < row["readback_ms"]
        assert (row["launch_ms"] + row["readback_ms"]
                <= st["phase_ms"][f"{program}_device"])


def test_phases_are_inside_the_working_wall(served):
    eng, _, _ = served
    st = eng.stats()
    assert set(st["phase_ms"]) == set(_PHASES)
    assert all(v > 0 for v in st["phase_ms"].values())
    assert sum(st["phase_ms"].values()) <= st["working_wall_ms"]
    steps = [e for e in _spans(eng) if e["name"] == "tony:engine.step"]
    assert st["working_iterations"] == len(steps) <= st["iterations"]


def test_phases_sum_to_the_working_wall():
    """Within 5%: what is left is the host's own (admit, assemble, emit,
    publish and between spans), a hundred microseconds an iteration, so
    the model here is one whose iteration takes milliseconds on a CPU
    even with its programs compiled."""
    cfg = TransformerConfig(
        vocab_size=512, d_model=256, n_layers=4, n_heads=4, head_dim=64,
        d_ff=1024, max_seq=256, dtype="float32", remat=False,
    )
    eng = ServingEngine(init_params(jax.random.key(0), cfg), cfg, slots=8,
                        max_len=256, registry=MetricsRegistry())
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(0, 512, n).astype(np.int32), 8)
            for n in (40, 90, 130, 17)]
    _drive(eng, reqs)
    st = eng.stats()
    total = sum(st["phase_ms"].values())
    assert 0.95 * st["working_wall_ms"] <= total <= st["working_wall_ms"]
    host = st["working_wall_ms"] - (st["phase_ms"]["prefill_device"]
                                    + st["phase_ms"]["decode_device"])
    assert 0 < host < st["working_wall_ms"]


def test_counters_count_what_the_spans_show(served):
    eng, reqs, _ = served
    st = eng.stats()
    spans = _spans(eng)
    decodes = [e for e in spans if e["name"] == "tony:engine.decode_device"]
    rounds = [e for e in spans if e["name"] == "tony:engine.prefill_round"]
    # a span that launches nothing reads the last iteration back
    assert st["decode_iterations"] == sum(e["args"]["slots"] > 0
                                          for e in decodes)
    assert st["decode_slots_sum"] == sum(e["args"]["slots"] for e in decodes)
    assert st["prefill_rounds"] == len(rounds)
    rows = sum(e["args"]["batch"] for e in rounds)
    assert rows == sum(len(_chunk_plan(n, 4)) for n in PROMPT_LENS)
    assert st["prefill_rows_padded"] == 2 * len(rounds) - rows
    assert st["prefill_tokens_valid"] == sum(
        n for p in PROMPT_LENS for _, n in _chunk_plan(p, 4))
    assert st["queue_wait_ms"]["n"] == st["prefill_span_ms"]["n"] == len(reqs)
    waits = sorted((r.t_admit - r.t_submit) * 1000.0 for r in reqs)
    assert st["queue_wait_ms"]["max"] == pytest.approx(waits[-1])
    assert st["queue_wait_ms"]["p50"] == pytest.approx(waits[2])
    # three slots, six requests: the later ones waited for a slot
    assert waits[-1] > waits[0]


def test_live_kv_never_exceeds_what_is_reserved(served):
    eng, _, _ = served
    kv = eng.stats()["kv"]
    assert kv["reserved_positions"] == 3 * 64
    # float32 K and V rows of 2 layers x 2 heads x 16
    assert kv["bytes_per_position"] == 2 * 2 * 2 * 16 * 4
    mean_live = kv["live_position_ms"] / eng.stats()["working_wall_ms"]
    assert 0 < mean_live <= kv["reserved_positions"]
    # no more than every prompt and every output token, all at once
    assert mean_live <= sum(PROMPT_LENS) + 6 * len(PROMPT_LENS)


def test_close_leaves_the_counters_as_they_were(served):
    eng, _, before_close = served
    after = eng.stats()
    for key in ("working_iterations", "working_wall_ms", "phase_ms",
                "dispatch", "decode_iterations", "decode_slots_sum", "prefill_rounds",
                "prefill_tokens_valid", "prefill_rows_padded",
                "prefill_keys", "decode_keys", "kv",
                "queue_wait_ms", "prefill_span_ms", "retired"):
        assert after[key] == before_close[key], key


def _layered_engine(**kw) -> ServingEngine:
    """Two attention kinds (layer 0 full and dense, then window layers
    with experts of which a share is held): the model whose dispatches
    carry expert counts and whose cache has two kinds of rows."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=3, n_heads=4, head_dim=12,
        v_head_dim=8, rotary_dim=4, n_kv_heads=1, max_seq=96,
        dtype="float32", remat=False,
        attn_kinds=("full", "window", "window"), window=8,
        window_kv_heads=2, window_rope_theta=1e4, window_sink=True,
        n_dense_layers=1, dense_d_ff=48, d_ff=16, n_experts=8,
        expert_top_k=3, router_scoring="sigmoid", router_bias=True,
        experts_held=(2, 4),
    )
    params = init_params(jax.random.key(0), cfg)
    eng = ServingEngine(params, cfg, registry=MetricsRegistry(), **kw)
    eng._tracer = obs_trace.Tracer(proc="test-engine")
    return eng


@pytest.fixture(scope="module")
def served_layered():
    eng = _layered_engine(slots=2, prefill_chunk=4, prefill_batch=2,
                          max_len=64, kv_quant="none")
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(0, 64, n).astype(np.int32), 6)
            for n in (5, 13, 22)]
    _drive(eng, reqs)
    eng.close()
    return eng


def test_device_spans_carry_the_pairs_on_held_experts(served_layered):
    """``expert_pairs`` on a ``decode_device`` / ``prefill_device`` span
    that reads back is the (token, choice) pairs on the held experts of
    what it brought home: a fenced round's own, the iteration before the
    one a decode span launched, and the unfenced rounds between; over all
    dispatches they are ``stats()["experts"]``."""
    eng = served_layered
    ex = eng.stats()["experts"]
    spans = _spans(eng)
    device = [e for e in spans if e["name"] in
              ("tony:engine.decode_device", "tony:engine.prefill_device")]
    # a round that is not fenced and an iteration just launched have no
    # counts yet: the span that reads them back carries them
    parents = {e["args"]["parent_id"] for e in spans
               if e["name"].endswith("_readback")}
    fenced = [e for e in device if e["args"]["span_id"] in parents]
    assert len(fenced) < len(device)
    assert all(("expert_pairs" in e["args"]) == (e in fenced) for e in device)
    assert sum(e["args"]["expert_pairs"] for e in fenced) == \
        ex["pairs_held"] == sum(ex["pairs_per_expert"])
    assert ex["held"] == [2, 4] and len(ex["pairs_per_expert"]) == 4
    # every dispatch launches but the one that reads the last iteration
    assert ex["dispatches"] == sum(e["args"].get("slots", 1) > 0
                                   for e in device)
    # tokens through the layers: every chunk's valid ones (a prompt's
    # last chunk overlaps) and every served token but a request's last;
    # 2 expert layers, 3 choices a token
    tokens = sum(sum(n for _, n in _chunk_plan(p, 4)) + 6 - 1
                 for p in (5, 13, 22))
    assert ex["pairs_total"] == tokens * 2 * 3
    assert 0 < ex["pairs_held"] < ex["pairs_total"]


def test_stats_count_the_passes_of_the_expert_layers(served_layered):
    """``stats()["experts"]["passes"]``: the passes the expert layers
    ran, which come back in each dispatch's one readback beside the
    pairs. A toy's pair rows are few beside its weights, so each of the
    2 expert layers runs one pass a dispatch (decode window 1): the
    ratio a served model reads 1.0 at while no layer streams its
    weights twice."""
    ex = served_layered.stats()["experts"]
    assert ex["dispatches"] > 0
    assert ex["passes"] == ex["dispatches"] * 2


def test_kv_counters_by_cache_kind(served_layered):
    eng = served_layered
    st = eng.stats()
    kv, kinds = st["kv"], st["kv"]["kinds"]
    assert set(kinds) == {"full", "window"}
    # the top-level keys keep their meaning: the full kind's
    for key in ("reserved_positions", "bytes_per_position",
                "live_position_ms"):
        assert kv[key] == kinds["full"][key], key
    assert kinds["full"]["reserved_positions"] == 2 * 64
    # K 12 + V 8 wide, one full layer of 1 head, float32
    assert kinds["full"]["bytes_per_position"] == (12 + 8) * 4
    # a ring of 4 * ceil((8 + 4) / 4) = 12 positions and the parking row;
    # two window layers of 2 heads
    assert kinds["window"]["reserved_positions"] == 2 * 13
    assert kinds["window"]["bytes_per_position"] == 2 * 2 * (12 + 8) * 4
    for row in kinds.values():
        assert row["bytes_reserved"] == (row["reserved_positions"]
                                         * row["bytes_per_position"])
    # a window layer keeps at most its window of each slot
    assert 0 < kinds["window"]["live_position_ms"] <= \
        2 * 8 * st["working_wall_ms"]
    assert kinds["window"]["live_position_ms"] < \
        kinds["full"]["live_position_ms"]


@pytest.mark.parametrize("layered,limit,read", [
    # one full layer of 1 KV head, key blocks of gcd(96, 2048) = 32
    # positions; chunk ends 8 | 8, 13 | 8, 16, 24, 32, 40, 45 read
    # 32 | 32, 32 | 32, 32, 32, 32, 64, 64
    (True, 2 ** 13, 32 + 64 + (4 * 32 + 2 * 64)),
    # the same model with the limit as shipped, and a dense model of two
    # layers: every row reads its slot's whole reservation
    (True, None, 9 * 96),
    (False, None, 9 * 96 * 2),
], ids=["scores-over-the-limit", "layered", "dense"])
def test_prefill_keys_count_what_a_chunk_is_given_to_read(monkeypatch,
                                                          layered, limit,
                                                          read):
    """Prompts of 5, 13 and 45 tokens at a chunk of 8 and 96 positions a
    slot: 9 rows. With the limit on a round's float32 scores lowered to
    8 KiB the full layer's 24 KiB (2 rows x 8 queries x 4 heads x 96
    keys) attend through ``cache_prefill_attention`` and a row reads
    whole key blocks up to its chunk's end; the rings' 4 KiB do not."""
    from tony_tpu.serving import engine as engine_lib

    if limit:
        monkeypatch.setattr(engine_lib, "SCORES_LIMIT", limit)
    engine_lib.prefill_chunks.clear_cache()
    try:
        make = _layered_engine if layered else _engine
        eng = make(slots=2, prefill_chunk=8, prefill_batch=2, max_len=96,
                   kv_quant="none")
        reqs = [eng.submit(np.arange(n, dtype=np.int32) % 64, 6)
                for n in (5, 13, 45)]
        _drive(eng, reqs)
        eng.close()
    finally:
        engine_lib.prefill_chunks.clear_cache()
    keys = eng.stats()["prefill_keys"]
    assert keys["read_positions"] == read
    assert keys["reserved_positions"] == 9 * 96 * (1 if layered else 2)
    device = [e for e in _spans(eng)
              if e["name"] == "tony:engine.prefill_device"]
    assert sum(e["args"]["keys_read"] for e in device) == read


@pytest.mark.parametrize("case,read,reserved", [
    # a prompt of 15 tokens, 3 new ones: ONE window of 2 steps feeds
    # positions 15 (block 0) and 16 (blocks 0 and 1); 2 layers. The other
    # two lanes are parked at stale positions of their last tenants (40
    # and 63: three and four blocks), and read nothing
    ("stale-parked", (16 + 32) * 2, 3 * 64 * 2 * 2),
    # the same with the other lanes at 0
    ("block-edge", (16 + 32) * 2, 3 * 64 * 2 * 2),
    # one full layer of 1 KV head beside the rings: a prompt of 30
    # tokens, 4 new ones, three iterations feed 30, 31 (one block of 32)
    # and 32 (two); the other lane stays parked
    ("ring", 32 + 32 + 64, 3 * 2 * 96),
])
def test_decode_keys_count_the_blocks_a_step_reads(monkeypatch, case, read,
                                                   reserved):
    """``stats()["decode_keys"]`` by the kernel's own rule of key blocks
    (``decode_last_block``), per full layer, parked lanes at nothing;
    the decode span carries each dispatch's share."""
    from tony_tpu.ops import attention
    from tony_tpu.serving import engine as engine_lib

    engine_lib.decode_window.clear_cache()
    try:
        if case == "ring":
            monkeypatch.setattr(attention, "DECODE_BLOCK_ROWS", 32)
            eng = _layered_engine(slots=2, prefill_chunk=8, prefill_batch=2,
                                  max_len=96, kv_quant="none")
            assert eng._dc_read_block == 32
            reqs = [eng.submit(np.arange(30, dtype=np.int32) % 64, 4)]
        else:
            monkeypatch.setattr(attention, "DECODE_BLOCK_ROWS", 32)
            eng = _engine(slots=3, prefill_chunk=4, prefill_batch=2,
                          max_len=64, decode_window=2)
            assert eng._dc_read_block == 16       # 2 KV heads
            if case == "stale-parked":
                eng._pos[:] = (5, 40, 63)
            reqs = [eng.submit(np.arange(15, dtype=np.int32), 3)]
        _drive(eng, reqs)
        eng.close()
    finally:
        engine_lib.decode_window.clear_cache()
    assert eng.stats()["decode_keys"] == {"read_positions": read,
                                          "reserved_positions": reserved}
    device = [e for e in _spans(eng)
              if e["name"] == "tony:engine.decode_device"]
    assert device and sum(e["args"]["keys_read"] for e in device) == read


def test_a_uniform_model_has_one_cache_kind_and_no_expert_block(served):
    eng, _, _ = served
    st = eng.stats()
    assert "experts" not in st
    assert list(st["kv"]["kinds"]) == ["full"]
    assert st["kv"]["kinds"]["full"]["reserved_positions"] == \
        st["kv"]["reserved_positions"]
    device = [e for e in _spans(eng) if e["name"] in
              ("tony:engine.decode_device", "tony:engine.prefill_device")]
    assert not any("expert_pairs" in e["args"] for e in device)


@pytest.mark.parametrize("prompt_len,chunk", [(3, 8), (16, 8), (20, 8),
                                              (33, 4)])
def test_prefill_rounds_of_one_prompt_are_its_chunk_plan(prompt_len, chunk):
    eng = _engine(slots=2, prefill_chunk=chunk, max_len=64)
    req = eng.submit(np.arange(prompt_len, dtype=np.int32) % 64, 2)
    _drive(eng, [req])
    st = eng.stats()
    assert st["prefill_rounds"] == len(_chunk_plan(prompt_len, chunk))
    assert st["prefill_rows_padded"] == (
        st["prefill_rounds"] * (eng.prefill_batch - 1))
    assert st["prefill_span_ms"]["n"] == 1


def test_idle_polls_record_and_count_nothing():
    eng = _engine(slots=2, max_len=32)
    assert eng.step() is False and eng.step() is False
    st = eng.stats()
    assert st["iterations"] == 2 and st["working_iterations"] == 0
    assert st["working_wall_ms"] == 0 and not any(st["phase_ms"].values())
    assert st["queue_wait_ms"] == {"n": 0, "mean": None, "p50": None,
                                   "p90": None, "max": None}
    assert len(eng._tracer) == 0


def test_disaggregated_halves_have_only_the_spans_they_lived():
    """A prefill-only request never decodes and one injected with shipped
    KV never prefills: no such span, and no made-up prefill time."""
    pre = _engine(slots=2, prefill_chunk=4, max_len=32)
    prompt = np.arange(9, dtype=np.int32)
    half = pre.prefill_only(prompt, 4)
    _drive(pre, [half])
    names = {e["name"] for e in _spans(pre)
             if e["name"].startswith("tony:request.")}
    assert names == {"tony:request.queue", "tony:request.prefill"}
    assert pre.stats()["prefill_span_ms"]["n"] == 1

    dec = _engine(slots=2, prefill_chunk=4, max_len=32)
    kv_k, kv_v = half.kv
    rest = dec.submit_with_kv(kv_k, kv_v, half.tokens[0], prompt.size, 3)
    _drive(dec, [rest])
    names = {e["name"] for e in _spans(dec)
             if e["name"].startswith("tony:request.")}
    assert names == {"tony:request.queue", "tony:request.decode"}
    st = dec.stats()
    assert st["queue_wait_ms"]["n"] == 1 and st["prefill_span_ms"]["n"] == 0
    assert st["prefill_rounds"] == 0 and st["phase_ms"]["prefill_device"] == 0
    assert rest.t_submit <= rest.t_admit <= rest.t_done


def test_latency_summary_by_hand():
    s = _summary([float(v) for v in range(1, 11)])      # 1..10
    assert s == {"n": 10, "mean": 5.5, "p50": 5.0, "p90": 9.0, "max": 10.0}
    assert _summary([7.0]) == {"n": 1, "mean": 7.0, "p50": 7.0, "p90": 7.0,
                               "max": 7.0}
    s = _summary([float(v) for v in range(100, 0, -1)])  # 100..1, unsorted
    assert (s["p50"], s["p90"], s["max"]) == (50.0, 90.0, 100.0)
    assert _summary([1.0, 2.0, 3.0])["p90"] == 3.0
