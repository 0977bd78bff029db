"""The yardstick's own arithmetic, checked without a chip.
Run: JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q"""

from __future__ import annotations

import json
import math
from pathlib import Path

import pytest

from yardstick import compare, counts, spec, stats, traffic, xplane

DATA = Path(__file__).resolve().parent / "data"
dense = spec.load_model("dense")
toymoe = spec.load_module(DATA / "moe" / "models" / "toymoe.py", "toymoe")
MISTRAL = dict(hidden_size=4096, intermediate_size=14336,
               num_attention_heads=32, num_key_value_heads=8, head_dim=128,
               vocab_size=32000, num_hidden_layers=2)


# -- trace reduction ---------------------------------------------------------
def synthetic():
    return xplane.reduce(json.loads((DATA / "synthetic_trace.json").read_text()))


def test_idle_share_is_one_minus_the_union_of_op_intervals():
    r = synthetic()
    assert r["window_s"] == pytest.approx(0.010)
    # device 0: busy [0,5) [6,7) [8,9) = 7 ms; device 1: busy all 10 ms
    assert r["devices"]["0"]["busy_s"] == pytest.approx(0.007)
    assert r["devices"]["1"]["idle_s"] == pytest.approx(0.0)
    assert r["busy_s"] == pytest.approx(0.0085)


def test_time_by_program_and_by_operation():
    r = synthetic()
    step = xplane.program(r, "jit_step")
    assert step["calls"] == 3 and step["total_s"] == pytest.approx(0.018)
    assert step["median_s"] == pytest.approx(0.005)
    assert xplane.program(r, "absent") is None
    assert dict(r["device_ops"])["flash_fwd"] == pytest.approx(0.001)
    assert r["device_op_calls"]["flash_fwd"] == pytest.approx(1.0)
    # 2 + 4 ms over 2 devices, ranked before the 1 ms of all-reduce x 2
    assert dict(r["device_ops"])["fusion:b"] == pytest.approx(0.003)
    assert [n for n, _ in r["device_ops"]][-1] == "flash_fwd"


def test_idle_gaps_go_to_the_innermost_host_span_open_at_the_time():
    gaps = dict(synthetic()["idle_gaps"])
    # device 0 idles [5,6) [7,8) [9,10): outer covers [4,9), inner [7,8)
    assert gaps["bench:outer"] == pytest.approx(0.0005)
    assert gaps["bench:inner"] == pytest.approx(0.0005)
    assert gaps[xplane.NO_SPAN] == pytest.approx(0.0005)


def test_idle_gaps_are_named_after_the_programs_own_spans_too():
    """A ``tony:`` span inside a ``bench:`` one is the innermost and takes
    the gap; a span of neither prefix is not extracted at all."""
    assert xplane.HOST_SPAN_PREFIX == ("bench:", "tony:")
    assert "tony:engine.step".startswith(xplane.HOST_SPAN_PREFIX)
    assert not "other:span".startswith(xplane.HOST_SPAN_PREFIX)
    trace = json.loads((DATA / "synthetic_trace.json").read_text())
    trace["host_spans"].append(["tony:engine.decode_device", 5200000, 500000])
    gaps = dict(xplane.reduce(trace)["idle_gaps"])
    # device 0 idles [5,6): 0.5 ms of it inside the program's span
    assert gaps["tony:engine.decode_device"] == pytest.approx(0.00025)
    assert gaps["bench:outer"] == pytest.approx(0.00025)
    assert gaps["bench:inner"] == pytest.approx(0.0005)


def test_the_sweep_over_idle_gaps_agrees_with_asking_every_span():
    """``_gaps_by_span`` hands ``_innermost`` only the spans that can cover
    a gap; over many gaps under nested spans it must say what asking all
    of them says."""
    import random

    lo, hi = 0.0, 1e9
    rng = random.Random(3)
    spans = []
    for i in range(300):                  # outer spans, each with an inner
        s = rng.uniform(lo, hi)
        d = rng.uniform(0, (hi - lo) / 100)
        spans += [(f"bench:o{i % 7}", s, s + d),
                  (f"tony:i{i % 5}", s + d / 4, s + d / 2)]
    spans.sort(key=lambda sp: sp[1])
    busy = xplane.union([[s, s + rng.uniform(0, 2e6)] for s in
                         (rng.uniform(lo, hi) for _ in range(1000))])
    idle = xplane.subtract([[lo, hi]], busy)
    assert len(idle) > 300

    def totals(pairs):
        out = {}
        for name, dur in pairs:
            out[name] = out.get(name, 0.0) + dur
        return out

    slow = totals(p for s, e in idle for p in xplane._innermost(spans, s, e))
    assert totals(xplane._gaps_by_span(spans, idle)) == pytest.approx(slow)
    assert len(slow) == 13


def test_exposed_collective_time_is_what_no_compute_overlaps():
    r = synthetic()
    # device 0: all-reduce [2,4), compute covers [3,5): 1 ms exposed
    assert r["devices"]["0"]["collective_exposed_s"] == pytest.approx(0.001)
    assert r["devices"]["1"]["collective_exposed_s"] == pytest.approx(0.002)


@pytest.mark.parametrize("name,programs,top", [
    ("recorded_serve_trace.json", ("prefill_chunks",),
     "dynamic-slice_bitcast_fusion:bf16[32,2048,8,128]"),
    ("recorded_train_trace.json", ("step",),
     None),
])
def test_recorded_chip_trace_agrees_with_a_count_on_a_grid(name, programs, top):
    """Cuts of real TPU v5e traces (PR 24's runs on the chip: a steady
    serving window, a training step). Busy time is counted again here in
    another way, on a grid of 1 microsecond, and must agree."""
    import numpy as np

    trace = json.loads((DATA / name).read_text())
    r = xplane.reduce(trace)
    lo, hi = xplane.window_of(trace)
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _, s, d in trace["devices"]["0"]["ops"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[int((a - lo) // 1000): int(-(-(b - lo) // 1000))] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=0.02)
    assert 0.5 * r["window_s"] < r["busy_s"] < r["window_s"]
    assert sum(t for _, t in r["device_ops"]) == pytest.approx(
        r["busy_s"], rel=0.02), "self times add up to the busy time"
    for needle in programs:
        assert xplane.program(r, needle)["calls"] >= 1
    if top:
        # the two whole-slab cache copies lead
        assert top in [n for n, _ in r["device_ops"][:2]]
        assert dict(r["idle_gaps"])["bench:engine-step"] > 0
    else:
        assert any(n.startswith("mosaic:(bf16[128,2048,128],f32[")
                   for n, _ in r["device_ops"]), "flash forward is there"


# -- percentiles and rates ---------------------------------------------------
def req(i, due, answered, ttft_ms, wall_ms, length, status=200):
    r = stats.Request(i, due, 10, length)
    r.sent, r.answered, r.status = due, answered, status
    r.length, r.ttft_ms, r.wall_ms = length, ttft_ms, wall_ms
    return r


def steady(n=20, stall=0.0):
    out = []
    for i in range(n):
        due = 10.0 + i * 0.5
        wait = stall if i >= 10 else 0.0        # a stall delays later ones
        out.append(req(i, due, due + wait + 1.0, 100.0, 1000.0, 10))
    return out


def test_percentile_is_over_all_requests_and_nearest_rank():
    assert stats.percentile(range(1, 101), 90) == 90
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.percentile([1, 2, math.inf], 90) == math.inf


def test_a_stall_lowers_the_rate_and_raises_the_tail():
    calm = stats.serving_metrics(steady(), 10.0, 20.0)
    stalled = stats.serving_metrics(steady(stall=3.0), 10.0, 20.0)
    assert calm["serve_ttft_p90_ms"] == pytest.approx(100.0)
    # timed from when it was DUE: the stall is charged to the request
    assert stalled["serve_ttft_p90_ms"] == pytest.approx(3100.0)
    assert stalled["tokens_answered_per_s"] < calm["tokens_answered_per_s"]
    assert calm["serve_tpot_p90_ms"] == pytest.approx(100.0)


def test_a_failed_request_misses_every_limit():
    rs = steady(10)
    rs[3].status = 503             # two of ten reach into the p90: one
    rs[4].length -= 1              # refused, one ended a token early
    assert not rs[4].ok and rs[4].tpot_ms() is None
    m = stats.serving_metrics(rs, 10.0, 15.0)
    assert m["serve_ttft_p90_ms"] == math.inf
    # eight answers land inside the window; the two failed ones count nothing
    assert m["tokens_answered_per_s"] == pytest.approx(6 * 10 / 5.0)
    rs = steady(20)
    rs[3].status = 503             # one of twenty is outside the p90
    m = stats.serving_metrics(rs, 10.0, 20.0)
    assert math.isfinite(m["serve_ttft_p90_ms"])
    # answers arrive a second after they were due: 18 inside, one failed
    assert m["tokens_answered_per_s"] == pytest.approx(17 * 10 / 10.0)


def test_answered_tokens_count_where_their_answer_arrives():
    rs = [req(0, 5.0, 12.0, 50.0, 500.0, 7),     # due in the pre-roll
          req(1, 19.5, 21.0, 50.0, 500.0, 9)]    # answered after the window
    m = stats.serving_metrics(rs, 10.0, 20.0)
    assert m["tokens_answered_per_s"] == pytest.approx(0.7)
    assert m["due_in_window"] == 1
    assert "serve_tokens_per_s" not in m     # that is the counter's


# the engine's counter as the job samples it: [time, slots, queue,
# prefilling, tokens so far]; 40 tokens a second from t = 101
COUNTER = [[100.0 + 0.5 * i, 3, 0, 0, max(0, 20 * i - 40)] for i in range(13)]


@pytest.mark.parametrize("t,want", [
    (101.0, 0.0),            # on a sample
    (103.0, 80.0),
    (102.25, 50.0),          # between two samples: linear
    (100.3, 0.0),            # between two samples that read the same
    (100.0, 0.0),            # on the first sample
    (106.0, 200.0),          # on the last
], ids=["on-sample", "on-sample-later", "between", "flat", "first", "last"])
def test_the_counter_is_read_between_its_two_samples(t, want):
    assert stats.counter_at(COUNTER, t) == pytest.approx(want)


@pytest.mark.parametrize("samples,t", [
    (COUNTER, 99.999), (COUNTER, 106.001), ([], 101.0),
], ids=["before-the-first", "after-the-last", "no-samples"])
def test_the_counter_is_never_extrapolated(samples, t):
    with pytest.raises(ValueError, match="no samples of the counter"):
        stats.counter_at(samples, t)
    with pytest.raises(ValueError):
        stats.generated_rate(samples, min(t, 101.0), max(t, 102.0))


def test_the_rate_is_the_counters_difference_over_the_window():
    # edges between samples, on samples, and a window that starts before
    # the first token: what was made inside, over all of its length
    assert stats.generated_rate(COUNTER, 102.25, 104.75) == pytest.approx(40.0)
    assert stats.generated_rate(COUNTER, 101.0, 106.0) == pytest.approx(40.0)
    assert stats.generated_rate(COUNTER, 100.0, 102.0) == pytest.approx(20.0)


@pytest.mark.parametrize("generated,answered,allowance,want", [
    (500, 500, 0, 0),        # drained: every token reached a client
    (501, 500, 0, 1),        # a token counted that no client received
    (499, 500, 0, 1),        # a token received that was not counted
    (560, 500, 64, 0),       # cut: inside what the cut requests may hold
    (565, 500, 64, 1),
    (499, 500, 64, 1),
])
def test_the_counter_is_held_to_the_tokens_clients_received(
        generated, answered, allowance, want):
    assert stats.tokens_unaccounted(generated, answered, allowance) == want


def test_train_rate_counts_steps_finished_inside_over_the_whole_window():
    calm, stalled = [0.5, 1.0, 1.5, 2.0], [0.5, 1.0, 1.5, 2.5]
    assert stats.train_rate(calm, 100, 2.0, 1) == pytest.approx(200.0)
    # a stall before the last step: the same work over more time
    assert stats.train_rate(stalled, 100, 2.5, 1) == pytest.approx(160.0)
    assert stats.train_rate(stalled, 100, 2.0, 1) == pytest.approx(150.0)
    assert stats.train_rate(calm, 100, 2.0, 4) == pytest.approx(50.0)


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.spread([10, 10, 10, 10, 10, 10]) == 0
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)


# -- operations and bytes ----------------------------------------------------
def test_counts_agree_with_sums_made_by_hand():
    layer = (4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336)
    assert dense.layer_matmul_params(MISTRAL) == layer == 218_103_808
    assert dense.params_total(MISTRAL) == (
        2 * (layer + 2 * 4096) + 2 * 32000 * 4096 + 4096)
    assert dense.matmul_params_per_token(MISTRAL) == 2 * layer + 4096 * 32000
    # head yes, lookup no; causal attention 6·L·S·H·Dh a token
    want = 6 * (2 * layer + 4096 * 32000) + 6 * 2 * 2048 * 32 * 128
    assert dense.train_flops_per_token(MISTRAL, 2048) == want
    assert want == pytest.approx(3.504e9, rel=1e-3)


def test_flash_and_decode_bytes():
    fwd = counts.flash_call_cost("fwd", 4, 2048, 32, 8, 128)
    assert fwd == dense.attention_call_cost(MISTRAL, "fwd", 4, 2048)
    assert dense.attention_call_cost(MISTRAL, "dkv", 2, 2048, tp=2) == \
        counts.flash_call_cost("dkv", 2, 2048, 16, 4, 128)
    assert fwd["flops"] == 2.0 * 4 * 32 * 2048 * 2048 * 128
    q = 4 * 2048 * 32 * 128 * 2
    assert fwd["bytes"] == 2 * q + 2 * (q // 4) + 4 * 32 * 2048 * 4
    bwd = (counts.flash_call_cost("dq", 4, 2048, 32, 8, 128)["flops"]
           + counts.flash_call_cost("dkv", 4, 2048, 32, 8, 128)["flops"])
    assert bwd == 2.5 * fwd["flops"]
    cfg = dict(MISTRAL, num_hidden_layers=16)
    w = dense.weight_bytes(cfg)
    assert w == (16 * (218_103_808 + 8192) + 4096 * 32000 + 4096) * 2
    need = dense.decode_iter_bytes(cfg, live_positions=10_000,
                                    active_slots=20)
    row = 8 * 128 * 2
    assert need == w + 2 * 16 * 10_000 * row + 2 * 16 * 20 * row + 20 * 8192
    t, bound = counts.roofline_seconds(0.0, need, {"flops_bf16": 197e12,
                                                   "hbm_bytes_per_s": 819e9})
    assert bound == "memory" and t == pytest.approx(need / 819e9)


def test_an_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v9 imaginary")


# -- traffic -----------------------------------------------------------------
def chat():
    return json.loads((spec.ROOT / "traffic" / "chat-steady.json").read_text())


def test_traffic_is_a_pure_function_of_the_seed():
    a = traffic.serving_schedule(chat(), 2**31 + 5, 20, 32000)
    b = traffic.serving_schedule(chat(), 2**31 + 5, 20, 32000)
    c = traffic.serving_schedule(chat(), 7, 20, 32000)
    assert a == b and a != c
    x = traffic.training_records({"records": 8, "seq": 16}, 3, 512)
    y = traffic.training_records({"records": 8, "seq": 16}, 3, 512)
    assert (x == y).all() and len({r.tobytes() for r in x}) == 8


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    turning = dict(chat(), seed_turns_order=True)   # the seed turns the circle
    a = traffic.serving_schedule(turning, 1, 30, 32000)["requests"]
    c = traffic.serving_schedule(turning, 2, 30, 32000)["requests"]
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new_tokens"]):
        assert sorted(map(key, a)) == sorted(map(key, c))
    assert [key(r) for r in a] != [key(r) for r in c]
    assert len(a) == len(c)
    fixed = dict(chat(), seed_turns_order=False)   # the seed draws ids only
    f1 = traffic.serving_schedule(fixed, 1, 30, 32000)["requests"]
    f2 = traffic.serving_schedule(fixed, 2, 30, 32000)["requests"]
    assert [(r["due"], len(r["prompt"])) for r in f1] == [
        (r["due"], len(r["prompt"])) for r in f2]
    assert [r["prompt"] for r in f1] != [r["prompt"] for r in f2]
    t = chat()
    assert all(t["prompt_len"]["min"] <= len(r["prompt"])
               <= t["prompt_len"]["max"] for r in a)
    assert all(0 <= r["due"] < t["preroll_s"] + 30 for r in a)
    assert traffic.warmup_requests(t, 32000) == traffic.warmup_requests(t, 32000)


# -- the comparison ----------------------------------------------------------
def test_worst_leaf_gap_is_against_the_larger_of_leaf_and_median():
    ref = {"a": 10.0, "b": 1.0, "c": 1e-6}
    gap, leaf = compare.worst_leaf_gap({"a": 10.5, "b": 1.0, "c": 2e-6}, ref)
    assert leaf == "a" and gap == pytest.approx(0.05)
    gap, leaf = compare.worst_leaf_gap({"a": 10.0, "b": 1.0}, ref)
    assert gap == math.inf and "c" in leaf


def test_judge_needs_every_number_and_keeps_each_limit():
    ok, table = compare.judge({"x": 0.1, "y": 0}, {"x": 0.2, "y": 0})
    assert ok and table["x"] == {"value": 0.1, "limit": 0.2, "ok": True}
    assert not compare.judge({"x": 0.3, "y": 0}, {"x": 0.2, "y": 0})[0]
    assert not compare.judge({"y": 0}, {"x": 0.2, "y": 0})[0]
    assert not compare.judge({"x": float("nan")}, {"x": 0.2})[0]


# -- seeded weights and the model modules -------------------------------------
def digest(array) -> str:
    import hashlib

    import jax.numpy as jnp
    import numpy as np

    return hashlib.sha256(
        np.asarray(array.astype(jnp.float32)).tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("seed", [1, 2**31 + 11])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_every_weight_is_bit_for_bit_what_the_parent_made(seed, dtype):
    """The digests were recorded from the parent's ``weights.leaf`` (one
    closed table, ids by place) before the table moved to ``models/dense``
    and ids became a function of the name."""
    import jax.numpy as jnp

    from yardstick import weights

    recorded = json.loads((DATA / "parent_weight_digests.json").read_text())
    cfg, want = recorded["config"], recorded["digests"][f"{seed}:{dtype}"]
    table, key = dense.leaf_table(cfg), weights.seed_key(seed)
    stacked = weights.stacked_layers(key, table, jnp.dtype(dtype))
    got = {**stacked, **weights.top_tree(key, table, jnp.dtype(dtype))}
    assert {n: digest(a) for n, a in got.items()} == want
    assert len(want) == 12
    # a layer made alone is that layer of the stack, and the program's tree
    # holds the same arrays under the program's names
    for layer in range(cfg["num_hidden_layers"]):
        alone = weights.layer_tree(key, table, layer, jnp.dtype(dtype))
        assert set(alone) == set(stacked)
        for name, array in alone.items():
            assert digest(array) == digest(stacked[name][layer])
    tree = dense.program_params(key, cfg, jnp.dtype(dtype))
    assert digest(tree["layers"]["wq"]) == want["q_proj"]
    assert digest(tree["unembed"]) == want["lm_head"]
    assert set(dense.leaf_norms(tree)) == set(want)


def test_a_leafs_id_follows_from_its_name_alone():
    from yardstick import weights

    pinned = json.loads(weights.PINNED_IDS.read_text())
    assert sorted(pinned.values()) == list(range(12))
    assert [weights.leaf_id(n) for n in pinned] == list(pinned.values())
    # a new name is hashed, above every pinned id and inside 32 bits; the
    # same in every table it appears in, whatever stands beside it
    new = weights.leaf_id("router")
    assert new == weights.leaf_id("router") and 2**16 <= new < 2**31
    assert new != weights.leaf_id("experts_gate")
    ids = {weights.leaf_id(n) for n in toymoe.leaf_table(MOE)}
    assert len(ids) == 13 and set(range(6)) | {9, 10, 11} < ids


def test_two_names_that_share_an_id_are_an_error_when_the_table_is_built(
        monkeypatch):
    from yardstick import weights

    leaf = weights.Leaf((4,))
    assert weights.check_table({"a": leaf, "b": leaf})
    monkeypatch.setattr(weights, "leaf_id", lambda name: 7)
    with pytest.raises(ValueError, match="share the id"):
        weights.check_table({"a": leaf, "b": leaf})


def test_a_leaf_may_live_on_some_layers_only():
    import jax.numpy as jnp

    from yardstick import weights

    table = {"first_only": weights.Leaf((3,), layers=range(0, 1)),
             "all_but_first": weights.Leaf((3,), 0.5, layers=range(1, 4)),
             "everywhere": weights.Leaf((3,), norm=True, layers=range(4)),
             "top": weights.Leaf((2, 3))}
    key = weights.seed_key(5)
    stacked = weights.stacked_layers(key, table, jnp.float32)
    assert {n: a.shape for n, a in stacked.items()} == {
        "first_only": (1, 3), "all_but_first": (3, 3), "everywhere": (4, 3)}
    assert set(weights.layer_tree(key, table, 0, jnp.float32)) == {
        "first_only", "everywhere"}
    assert set(weights.layer_tree(key, table, 2, jnp.float32, like=1)) == {
        "all_but_first", "everywhere"}
    # a stack's row is the layer's own draw, not the row's index
    assert digest(stacked["all_but_first"][0]) == digest(
        weights.leaf(key, table, "all_but_first", 1, jnp.float32))
    assert set(weights.top_tree(key, table, jnp.float32)) == {"top"}


MOE = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, vocab_size=512,
           num_hidden_layers=2, num_local_experts=4, num_experts_per_tok=2,
           router_aux_loss_coef=0.01, router_z_loss_coef=0.001,
           rope_theta=10000.0)


def test_a_mixture_of_experts_counts_stored_and_active_parameters_apart():
    attn = 2 * 64 * 16 * (4 + 2)
    expert, router = 3 * 64 * 96, 64 * 4
    assert toymoe.params_total(MOE) == (
        2 * (attn + router + 2 * 64 + 4 * expert) + 2 * 512 * 64 + 64)
    active = 2 * (attn + router + 2 * expert) + 64 * 512
    assert toymoe.matmul_params_per_token(MOE) == active
    assert toymoe.train_flops_per_token(MOE, 64) == (
        6 * active + 6 * 2 * 64 * 4 * 16)
    # one slot reaches two experts, three or more reach all four at most
    one = toymoe.weight_bytes(MOE, active_slots=1)
    assert one == 2 * ((attn + 128 + 2 * expert) * 2 + router * 4) + (
        64 * 512 + 64) * 2
    assert toymoe.weight_bytes(MOE, 3) - one == 2 * 2 * expert * 2
    assert toymoe.weight_bytes(MOE, 3) == toymoe.weight_bytes(MOE, 30)
    row = 2 * 16 * 2
    assert toymoe.decode_iter_bytes(MOE, 100, 3) == (
        toymoe.weight_bytes(MOE, 3) + 2 * 2 * 103 * row + 3 * 64 * 2)
    cfg = toymoe.program_config(MOE, {}, max_seq=64, dtype="float32")
    assert (cfg.n_experts, cfg.expert_top_k) == (4, 2)
    # room for every token at every expert: the program drops none
    assert int(cfg.capacity_factor * 4 * 64 * 2 / 4) >= 4 * 64
    assert (cfg.moe_balance_coef, cfg.moe_zloss_coef) == (0.01, 0.001)


@pytest.mark.parametrize("model,cfg", [(dense, dict(MISTRAL, num_hidden_layers=16)),
                                       (toymoe, MOE)])
def test_the_roofline_readers_count_with_the_cells_model_module(model, cfg):
    """The same trace and the same load read against the bytes and FLOPs
    of whichever architecture the cell's configuration names."""
    from types import SimpleNamespace

    from yardstick import readers

    cell = SimpleNamespace(model=model, root=spec.ROOT, chips=1)
    requests = [req(i, 1.0, 9.0, 100.0, 8000.0, 200) for i in range(3)]
    run = {"cell": cell, "config": dict(cfg, run={}),
           "device": {"kind": "TPU v5 lite"}, "requests": requests,
           "traced_span_client": (4.0, 6.0), "job": {"decode_window": 1},
           "trace": {"programs": {"jit_decode_window": {
               "calls": 10, "total_s": 0.2, "median_s": 0.02}}}}
    slots, positions = stats.live_load(requests, 4.0, 6.0)
    need = model.decode_iter_bytes(run["config"], positions, slots)
    assert readers.decode_hbm_roofline(run) == pytest.approx(
        100.0 * need / 819e9 / 0.02)
    run["job"] = {"step_ends": [1.0, 2.0], "tokens_per_step": 8192,
                  "window_s": 2.0, "seq": 2048}
    mfu = spec.Cell(spec.load_benchmark(), "mistral7b.train.b4x2048").reader(
        "train_mfu_pct")
    assert mfu(run) == pytest.approx(
        100.0 * model.train_flops_per_token(cfg, 2048) * 8192 / 197e12)
