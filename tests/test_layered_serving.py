"""A model whose layers are not all alike, served: full and window
attention layers with their own KV head counts, rope bases and caches, q/k
wider than v, partial rotary, a value scale, sinks, a leading dense layer,
then a sigmoid top-k router with a selection bias over experts of which a
share is held. The serving engine against the benchmark's PLAIN reference
(``perfbench/configs/mimo-v2-flash-serve-1chip.reference.py``: float32, no
cache, no kernels, the weights again from the seed through the model
module's leaf table) at tiny widths that keep every ratio."""

from __future__ import annotations

import dataclasses
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

PERFBENCH = Path(__file__).resolve().parents[1] / "perfbench"
sys.path.insert(0, str(PERFBENCH))

from yardstick import spec, weights  # noqa: E402

from tony_tpu.models import decode_weights, init_params  # noqa: E402
from tony_tpu.models.decode import _moe_mlp_decode, _pass_rows  # noqa: E402
from tony_tpu.ops import (  # noqa: E402
    cache_decode_attention,
    cache_prefill_attention,
)
from tony_tpu.serving import ServingEngine  # noqa: E402
from tony_tpu.serving import engine as engine_lib  # noqa: E402

SEED = 2 ** 31 + 77

# MiMo-V2-Flash's keys at toy sizes: qk 12 / v 8, 4 of 12 dims rotate, a
# window of 8 against sequences of 40-70, KV heads 1 (full) and 2 (window),
# F W W W F W, layer 0 dense, 8 experts top-3 of which 4 are held.
TINY = {
    "model": "mimo_v2_flash", "hidden_size": 32, "intermediate_size": 48,
    "moe_intermediate_size": 16, "num_attention_heads": 4, "head_dim": 12,
    "v_head_dim": 8, "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
    "num_hidden_layers": 6, "vocab_size": 96, "layernorm_epsilon": 1e-5,
    "rope_theta": 5_000_000, "swa_rope_theta": 10_000,
    "partial_rotary_factor": 0.334, "attention_value_scale": 0.707,
    "sliding_window": 8, "hybrid_layer_pattern": [0, 1, 1, 1, 0, 1, 1, 0],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1, 1],
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "n_routed_experts": 4,
    "n_shared_experts": None, "num_experts_per_tok": 3,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc",
    "routed_scaling_factor": None,
    "published": {"n_routed_experts": 8},
    "deployment": {"experts_first": 2},
}


@pytest.fixture(scope="module")
def model():
    return spec.load_model("mimo_v2_flash")


@pytest.fixture(scope="module")
def reference():
    return spec.load_module(
        PERFBENCH / "configs" / "mimo-v2-flash-serve-1chip.reference.py",
        "mimo_reference")


# The same with the PUBLISHED head widths (q/k 192 of which 64 rotate, v
# 128): a K row is then kept as two 128-lane tiles in both caches.
WIDE = dict(TINY, head_dim=192, v_head_dim=128)


def program(model, dtype="float32", max_seq=96, cfg=TINY):
    tcfg = model.program_config(cfg, {}, max_seq=max_seq, dtype=dtype)
    params = model.program_params(weights.seed_key(SEED), cfg,
                                  jnp.dtype(dtype))
    return tcfg, decode_weights(params, tcfg)


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TINY["vocab_size"], size=n).astype(np.int32)
            for n in lengths]


def reference_logits(reference, rows, cfg=TINY):
    """The reference's full forward pass over each row (its own length)."""
    return [np.asarray(reference.logits(
        cfg, SEED, jnp.asarray(r)[None], dtype="float32"))[0] for r in rows]


def widest_gap(reference, rows, n_prompt, cfg=TINY):
    """How far a served token's reference logit lies below the
    reference's best, at worst, over the served positions."""
    worst = 0.0
    for row, n, ref in zip(rows, n_prompt,
                           reference_logits(reference, rows, cfg)):
        at = np.arange(n - 1, len(row) - 1)
        worst = max(worst, float(np.max(ref[at].max(-1) - ref[at, row[at + 1]])))
    return worst


def test_the_program_takes_the_configuration(model):
    tcfg, fused = program(model)
    assert tcfg.layered and tcfg.layer_groups == {
        "full_dense": (0,), "window_moe": (1, 2, 3, 5), "full_moe": (4,)}
    assert tcfg.rot_dim == 4 and tcfg.held == (2, 4)
    assert isinstance(fused["layers"], tuple) and len(fused["layers"]) == 6
    assert "sink" in fused["layers"][1] and "sink" not in fused["layers"][4]
    assert fused["layers"][0]["gate_up"].shape == (32, 96)
    assert fused["layers"][1]["gate_up"].shape == (4, 32, 32)
    assert fused["layers"][1]["router"].dtype == jnp.float32
    k, v = engine_lib.init_slot_cache(tcfg, 3, 64, prefill_chunk=5)
    # full: Tmax rows of 1 head; window: a ring of 5 * ceil(13 / 5) = 15
    # positions and the parking row, 2 heads
    assert k["full"].shape == (2, 3, 64, 1, 12)
    assert v["window"].shape == (4, 3, 16, 2, 8)
    # the published widths: K 192 wide is kept as two 128-lane tiles
    big = dataclasses.replace(tcfg, head_dim=192, v_head_dim=128,
                              rotary_dim=64)
    k, v = jax.eval_shape(
        lambda: engine_lib.init_slot_cache(big, 3, 64, prefill_chunk=5))
    assert [t.shape for t in k["full"]] == [(2, 3, 64, 1, 128)] * 2
    assert v["window"].shape == (4, 3, 16, 2, 128)


def prefill_logit_gap(model, reference, chunk, dtype):
    """Prompts several windows long, of mixed lengths in one batch, through
    ``prefill_chunks`` chunk by chunk (the last chunk overlapping, short
    batches padded with a duplicate of row 0 as the host pads them): the
    largest distance of any chunk's last-position logits from the
    reference's full forward pass over float32 weights."""
    from tony_tpu.serving.scheduler import _chunk_plan

    tcfg, fused = program(model, dtype=dtype)
    rows = prompts([37, 9, 64, 23])
    want = reference_logits(reference, rows)
    k, v = engine_lib.init_slot_cache(tcfg, 4, 96, prefill_chunk=chunk)
    key = jax.random.key(0)
    worst = 0.0
    plans = [_chunk_plan(len(r), chunk) for r in rows]
    for step in range(max(len(p) for p in plans)):
        live = [i for i, p in enumerate(plans) if step < len(p)]
        live += [live[0]] * (4 - len(live))          # pad like the host
        toks = np.zeros((4, chunk), np.int32)
        starts, valids = np.zeros(4, np.int32), np.zeros(4, np.int32)
        for j, i in enumerate(live):
            starts[j], valids[j] = plans[i][step]
            toks[j, :valids[j]] = rows[i][starts[j]:starts[j] + valids[j]]
        k, v, _, logits, counts = engine_lib.prefill_chunks(
            fused, k, v, toks, np.asarray(live, np.int32), starts, valids,
            np.zeros(4, np.float32), key, np.int32(0), cfg=tcfg)
        assert counts["pairs"].shape == (4,)
        for j, i in enumerate(live):
            at = starts[j] + valids[j] - 1
            worst = max(worst, float(np.max(np.abs(
                np.asarray(logits[j]) - want[i][at]))))
    return worst


@pytest.fixture
def scores_limit(monkeypatch):
    """Lower ``engine.SCORES_LIMIT`` for a test (the programs read it
    while they trace, so their caches are dropped around the change)."""
    def lower(limit: int) -> None:
        monkeypatch.setattr(engine_lib, "SCORES_LIMIT", limit)
        engine_lib.prefill_chunks.clear_cache()

    yield lower
    engine_lib.prefill_chunks.clear_cache()


@pytest.mark.parametrize("chunk,limit", [(5, None), (16, None), (16, 2 ** 16)],
                         ids=["5", "16", "16-scores-over-the-limit"])
def test_chunked_prefill_logits_equal_the_references(model, reference, chunk,
                                                     limit, scores_limit):
    """Float32 on both sides: 2e-4 is ten times the largest gap seen
    (summation order on logits of order 1), a hundredth of what bfloat16
    gives (next test). The last size with the limit on a round's scores
    lowered to 64 KiB: the full layers' 96 KiB (4 rows x 16 queries x 4
    heads x 96 keys in float32) go through ``cache_prefill_attention``,
    the rings' 33 KiB stay on the batched path."""
    if limit:
        scores_limit(limit)
        tcfg, _ = program(model)
        k, _ = jax.eval_shape(lambda: engine_lib.init_slot_cache(
            tcfg, 4, 96, prefill_chunk=chunk))
        assert engine_lib.prefill_read_block(tcfg, k, 4, chunk) == 32
    worst = prefill_logit_gap(model, reference, chunk, "float32")
    assert worst < 2e-4, worst


def test_bfloat16_in_float32s_place_fails_the_same_tolerance(model,
                                                             reference):
    """The same comparison with the program in bfloat16 (weights, matmuls
    and both caches) where float32 is stated: far outside 2e-4."""
    worst = prefill_logit_gap(model, reference, 5, "bfloat16")
    assert worst > 2e-3, worst


def serve(tcfg, fused, requests, **kw):
    eng = ServingEngine(fused, tcfg, slots=2, max_len=96, prefill_chunk=5,
                        kv_quant="none", **kw)
    handles = [eng.submit(p, n) for p, n in requests]
    while eng.step():
        pass
    rows = [np.concatenate([p, np.asarray(h.result()["tokens"], np.int32)])
            for (p, _), h in zip(requests, handles)]
    return eng, rows


@pytest.mark.parametrize("window,cfg", [(1, TINY), (3, TINY), (1, WIDE)],
                         ids=["window1", "window3", "published-head-widths"])
def test_served_tokens_are_the_references_first_choice(model, reference,
                                                       window, cfg):
    """Two slots, five requests of mixed lengths: prefill in chunks, then
    decode through both caches, a slot reused by a SHORTER request after a
    longer one (its ring and its full rows still hold the last tenant's).
    Every served token's reference logit lies within 1e-3 of the
    reference's best over its own prefix: float32 rounding moves a logit
    by 1e-5, so a token served is the reference's first choice or ties
    with it to that rounding (the logits themselves are held to 2e-4
    above, where bfloat16 fails)."""
    requests = list(zip(prompts([41, 12, 30, 7, 19], seed=3),
                        [30, 25, 12, 40, 9]))
    tcfg, fused = program(model, cfg=cfg)
    eng, rows = serve(tcfg, fused, requests, decode_window=window)
    gap = widest_gap(reference, rows, [len(p) for p, _ in requests], cfg)
    assert gap < 1e-3, gap
    stats = eng.stats()
    # tokens that went through the layers: every chunk's (the last chunk
    # of a prompt overlaps the one before), and every served token but a
    # request's last, which is never fed
    from tony_tpu.serving.scheduler import _chunk_plan
    n_tokens = sum(sum(n for _, n in _chunk_plan(len(p), 5)) + n_new - 1
                   for p, n_new in requests)
    assert stats["experts"]["held"] == [2, 4]
    if window == 1:       # a deeper window decodes past a retirement
        assert stats["experts"]["pairs_total"] == n_tokens * 5 * 3
    assert 0 < stats["experts"]["pairs_held"] < stats["experts"]["pairs_total"]
    assert sum(stats["experts"]["pairs_per_expert"]) == \
        stats["experts"]["pairs_held"]
    kinds = stats["kv"]["kinds"]
    assert kinds["full"]["reserved_positions"] == 2 * 96 == \
        stats["kv"]["reserved_positions"]
    assert kinds["window"]["reserved_positions"] == 2 * 16
    assert 0 < kinds["window"]["live_position_ms"] < \
        kinds["full"]["live_position_ms"] == stats["kv"]["live_position_ms"]


@pytest.mark.parametrize("cfg", [TINY, WIDE],
                         ids=["tiny", "published-head-widths"])
def test_shipped_rows_continue_where_prefill_left(model, reference, cfg):
    """Prefill on one engine, decode on another from the exported rows of
    both cache kinds: the same tokens as one engine serving the request."""
    tcfg, fused = program(model, cfg=cfg)
    prompt = prompts([33], seed=5)[0]
    a = ServingEngine(fused, tcfg, slots=2, max_len=96, prefill_chunk=5,
                      kv_quant="none")
    first = a.prefill_only(prompt, 12)
    while a.step():
        pass
    kv_k, kv_v = first.kv
    assert kv_k["full"].shape == (2, 33, 1, cfg["head_dim"])
    assert kv_v["window"].shape == (4, 15, 2, cfg["v_head_dim"])
    b = ServingEngine(fused, tcfg, slots=2, max_len=96, prefill_chunk=5,
                      kv_quant="none")
    rest = b.submit_with_kv(kv_k, kv_v, first.result()["tokens"][0], 33, 11)
    while b.step():
        pass
    _, rows = serve(tcfg, fused, [(prompt, 12)])
    got = first.result()["tokens"] + rest.result()["tokens"]
    np.testing.assert_array_equal(rows[0][33:], got)


# -- the expert layer ----------------------------------------------------------
def expert_layer(model, reference, first, count, *, bias=None, tokens=24,
                 n_experts=8, token_mask=None, count_mask=None):
    """One expert layer of the program (held = first .. first + count of
    ``n_experts``) and the UNCUT reference layer's weights, the same
    experts."""
    cfg = dict(TINY, n_routed_experts=n_experts,
               published={"n_routed_experts": n_experts},
               deployment={"experts_first": 0})
    table = model.leaf_table(cfg)
    p = jax.tree.map(lambda w: w.astype(jnp.float32), weights.layer_tree(
        weights.seed_key(SEED), table, 1, jnp.float32))
    if bias is not None:
        p["router_bias"] = jnp.asarray(bias, jnp.float32)
    x = jax.random.normal(jax.random.key(4), (2, tokens // 2, 32))
    tcfg = dataclasses.replace(
        model.program_config(cfg, {}, max_seq=64, dtype="float32"),
        experts_held=(first, count))
    held = slice(first, first + count)
    lp = {"ln2": p["post_norm"], "router": p["router"],
          "router_bias": p["router_bias"],
          "gate_up": jnp.concatenate([p["experts_gate"][held],
                                      p["experts_up"][held]], -1),
          "w_down": p["experts_down"][held]}
    out, counts = jax.jit(lambda x: _moe_mlp_decode(
        x, lp, tcfg, token_mask, count_mask))(x)
    return cfg, p, x, out, counts


def test_the_shares_of_one_layer_add_up_to_the_uncut_reference(
        model, reference):
    """Four chips hold 2 of 8 experts each: the parts they give add up to
    the reference's layer with all 8 (float32: 1e-5 is summation order)."""
    total, all_pairs = 0.0, 0
    for first in (0, 2, 4, 6):
        cfg, p, x, out, counts = expert_layer(model, reference, first, 2)
        total = total + out
        all_pairs += int(counts["pairs"].sum())
    want = reference.experts(x, p, cfg, lambda a: a, first=0) - x
    assert all_pairs == x.shape[0] * x.shape[1] * 3
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5, rtol=0)
    # and each share alone is the reference's share
    cfg, p, x, out, _ = expert_layer(model, reference, 4, 2)
    held = {k: v[4:6] if k.startswith("experts_") else v
            for k, v in p.items()}
    want = reference.experts(x, held, cfg, lambda a: a, first=4) - x
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=0)


@pytest.mark.parametrize("held,landing", [((5, 1), 24), ((0, 3), 0)])
def test_no_pair_is_dropped_under_any_routing(model, reference, held,
                                              landing):
    """A selection bias sends EVERY token to experts 5, 6 and 7. A chip
    that holds expert 5 alone gets all 24 tokens on its one expert (no
    capacity drops one); a chip that holds 0-2 gets none and adds
    nothing. Both equal the reference."""
    bias = [0, 0, 0, 0, 0, 50, 50, 50]
    cfg, p, x, out, counts = expert_layer(model, reference, *held, bias=bias)
    assert int(counts["pairs"].sum()) == landing
    part = {k: v[held[0]:held[0] + held[1]] if k.startswith("experts_")
            else v for k, v in p.items()}
    want = reference.experts(x, part, cfg, lambda a: a, first=held[0]) - x
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=0)
    if not landing:
        assert not np.asarray(out).any()


# 512 tokens x top-3 over 32 experts: 1,536 pair rows against 1-3 held
# experts' weights is a shape that takes its pairs in passes.
PASS_TOKENS = 512
TO_5_6_7 = [0.0] * 5 + [50.0] * 3 + [0.0] * 24
FIRST_257 = np.arange(PASS_TOKENS).reshape(2, -1) < 257


@pytest.mark.parametrize("held,bias,token_mask,count_mask,rows,passes", [
    ((4, 2), None, None, None, 384, 1),
    ((5, 3), TO_5_6_7, None, None, 640, 3),
    ((0, 2), TO_5_6_7, None, None, 384, 0),
    ((5, 1), TO_5_6_7, FIRST_257, None, 256, 2),
    ((5, 2), TO_5_6_7, ~FIRST_257, FIRST_257, 384, 2),
], ids=["uniform", "every-pair-here", "no-pair-here", "one-over-a-pass",
        "token-and-count-masks"])
def test_passes_drop_no_pair_and_equal_the_reference(
        model, reference, held, bias, token_mask, count_mask, rows, passes):
    """The expert layer where it takes its pairs in PASSES of ``rows``
    (``_pass_rows``), against the plain float32 reference's share: a
    routing near uniform (one pass); a selection bias that sends every
    token to experts 5, 6 and 7 onto a chip that holds all three (all
    1,536 pairs land: three passes of 640, the last padded, none
    dropped), that holds none (no pass, exactly zero), that holds
    expert 5 with 257 tokens taking part (one pair over a pass: two),
    and with both masks set (255 tokens take part on two experts: 510
    pairs in two passes; none of them counted)."""
    first, count = held
    cfg, p, x, out, counts = expert_layer(
        model, reference, first, count, bias=bias, tokens=PASS_TOKENS,
        n_experts=32,
        token_mask=None if token_mask is None else jnp.asarray(token_mask),
        count_mask=None if count_mask is None else jnp.asarray(count_mask))
    assert _pass_rows(PASS_TOKENS * 3, 32, count, 32, 4,
                      count * 3 * 32 * 16 * 4) == rows
    assert int(counts["passes"]) == passes
    part = {k: v[first:first + count] if k.startswith("experts_") else v
            for k, v in p.items()}
    want = np.asarray(reference.experts(x, part, cfg, lambda a: a,
                                        first=first) - x)
    taking_part = np.ones((2, PASS_TOKENS // 2), bool)
    if token_mask is not None:
        taking_part = token_mask
        want = want * token_mask[..., None]
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5, rtol=0)
    if bias is not None:
        # every token that takes part sends one pair to each of 5, 6, 7
        landing = count * int(taking_part.sum()) if first == 5 else 0
        counted = taking_part if count_mask is None else (
            taking_part & count_mask)
        assert (landing + rows - 1) // rows == passes
        assert int(counts["pairs"].sum()) == (
            count * int(counted.sum()) if first == 5 else 0)
    if not passes:
        assert not np.asarray(out).any()


@pytest.mark.parametrize("nk,rows", [(4096, 1024), (512, 512)],
                         ids=["prefill-round", "decode-iteration"])
def test_pass_rows_at_the_served_widths(nk, rows):
    """MiMo-V2-Flash's share on one chip (16 of 256 experts, d 4096, F
    2048, bfloat16: 805 MB of weights): a prefill round's 4,096 pair
    rows go in passes of 1,024, a decode iteration's 512 in one."""
    held_bytes = 16 * 3 * 4096 * 2048 * 2
    assert _pass_rows(nk, 4096, 16, 256, 2, held_bytes) == rows
    # every expert held: the worst case is the mean, nothing to follow
    assert _pass_rows(nk, 4096, 256, 256, 2, 16 * held_bytes) == nk


@pytest.mark.parametrize("sizes,k", [([3, 0, 100, 17, 40], 256),
                                     ([0, 0, 0, 0, 256], 256),
                                     ([0, 0, 0, 0, 0], 256),
                                     ([70, 0, 1, 57, 100], 8192)],
                         ids=["uneven", "all-on-one", "none", "two-k-tiles"])
def test_grouped_matmul_kernel_equals_ragged_dot(sizes, k):
    """The grouped product's Pallas kernel (interpret mode) against
    ``lax.ragged_dot`` on the rows that belong to a group: groups of
    uneven size, an empty group, every row on one group, and no row at
    all; rows past the groups' sum are undefined and not compared. The
    tiles keep all of k = 256 (one k tile: the rows stay resident); k =
    8,192 in float32 is past what a [k, 128] tile may hold, and runs as
    two. Float32: 1e-4 (1e-3 at k = 8,192) is summation order."""
    from tony_tpu.ops import grouped_matmul
    from tony_tpu.ops.grouped import _tiling

    n = 384 if k == 256 else 128
    assert _tiling(256, k, n, 4) == (128, min(k, 4096), 128)
    k1, k2 = jax.random.split(jax.random.key(0))
    lhs = jax.random.normal(k1, (256, k), jnp.float32)
    rhs = jax.random.normal(k2, (5, k, n), jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)
    want = grouped_matmul(lhs, rhs, sizes, mode="jax")
    got = grouped_matmul(lhs, rhs, sizes, mode="interpret")
    rows = int(sizes.sum())
    assert got.shape == want.shape == (256, n)
    np.testing.assert_allclose(np.asarray(got[:rows]), np.asarray(want[:rows]),
                               atol=1e-4 if k == 256 else 1e-3, rtol=0)


@pytest.mark.parametrize("name,h_kv,window,sink,block_rows", [
    ("full", 4, 0, False, 64),
    ("ring", 8, 16, True, 4096),
    ("ring-blocks", 2, 16, True, 16),      # blocks that hold no visible row
    ("ring-no-sink", 8, 16, False, 4096),
])
def test_cache_decode_kernel_equals_the_plain_path(name, h_kv, window, sink,
                                                   block_rows):
    """K rows 192 wide kept as two 128-lane tiles (the second zero-filled
    past 64), V rows 128: a full cache read up to pos, and a ring of 32
    positions + parking row read through its window of 16 with the sinks
    in the denominator. Slots at position 0, before the first wrap and
    past it. The kernel (interpret mode) sums the tiles' products; the
    plain path lays the tiles side by side. bfloat16 data, float32
    accumulation in both: 2e-2 on values of order 1."""
    slots, n_h, t = 4, 16, 33 if window else 64
    keys = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(keys[0], (slots, n_h, 192), jnp.bfloat16)
    k = jax.random.normal(keys[1], (2, slots, t, h_kv, 192), jnp.bfloat16)
    k_tiles = engine_lib._lane_tiles(k, 2)
    assert [x.shape[-1] for x in k_tiles] == [128, 128]
    v_all = jax.random.normal(keys[2], (2, slots, t, h_kv, 128), jnp.bfloat16)
    b = jax.random.normal(keys[3], (n_h,)) * 2 if sink else None
    pos = jnp.asarray([0, 9, 40, 63], jnp.int32)
    kw = dict(window=window, sink=b)
    want = cache_decode_attention(q, k, v_all, jnp.int32(1), pos,
                                  mode="jax", **kw)
    tiled = cache_decode_attention(q, k_tiles, v_all, jnp.int32(1), pos,
                                   mode="jax", **kw)
    np.testing.assert_array_equal(np.asarray(tiled, np.float32),
                                  np.asarray(want, np.float32))
    got = cache_decode_attention(q, k_tiles, v_all, jnp.int32(1), pos,
                                 mode="interpret", block_rows=block_rows,
                                 **kw)
    assert got.shape == (slots, n_h, 128)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


@pytest.mark.parametrize("name,h_kv,window,block_rows", [
    ("full-4", 4, 0, 64),         # blocks of 16 positions of 64
    ("full-8", 8, 0, 64),         # blocks of 8 positions
    ("ring-sink", 8, 16, 64),     # a ring of 32 positions in 4 blocks
])
def test_cache_decode_kernel_reads_a_slots_live_blocks_only(name, h_kv,
                                                            window,
                                                            block_rows):
    """K 192 wide in two lane tiles. Slots at position 0, at the last
    position of their first key block, at the first of the second, deep
    in the cache (a ring: past its first wrap, every row live), and one
    that decodes nothing (pos -1: zeros). NaN in every row of every key
    block past a slot's last live one, the ring's parking row and all of
    the idle slot: the result is finite, bit for bit what the clean
    cache gives, and within 2e-2 of the plain path."""
    from tony_tpu.ops.attention import decode_key_block, decode_last_block

    slots, n_h, t = 5, 16, 33 if window else 64
    t_read = t - 1 if window else t
    block = decode_key_block(t_read, h_kv, block_rows)
    keys = jax.random.split(jax.random.key(11), 4)
    q = jax.random.normal(keys[0], (slots, n_h, 192), jnp.bfloat16)
    k = jax.random.normal(keys[1], (2, slots, t, h_kv, 192), jnp.bfloat16)
    v = jax.random.normal(keys[2], (2, slots, t, h_kv, 128), jnp.bfloat16)
    b = jax.random.normal(keys[3], (n_h,)) * 2 if window else None
    pos = jnp.asarray([0, block - 1, block, 45, -1], jnp.int32)
    last = np.asarray(decode_last_block(pos, t_read, block))
    assert last.tolist() == [0, 0, 1, t_read // block - 1
                             if window else 45 // block, 0]
    at = np.arange(t)[None, :]
    dead = (at // block > last[:, None]) | (at >= t_read) \
        | (np.asarray(pos) < 0)[:, None]
    dead = jnp.asarray(dead)[None, :, :, None, None]
    run = functools.partial(
        cache_decode_attention, layer=jnp.int32(1), pos=pos, window=window,
        sink=b, mode="interpret", block_rows=block_rows)
    clean = run(q, engine_lib._lane_tiles(k, 2), v)
    got = run(q, engine_lib._lane_tiles(jnp.where(dead, jnp.nan, k), 2),
              jnp.where(dead, jnp.nan, v))
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(clean, np.float32))
    assert not np.asarray(got[-1], np.float32).any()
    want = cache_decode_attention(q, k, v, jnp.int32(1), pos, window=window,
                                  sink=b, mode="jax")
    np.testing.assert_allclose(np.asarray(got[:-1], np.float32),
                               np.asarray(want[:-1], np.float32), atol=2e-2)


def test_a_ring_is_masked_by_position_not_by_row():
    """A slot at position 5 of a ring full of a last tenant's rows sees
    rows 0..5 only: the answer does not move when every other row does."""
    keys = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(keys[0], (1, 4, 16))
    k = jax.random.normal(keys[1], (1, 1, 33, 2, 16))
    v = jax.random.normal(keys[2], (1, 1, 33, 2, 8))
    pos = jnp.asarray([5], jnp.int32)
    a = cache_decode_attention(q, k, v, jnp.int32(0), pos, window=16,
                               mode="jax")
    b = cache_decode_attention(q, k.at[:, :, 6:].mul(-3.0),
                               v.at[:, :, 6:].add(9.0), jnp.int32(0), pos,
                               window=16, mode="jax")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the prefill kernel, interpret mode against the plain path -------------------
def prefill_case(h_kv, d_k, starts, slots, chunk=8, t=64, n_h=16,
                 dtype=jnp.bfloat16):
    """A stacked cache of 2 layers x 5 slots x ``t`` positions and a
    round of chunks at ``starts`` in ``slots``; K 192 wide comes as two
    128-lane tiles."""
    keys = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(keys[0], (len(starts), chunk, n_h, d_k), dtype)
    k = jax.random.normal(keys[1], (2, 5, t, h_kv, d_k), dtype)
    v = jax.random.normal(keys[2], (2, 5, t, h_kv, 128), dtype)
    sink = jax.random.normal(keys[3], (n_h,)) * 2
    ends = jnp.asarray(starts, jnp.int32) + chunk
    return q, k, v, sink, jnp.asarray(slots, jnp.int32), ends


@pytest.mark.parametrize("name,h_kv,d_k,sink,starts,slots,block_rows", [
    # starts: 0, one inside a key block (16 positions), the last chunk
    # before Tmax; the fourth row duplicates the first as the host pads
    ("gqa4-k192", 4, 192, False, [0, 21, 56, 0], [2, 0, 4, 2], 64),
    ("gqa4-k192-sink", 4, 192, True, [0, 21, 56, 0], [2, 0, 4, 2], 64),
    ("gqa8-k128", 8, 128, False, [0, 21, 56, 0], [2, 0, 4, 2], 64),
    ("gqa8-k128-sink", 8, 128, True, [40, 40, 40, 40], [1, 1, 1, 1], 64),
    ("mha-one-block", 16, 128, True, [3, 50], [4, 3], 4096),
    ("one-kv-head", 1, 128, False, [0, 21, 56], [2, 0, 4], 16),
    # a float32 cache: a head's rows are read strided where they lie
    ("gqa4-float32", 4, 128, True, [0, 21, 56], [2, 0, 4], 64),
])
def test_cache_prefill_kernel_equals_the_plain_path(name, h_kv, d_k, sink,
                                                    starts, slots,
                                                    block_rows):
    """The kernel (interpret mode) reads the slots' blocks out of the
    stack up to each chunk's end and gives each KV head's queries their
    own keys; the plain path reads the slots' rows whole and masks.
    bfloat16 data, float32 scores and accumulation in both: 2e-2 on
    values of order 1."""
    dtype = jnp.float32 if "float32" in name else jnp.bfloat16
    q, k, v, b, slots, ends = prefill_case(h_kv, d_k, starts, slots,
                                           dtype=dtype)
    padded = starts[-1] == starts[0] and int(slots[-1]) == int(slots[0])
    if padded:
        q = q.at[-1].set(q[0])
    k_in = engine_lib._lane_tiles(k, 2) if d_k == 192 else k
    kw = dict(sink=b if sink else None)
    want = cache_prefill_attention(q, k_in, v, jnp.int32(1), slots, ends,
                                   mode="jax", **kw)
    got = cache_prefill_attention(q, k_in, v, jnp.int32(1), slots, ends,
                                  mode="interpret", block_rows=block_rows,
                                  **kw)
    assert got.shape == want.shape == q.shape[:3] + (128,)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    # a duplicated row reads what its first reads
    if padded:
        np.testing.assert_array_equal(np.asarray(got[-1], np.float32),
                                      np.asarray(got[0], np.float32))


def test_a_short_chunk_is_not_moved_by_its_padded_tail():
    """A prompt of 5 tokens under a chunk of 8: the chunk wrote garbage
    at positions 5, 6, 7 (here: rows of 1e4). The 5 valid queries read
    the same from the kernel as from the plain path."""
    q, k, v, _, slots, ends = prefill_case(4, 192, [0], [3])
    k = k.at[:, 3, 5:8].set(1e4)
    v = v.at[:, 3, 5:8].set(1e4)
    args = (q, engine_lib._lane_tiles(k, 2), v, jnp.int32(0), slots, ends)
    want = cache_prefill_attention(*args, mode="jax")
    got = cache_prefill_attention(*args, mode="interpret", block_rows=64)
    np.testing.assert_allclose(np.asarray(got[:, :5], np.float32),
                               np.asarray(want[:, :5], np.float32),
                               atol=2e-2)


@pytest.mark.parametrize("mode", ["jax", "interpret"])
def test_a_chunk_reads_nothing_past_its_end(mode):
    """Rows at and past ``ends[r]`` of every slot multiplied by -3 in K
    and V (a last tenant's, or positions decode will write): the output
    stays bit for bit, in blocks the kernel skips and in the block the
    chunk ends inside."""
    q, k, v, b, slots, ends = prefill_case(4, 128, [0, 21, 40], [2, 0, 4])
    past = (jnp.arange(64)[None, :] >= jnp.zeros(5, jnp.int32).at[
        slots].set(ends)[:, None])[None, :, :, None, None]
    a, c = (cache_prefill_attention(
        q, kk, vv, jnp.int32(1), slots, ends, sink=b, mode=mode,
        block_rows=64)
        for kk, vv in ((k, v), (jnp.where(past, k * -3, k),
                                jnp.where(past, v * -3, v))))
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(c, np.float32))


# -- forward and generate refuse, never a silently uniform model ---------------
def test_forward_and_generate_refuse_a_layered_configuration(model):
    from tony_tpu.models import forward, generate
    from tony_tpu.models.decode import advance, init_cache

    tcfg, fused = program(model)
    raw = init_params(jax.random.key(0), tcfg)
    assert set(raw["layers"]) == {"full_dense", "window_moe", "full_moe"}
    assert raw["layers"]["window_moe"]["wk"].shape == (4, 32, 2, 12)
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="uniform layers only"):
        forward(raw, tokens, tcfg)
    with pytest.raises(ValueError, match="uniform layers only"):
        generate(fused, tokens, tcfg, max_new_tokens=4)
    with pytest.raises(ValueError, match="uniform layers only"):
        advance(fused, init_cache(tcfg, 1, 16), tokens, tcfg)
