"""A model whose layers are mostly gated short convolutions with a
per-slot conv state, beside grouped-query attention layers, dense and
expert MLPs and a tied head, served: the serving engine against the
benchmark's PLAIN reference
(``perfbench/configs/lfm2-8b-a1b-serve-1chip.reference.py``: float32, the
convolution over the whole sequence, no cache, no state, the weights again
from the seed) at toy widths that keep every mechanism: d 64, 4 heads of 16
over 2 KV heads, 8 experts top-2, 3 taps, layers ``conv conv attn conv
conv``, the first two dense. And the cache of 64-wide heads, two to a row,
against the plain path."""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

PERFBENCH = Path(__file__).resolve().parents[1] / "perfbench"
sys.path.insert(0, str(PERFBENCH))

from yardstick import spec, weights  # noqa: E402

from tony_tpu.models import (TransformerConfig, decode_weights, generate,  # noqa: E402
                             init_params)
from tony_tpu.models import decode as decode_lib  # noqa: E402
from tony_tpu.ops import rms_norm  # noqa: E402
from tony_tpu.ops import attention as attention_lib  # noqa: E402
from tony_tpu.serving import ServingEngine  # noqa: E402
from tony_tpu.serving import engine as engine_lib  # noqa: E402
from tony_tpu.serving.scheduler import _chunk_plan  # noqa: E402

SEED = 2 ** 31 + 43
TINY = {
    "model": "lfm2_moe", "conv_L_cache": 3, "conv_bias": False,
    "hidden_size": 64, "intermediate_size": 96,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv"],
    "moe_intermediate_size": 48, "norm_eps": 1e-5, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_dense_layers": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 5,
    "num_key_value_heads": 2, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 96,
    "conf": {"tony.serving.prefill-chunk": 8},
}
TOL = 2e-4            # float32 against float32: sums in another order


@pytest.fixture(scope="module")
def model():
    return spec.load_model("lfm2_moe")


@pytest.fixture(scope="module")
def reference():
    return spec.load_module(
        PERFBENCH / "configs" / "lfm2-8b-a1b-serve-1chip.reference.py",
        "lfm2_reference")


_PROGRAM: list = []


def program(model):
    """(the program's configuration, the seeded weights fused), made once."""
    if not _PROGRAM:
        tcfg = model.program_config(TINY, {}, max_seq=128, dtype="float32")
        params = model.program_params(weights.seed_key(SEED), TINY,
                                      jnp.float32)
        _PROGRAM.append((tcfg, decode_weights(params, tcfg)))
    return _PROGRAM[0]


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TINY["vocab_size"], size=n).astype(np.int32)
            for n in lengths]


def reference_logits(reference, row, **how):
    """The reference over ``row`` padded to 128 positions (causal: what
    follows changes nothing before it), so that it compiles once."""
    padded = np.zeros(128, np.int32)
    padded[:row.size] = row
    return np.asarray(reference.logits(
        TINY, SEED, jnp.asarray(padded)[None], dtype="float32",
        **how))[0, :row.size]


def serve(engine, rows, new):
    reqs = [engine.submit(r, new) for r in rows]
    while not all(r.done() for r in reqs):
        engine.step()
    return [np.asarray(r.result()["tokens"], np.int32) for r in reqs]


def prefill(fused, tcfg, rows, chunk, slots=4, batch=2):
    """Prompts through ``prefill_chunks`` in rounds of ``batch`` rows (a
    short round padded with a duplicate of row 0, a prompt's last chunk
    padded, as the host does both), prompt i into slot i + 1. Returns the
    caches and every chunk's last-position logits by (prompt, position)."""
    k, v = engine_lib.init_slot_cache(tcfg, slots, 128, prefill_chunk=chunk)
    plans = [_chunk_plan(r.size, chunk, aligned=True) for r in rows]
    out = {}
    for step in range(max(len(p) for p in plans)):
        live = [i for i, p in enumerate(plans) if step < len(p)]
        for lo in range(0, len(live), batch):
            part = live[lo:lo + batch]
            pad = part + [part[0]] * (batch - len(part))
            toks = np.zeros((batch, chunk), np.int32)
            starts = np.zeros(batch, np.int32)
            valid = np.zeros(batch, np.int32)
            for j, i in enumerate(pad):
                starts[j], valid[j] = plans[i][step]
                toks[j, :valid[j]] = rows[i][starts[j]:starts[j] + valid[j]]
            k, v, _, logits, _ = engine_lib.prefill_chunks(
                fused, k, v, toks, np.asarray([i + 1 for i in pad], np.int32),
                starts, valid, np.zeros(batch, np.float32),
                jax.random.key(0), np.int32(0), cfg=tcfg)
            for j, i in enumerate(part):
                out[i, int(starts[j] + valid[j] - 1)] = np.asarray(logits[j])
    return k, v, out


def test_the_program_takes_the_configuration(model):
    tcfg, fused = program(model)
    assert tcfg.layered and tcfg.tie_embeddings and tcfg.layer_groups == {
        "conv_dense": (0, 1), "full_moe": (2,), "conv_moe": (3, 4)}
    conv, full = fused["layers"][0], fused["layers"][2]
    assert {k: v.shape for k, v in conv.items() if "proj" in k or k == "conv_w"
            } == {"in_proj": (64, 192), "conv_w": (64, 3),
                  "out_proj": (64, 64)}
    assert not {"qkv", "wo", "q_norm"} & set(conv)
    assert full["qkv"].shape == (64, 64 + 32 + 32) and "q_norm" in full
    assert not {"in_proj", "conv_w"} & set(full)
    assert "unembed" not in fused          # one matrix on the device
    assert "conv" not in decode_lib.rope_tables(tcfg)
    k, v = engine_lib.init_slot_cache(tcfg, 3, 128, prefill_chunk=8)
    assert k["full"].shape == v["full"].shape == (1, 3, 128, 2, 16)
    assert [b.shape for b in k["conv"]] == [(3, 2, 64)] * 4
    assert "conv" not in v and engine_lib.has_state(tcfg)
    with pytest.raises(ValueError):
        TransformerConfig(n_layers=2, attn_kinds=("conv", "full"),
                          conv_kernel=1)


@pytest.mark.parametrize("what", ["forward", "train", "generate"])
def test_conv_layers_are_served_not_trained(model, what):
    from tony_tpu.models import forward, param_roles

    tcfg, fused = program(model)
    with pytest.raises(ValueError, match="served, not trained"):
        if what == "forward":
            forward(fused, jnp.zeros((1, 8), jnp.int32), tcfg)
        elif what == "train":
            param_roles(tcfg)
        else:
            generate(fused, jnp.zeros((1, 8), jnp.int32), tcfg, 4)


def test_the_tied_head_is_the_embeddings_transpose(model):
    tcfg, fused = program(model)
    x = jax.random.normal(jax.random.key(1), (3, 1, 64))
    want = rms_norm(x, fused["final_norm"], eps=tcfg.rms_eps,
                    force_jax=True)[:, 0] @ fused["embed"].T
    got = decode_lib.lm_head(x, fused, tcfg)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    raw = init_params(jax.random.key(0), tcfg)
    assert "unembed" not in raw and "unembed" not in decode_weights(raw, tcfg)


# -- (a) the system against the plain reference --------------------------------
def test_the_conv_operator_alone(model, reference):
    """One conv layer's operator over a whole sequence from an empty state
    (the closure hands it zeros) against the reference's, which pads."""
    tcfg, fused = program(model)
    lp = fused["layers"][3]
    table = model.leaf_table(TINY)
    p = {n: weights.leaf(weights.seed_key(SEED), table, n, 3, jnp.float32)
         for n, row in table.items() if row.layers and 3 in row.layers}
    x = jax.random.normal(jax.random.key(2), (2, 21, 64))
    want = reference.short_conv(x, p, TINY, lambda a: a)
    h = rms_norm(x, lp["ln1"], eps=tcfg.rms_eps, force_jax=True)
    seen = []

    def attend(g, k_new, v_new, attn, sink):
        seen.append((attn, g.shape))
        return jnp.zeros((2, 2, 64))

    got = x + decode_lib._short_conv(h, lp, attend, jnp.dtype("float32"))
    assert seen == [("conv", (2, 21, 64))]
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_prefill_rounds_give_the_references_logits(model, reference, chunk):
    """Prompts of mixed lengths that end mid-chunk: every chunk's
    last-position logits against the reference's full forward pass, the
    conv state carried from round to round."""
    tcfg, fused = program(model)
    rows = prompts([70, 45, 23])
    refs = [reference_logits(reference, r) for r in rows]
    _, _, logits = prefill(fused, tcfg, rows, chunk)
    assert len(logits) == sum(-(-r.size // chunk) for r in rows)
    assert max(np.abs(got - refs[i][at]).max()
               for (i, at), got in logits.items()) < TOL


def test_a_state_dropped_between_rounds_fails_the_same_tolerance(
        model, reference):
    """The control: the reference with the conv state zeroed at every chunk
    boundary, what an engine that kept no state would compute, is far
    outside the tolerance the engine meets — the comparison that decides
    ``correct`` can see the state."""
    tcfg, fused = program(model)
    row = prompts([58], seed=5)[0]
    want = reference_logits(reference, row)
    wrong = reference_logits(reference, row, state_reset=8)
    _, _, logits = prefill(fused, tcfg, [row], 8)
    ours = max(np.abs(got - want[at]).max() for (_, at), got in logits.items())
    theirs = max(np.abs(wrong[at] - want[at]).max() for _, at in logits)
    assert ours < TOL < 50 * TOL < theirs


@pytest.mark.parametrize("chunk,batch", [(4, 1), (8, 2), (16, 4)])
def test_served_tokens_are_the_references_first_choice(model, reference,
                                                        chunk, batch):
    """Prefill in uneven rounds and then decoding through the cache and
    the conv state, more requests than slots (so a slot is reused after a
    longer tenant): every served token is the one the reference's full
    forward pass over the served row puts first, its logits leaving no gap
    to it; and the engine's counters."""
    tcfg, fused = program(model)
    rows = prompts([70, 45, 23, 90, 37], seed=chunk)
    engine = ServingEngine(fused, tcfg, slots=3, max_len=128,
                           prefill_chunk=chunk, prefill_batch=batch)
    served = serve(engine, rows, 20)
    width = 110
    tokens = np.zeros((5, width), np.int32)
    for i, (prompt, new) in enumerate(zip(rows, served)):
        tokens[i, :prompt.size + 20] = np.concatenate([prompt, new])
    lens = np.asarray([r.size for r in rows], np.int32)
    gaps = reference.served_token_gaps(TINY, SEED, tokens, lens, lens + 20,
                                       dtype="float32")
    assert gaps["tokens"] == gaps["top1_agree"] == 100
    assert gaps["widest_gap"] < TOL
    stats = engine.stats()
    assert stats["state"]["slots_reset"] == 5
    assert stats["state"]["live_state_ms"] > 0
    chunks = [-(-r.size // chunk) for r in rows]
    assert stats["conv"] == {"layers": 4,
                             "rows_carried": 4 * sum(n - 1 for n in chunks)}
    # every expert is held: each token's k pairs in each of the 3 expert
    # layers are all counted, the first time the two must agree. A request
    # decodes 19 tokens (its 20th is sampled and never fed).
    experts = stats["experts"]
    n_prompt, n_decode = int(lens.sum()), 5 * 19
    assert experts["held"] == [0, 8]
    assert experts["prefill_pairs"] == n_prompt * 2 * 3
    assert experts["decode_pairs"] == n_decode * 2 * 3
    assert (experts["pairs_held"] == experts["pairs_total"]
            == (n_prompt + n_decode) * 2 * 3)


def test_the_device_spans_carry_the_conv_layers_and_the_pairs(model):
    """``tony:engine.decode_device`` and ``.prefill_device`` of a model
    with conv layers carry ``conv_layers`` and keep ``expert_pairs`` (a
    span that fences carries the pairs of the unfenced rounds it brought
    home beside its own, so the spans sum to the pairs counted, which
    ``stats()`` splits by the program that counted them)."""
    from tony_tpu.observability import trace as obs_trace
    from tony_tpu.observability.metrics import MetricsRegistry

    tcfg, fused = program(model)
    engine = ServingEngine(fused, tcfg, slots=3, max_len=128, prefill_chunk=8,
                           prefill_batch=2, registry=MetricsRegistry())
    engine._tracer = obs_trace.Tracer(proc="test-lfm2")
    serve(engine, prompts([30, 12, 21], seed=4), 5)
    spans = [e for e in engine._tracer.to_chrome_events() if e["ph"] == "X"]
    experts = engine.stats()["experts"]
    device = [e["args"] for e in spans if e["name"] in (
        "tony:engine.decode_device", "tony:engine.prefill_device")]
    # every dispatch launches but the last, which reads the last
    # iteration back
    assert len(device) == (engine.stats()["decode_iterations"]
                           + engine.stats()["prefill_rounds"] + 1)
    assert [a["slots"] for a in device if "slots" in a][-1] == 0
    assert all(a["conv_layers"] == 4 for a in device)
    assert sum(a.get("expert_pairs", 0) for a in device) \
        == experts["pairs_held"] \
        == experts["decode_pairs"] + experts["prefill_pairs"]
    assert experts["prefill_pairs"] == (30 + 12 + 21) * 2 * 3
    assert experts["decode_pairs"] == 3 * 4 * 2 * 3


@pytest.mark.parametrize("chunk,batch,rows", [
    (8, None, 4), (256, None, 4), (512, None, 2), (1024, None, 1),
    (512, 4, 4)])
def test_a_round_holds_at_most_1024_tokens_by_default(model, chunk, batch,
                                                      rows):
    """Left to the engine, a prefill round is four rows while it holds at
    most 1,024 tokens (every padding row is computed in full); a caller's
    own ``prefill_batch`` stands."""
    tcfg = model.program_config(TINY, {}, max_seq=2048, dtype="float32")
    _, fused = program(model)
    engine = ServingEngine(fused, tcfg, slots=2, max_len=2048,
                           prefill_chunk=chunk, prefill_batch=batch)
    assert engine.prefill_batch == rows


def test_a_reused_slot_reads_a_zero_state(model, reference):
    """A long tenant and then, in the slot it left, a short one: the
    second prompt's first chunk starts at 0 and reads zeros whatever the
    slot held."""
    tcfg, fused = program(model)
    engine = ServingEngine(fused, tcfg, slots=3, max_len=128,
                           prefill_chunk=8, prefill_batch=2)
    for prompt in prompts([61, 9], seed=3):
        new, = serve(engine, [prompt], 6)
        assert engine._slot_req == [None] * 3 and engine._pos[0] > 0
        row = np.concatenate([prompt, new])
        ref = reference_logits(reference, row)
        at = np.arange(prompt.size - 1, row.size - 1)
        assert (ref[at].argmax(-1) == row[at + 1]).all()
    assert engine.stats()["state"]["slots_reset"] == 2


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "t0.8"])
@pytest.mark.parametrize("window", [1, 2], ids=["window1", "window2"])
def test_tokens_are_those_of_an_engine_drained_after_every_step(
        model, window, temperature):
    """The conv states advance on the device from one launched window to
    the next: every request's tokens are those of the engine that reads
    each iteration back before it launches the next. Greedy requests
    reuse two slots; sampled ones have a slot each."""
    from test_unfenced_rounds import serve_pipelined_and_drained

    tcfg, fused = program(model)
    lens, budgets = (30, 5, 21, 12), (9, 5, 2, 7)
    eng, reqs, ref, ref_reqs = serve_pipelined_and_drained(
        lambda **kw: ServingEngine(fused, tcfg, max_len=128, prefill_chunk=8,
                                   prefill_batch=2,
                                   slots=4 if temperature else 2, **kw),
        window=window, temperature=temperature, prompt_lens=lens,
        budgets=budgets, vocab=TINY["vocab_size"])
    assert [r.tokens for r in reqs] == [r.tokens for r in ref_reqs]
    assert [len(r.tokens) for r in reqs] == list(budgets)
    decode = eng.stats()["dispatch"]["decode"]
    assert 0 < decode["pipelined"] <= decode["calls"]
    assert ref.stats()["dispatch"]["decode"]["pipelined"] == 0
    # the same tokens through the expert layers, in more or fewer
    # dispatches where a slot frees a step later
    for key in ("pairs_per_expert", "pairs_held", "decode_pairs",
                "prefill_pairs", "pairs_total"):
        assert eng.stats()["experts"][key] == ref.stats()["experts"][key]


@pytest.mark.parametrize("window", [1, 2], ids=["window1", "window2"])
def test_a_slot_freed_by_a_late_eos_holds_a_zero_conv_state(model, window):
    """The window launched for a lane that had ended shifted its slot's
    conv rows once more; the next tenant's first chunk reads zeros all the
    same."""
    from test_unfenced_rounds import reused_slot_after_a_late_eos

    tcfg, fused = program(model)
    got, fresh, engine = reused_slot_after_a_late_eos(
        lambda: ServingEngine(fused, tcfg, slots=2, max_len=128,
                              prefill_chunk=8, prefill_batch=2,
                              decode_window=window),
        prompts([10, 27, 19], seed=9))
    assert got == fresh
    assert engine.stats()["state"]["slots_reset"] == 3


def test_a_lane_parked_in_mid_prefill_keeps_its_state(model):
    """Slot 1 decodes while slot 2 is between two prefill rounds and slots
    0 and 3 are free: the decode iteration leaves the parked lanes' conv
    rows bit for bit and moves the decoding lane's."""
    tcfg, fused = program(model)
    a, b = prompts([16, 40], seed=9)
    k, v, _ = prefill(fused, tcfg, [a, b[:16]], 8)
    before = [np.asarray(buf) for buf in k["conv"]]
    assert all(np.abs(s[1:3]).min() > 0 for s in before)
    pos = np.asarray([0, 16, 0, 0], np.int32)
    wpos = np.asarray([127, 16, 127, 127], np.int32)   # all but lane 1 parked
    k, v, _, _ = engine_lib.decode_window(
        fused, k, v, pos, wpos, np.asarray([0, 5, 0, 0], np.int32),
        np.zeros(4, np.float32), jax.random.key(0), np.int32(0), cfg=tcfg,
        steps=2)
    for was, now in zip(before, (np.asarray(buf) for buf in k["conv"])):
        assert (now[[0, 2, 3]] == was[[0, 2, 3]]).all()
        assert (now[1] != was[1]).all()


# -- (b) heads of 64 lie two to a row of the cache -----------------------------
def _caches(width, h_kv=4, layers=2, slots=3, t=64):
    ks = jax.random.split(jax.random.key(width), 2)
    k, v = (jax.random.normal(kk, (layers, slots, t, h_kv, width))
            for kk in ks)
    n = attention_lib.cache_heads_per_row(h_kv, width, width)
    stored = tuple(c.reshape(layers, slots, t, h_kv // n, n * width)
                   for c in (k, v))
    return (k, v), stored, n


@pytest.mark.parametrize("width,rows", [(64, 2), (128, 1)])
@pytest.mark.parametrize("kernel", ["decode", "ring", "prefill"])
def test_a_paired_cache_reads_as_the_unpaired_plain_path(width, rows, kernel):
    """The decode kernel (a full cache, a ring) and the prefill kernel in
    interpret mode over the cache as the engine stores it (64: two KV heads
    a row, each query head against its own half; 128: as ever) against the
    plain path over [.., Hkv, D]."""
    (k, v), (ks, vs), n = _caches(width)
    assert n == rows and ks.shape[-1] == 128
    layer, q_heads = jnp.int32(1), 8
    if kernel == "prefill":
        q = jax.random.normal(jax.random.key(7), (2, 8, q_heads, width))
        slots, ends = jnp.asarray([2, 0]), jnp.asarray([24, 8])
        want = attention_lib.cache_prefill_attention(
            q, k, v, layer, slots, ends, mode="jax")
        got = attention_lib.cache_prefill_attention(
            q, ks, vs, layer, slots, ends, mode="interpret", block_rows=32)
        plain = attention_lib.cache_prefill_attention(
            q, ks, vs, layer, slots, ends, mode="jax")
    else:
        q = jax.random.normal(jax.random.key(7), (3, q_heads, width))
        pos = jnp.asarray([5, 70 if kernel == "ring" else 63, 40])
        how = dict(window=16) if kernel == "ring" else {}
        want = attention_lib.cache_decode_attention(
            q, k, v, layer, pos, mode="jax", **how)
        got = attention_lib.cache_decode_attention(
            q, ks, vs, layer, pos, mode="interpret", block_rows=32, **how)
        plain = attention_lib.cache_decode_attention(
            q, ks, vs, layer, pos, mode="jax", **how)
    assert got.shape == want.shape == plain.shape
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    assert np.abs(np.asarray(plain) - np.asarray(want)).max() < 1e-5


def test_a_model_of_64_wide_heads_is_served_from_paired_rows():
    """A uniform model at head width 64 through the engine: its cache is
    [L, S, Tmax, Hkv / 2, 128], its tokens are ``generate``'s, and a slot's
    rows leave and enter at their logical width."""
    cfg = TransformerConfig(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
                            head_dim=64, n_kv_heads=2, d_ff=96, max_seq=64,
                            dtype="float32", remat=False)
    params = init_params(jax.random.key(0), cfg)
    engine = ServingEngine(params, cfg, slots=2, max_len=64, prefill_chunk=8)
    assert engine._k.shape == engine._v.shape == (2, 2, 64, 1, 128)
    rows = prompts([19, 11], seed=1)
    rows = [r % 64 for r in rows]
    for prompt, new in zip(rows, serve(engine, rows, 8)):
        want = generate(params, jnp.asarray(prompt)[None], cfg, 8)
        assert (np.asarray(want)[0] == new).all()
    out = engine_lib.cache_export_rows(engine._k, 0, 19, cfg.head_dim)
    assert out.shape == (2, 19, 2, 64)
    back = engine_lib.cache_inject_rows(engine._k, 1, out)
    assert (np.asarray(back[:, 1, :19]) == np.asarray(
        engine._k[:, 0, :19])).all()
