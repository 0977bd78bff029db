"""A model of lightning (linear-attention) layers with a per-slot recurrent
state beside block-sparse attention layers that select their keys, served:
the serving engine against the benchmark's PLAIN reference
(``perfbench/configs/minicpm-sala-serve-1chip.reference.py``: float32, the
recurrence position by position, the selection from its definitions, no
cache, the weights again from the seed) at toy widths that keep every
mechanism: 4 layers ``sparse, linear, linear, linear``, ``dense_len`` 32,
blocks of 8, kernels of 4 every 2, top-2, a window of 16."""

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

from tony_tpu.models import TransformerConfig, decode_weights  # noqa: E402
from tony_tpu.ops import hybrid  # noqa: E402
from tony_tpu.serving import ServingEngine  # noqa: E402
from tony_tpu.serving import engine as engine_lib  # noqa: E402
from tony_tpu.serving.scheduler import _chunk_plan  # noqa: E402

SEED = 2 ** 31 + 77
SPARSE = {"kernel_size": 4, "kernel_stride": 2, "block_size": 8, "topk": 2,
          "init_blocks": 1, "window_size": 16, "dense_len": 32}
TINY = {
    "model": "minicpm_sala", "attention_bias": False, "attn_use_rope": False,
    "head_dim": 16, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 96, "lightning_head_dim": 16, "lightning_nh": 4,
    "lightning_nkv": 4, "lightning_scale": "1/sqrt(d)",
    "lightning_use_rope": True,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                    "lightning-attn"],
    "num_attention_heads": 4, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "qk_norm": True, "rms_norm_eps": 1e-6,
    "vocab_size": 96, "rope_theta": 10000, "scale_emb": 12,
    "scale_depth": 1.4, "dim_model_base": 16, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True, "published": {"num_hidden_layers": 8},
    "sparse_config": SPARSE,
    "seeded_weights": {"sparse_k_norm_std": 4.0, "sparse_v_gain": 4.0},
}
SIZES = dict(topk=2, init=1, window=16, block=8, dense_len=32)


@pytest.fixture(scope="module")
def model():
    return spec.load_model("minicpm_sala")


@pytest.fixture(scope="module")
def reference():
    return spec.load_module(
        PERFBENCH / "configs" / "minicpm-sala-serve-1chip.reference.py",
        "sala_reference")


def program(model, max_seq=128):
    tcfg = model.program_config(TINY, {}, max_seq=max_seq, dtype="float32")
    params = model.program_params(weights.seed_key(SEED), TINY, jnp.float32)
    return tcfg, decode_weights(params, tcfg)


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TINY["vocab_size"], size=n).astype(np.int32)
            for n in lengths]


def reference_logits(reference, row):
    return np.asarray(reference.logits(
        TINY, SEED, jnp.asarray(row)[None], dtype="float32"))[0]


def serve(engine, rows, new):
    reqs = [engine.submit(r, new) for r in rows]
    while not all(r.done() for r in reqs):
        engine.step()
    return [np.asarray(r.result()["tokens"], np.int32) for r in reqs]


def test_the_program_takes_the_configuration(model):
    tcfg, fused = program(model)
    assert tcfg.layered and tcfg.layer_groups == {
        "sparse_dense": (0,), "linear_dense": (1, 2, 3)}
    assert tcfg.kv_heads_of("sparse") == 2 and tcfg.kv_heads_of("linear") == 4
    assert abs(tcfg.residual_scale - 1.4 / 8 ** 0.5) < 1e-12
    # q|k|v|gate fused on the feature axis: 64 + 32 + 32 + 64, 4 x 64
    assert fused["layers"][0]["qkv"].shape == (64, 192)
    assert fused["layers"][1]["qkv"].shape == (64, 256)
    assert "o_norm" in fused["layers"][1] and "o_norm" not in fused["layers"][0]
    k, v = engine_lib.init_slot_cache(tcfg, 3, 128, prefill_chunk=16)
    assert [b.shape for b in k["sparse"]] == [(3, 2, 128, 16)]
    assert [b.shape for b in v["sparse"]] == [(3, 2, 128, 16)]
    assert [b.shape for b in k["sparse_kc"]] == [(3, 2, 65, 16)]
    assert k["sparse_kc"][0].dtype == jnp.float32
    assert [b.shape for b in k["linear"]] == [(4, 4, 16, 16)] * 3
    assert k["linear"][0].dtype == jnp.float32 and "linear" not in v
    with pytest.raises(ValueError, match="whole blocks"):
        engine_lib.init_slot_cache(tcfg, 3, 128, prefill_chunk=12)


@pytest.mark.parametrize("what", ["forward", "train", "generate"])
def test_linear_and_sparse_layers_are_served_not_trained(model, what):
    from tony_tpu.models import forward, generate, param_roles

    tcfg, fused = program(model)
    with pytest.raises(ValueError, match="served, not trained"):
        if what == "forward":
            forward(fused, jnp.zeros((1, 8), jnp.int32), tcfg)
        elif what == "train":
            param_roles(tcfg)
        else:
            generate(fused, jnp.zeros((1, 8), jnp.int32), tcfg, 4)


@pytest.mark.parametrize("bad", [
    {"attn_kinds": ("sparse", "latent")},
    {"attn_kinds": ("sparse", "linear"), "sparse_kernel": 5},
    {"attn_kinds": ("sparse", "linear"), "sparse_window": 20},
])
def test_a_configuration_the_program_cannot_run_is_refused(bad):
    with pytest.raises(ValueError):
        TransformerConfig(**{
            "n_layers": 2, "sparse_stride": 2, "sparse_kernel": 4,
            "sparse_block": 8, "sparse_window": 16, "sparse_dense_len": 32,
            **bad})


# -- (a) the system against the plain reference --------------------------------
@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_prefill_rounds_give_the_references_logits(model, reference, chunk):
    """Prompts of mixed lengths through ``prefill_chunks`` in rounds of two
    rows (a short round padded with a duplicate of row 0, a prompt's last
    chunk padded, as the host does both): every chunk's last-position
    logits against the reference's full forward pass."""
    tcfg, fused = program(model)
    rows = prompts([70, 45, 23])
    k, v = engine_lib.init_slot_cache(tcfg, 4, 128, prefill_chunk=chunk)
    refs = [reference_logits(reference, r) for r in rows]
    plans = [_chunk_plan(r.size, chunk, aligned=True) for r in rows]
    key = jax.random.key(0)
    worst = 0.0
    for step in range(max(len(p) for p in plans)):
        live = [i for i, p in enumerate(plans) if step < len(p)]
        for lo in range(0, len(live), 2):
            batch = live[lo:lo + 2]
            pad = batch + [batch[0]] * (2 - len(batch))
            toks = np.zeros((2, chunk), np.int32)
            starts, valid = np.zeros(2, np.int32), np.zeros(2, np.int32)
            for j, i in enumerate(pad):
                starts[j], valid[j] = plans[i][step]
                toks[j, :valid[j]] = rows[i][starts[j]:starts[j] + valid[j]]
            slots = np.asarray([i + 1 for i in pad], np.int32)
            k, v, _, logits, _ = engine_lib.prefill_chunks(
                fused, k, v, toks, slots, starts, valid,
                np.zeros(2, np.float32), key, np.int32(0), cfg=tcfg)
            for j, i in enumerate(batch):
                at = starts[j] + valid[j] - 1
                worst = max(worst, float(np.abs(
                    np.asarray(logits[j]) - refs[i][at]).max()))
    assert worst < 2e-4


@pytest.mark.parametrize("chunk,batch", [(8, 1), (16, 2), (32, 4)])
def test_served_tokens_are_the_references_first_choice(model, reference,
                                                        chunk, batch):
    """Prefill in uneven rounds and then decoding through the caches, more
    requests than slots: every served token is the one the reference's
    full forward pass over the served row puts first, and the reference's
    logits leave no gap to it."""
    tcfg, fused = program(model)
    rows = prompts([70, 45, 23, 90, 37], seed=chunk)
    engine = ServingEngine(fused, tcfg, slots=3, max_len=128,
                           prefill_chunk=chunk, prefill_batch=batch)
    for prompt, served in zip(rows, serve(engine, rows, 20)):
        row = np.concatenate([prompt, served])
        ref = reference_logits(reference, row)
        at = np.arange(prompt.size - 1, row.size - 1)
        assert (ref[at].argmax(-1) == row[at + 1]).all()
    stats = engine.stats()
    assert stats["state"]["slots_reset"] == 5
    # the device's count of the keys its selection listed is the
    # definition's (the benchmark's own formula), per KV group: a request
    # decodes at positions len(prompt) .. len(prompt) + 18
    at = [p for r in rows for p in range(r.size, r.size + 19)]
    assert stats["sparse"] == {
        "keys_read": 2 * sum(int(model.selected_keys(TINY, p)) for p in at),
        "keys_live": 2 * sum(p + 1 for p in at)}


@pytest.mark.parametrize("control", ["dense", "no_decay"])
def test_a_wrong_mechanism_is_told_apart_by_the_logits(model, reference,
                                                       control):
    """A prompt long enough that the selection leaves most blocks out (48
    blocks: the first, the window's 3 and the top 2 are read): the system's
    logits at the prompt's end are the reference's, and the reference
    WITHOUT the selection (every key read), or without the decay, is
    further from it by orders of magnitude — with the seeded keys peaked
    (``seeded_weights``) the comparison sees which blocks were read."""
    tcfg, fused = program(model, max_seq=512)
    row = prompts([384], seed=21)[0]
    k, v = engine_lib.init_slot_cache(tcfg, 1, 512, prefill_chunk=32)
    for start in range(0, row.size, 32):
        k, v, _, logits, _ = engine_lib.prefill_chunks(
            fused, k, v, row[None, start:start + 32],
            np.zeros(1, np.int32), np.asarray([start], np.int32),
            np.asarray([32], np.int32), np.zeros(1, np.float32),
            jax.random.key(0), np.int32(0), cfg=tcfg)
    want = reference_logits(reference, row)[-1]
    wrong = np.asarray(reference.logits(
        TINY, SEED, jnp.asarray(row)[None], dtype="float32",
        lowp=control))[0, -1]
    ours = np.abs(np.asarray(logits[0]) - want).max()
    assert ours < 2e-4 and np.abs(wrong - want).max() > 50 * ours


# -- (b) chunked lightning attention is the recurrence --------------------------
def recurrence(q, k, v, slopes):
    """S_t = lam S_{t-1} + k_t^T v_t, o_t = q_t S_t, position by position."""
    t, h, d = q.shape
    state, out = np.zeros((h, d, d)), []
    lam = np.exp(-np.asarray(slopes, np.float64))[:, None, None]
    for i in range(t):
        state = lam * state + k[i][:, :, None] * v[i][:, None, :]
        out.append(np.einsum("hd,hde->he", q[i], state))
    return np.stack(out), state


@pytest.mark.parametrize("mode", ["jax", "interpret"])
@pytest.mark.parametrize("chunk", [8, 16, 12, 64])
def test_chunked_lightning_attention_is_the_recurrence(chunk, mode):
    """A prompt of 40 positions in chunks that do (8) and do not (16, 12)
    divide it, and in one padded chunk (64): the state carried from chunk
    to chunk, the last one padded."""
    t, h, d = 40, 4, 16
    ks = jax.random.split(jax.random.key(3), 3)
    q, k, v = (np.asarray(jax.random.normal(kk, (t, h, d))) for kk in ks)
    slopes = hybrid.linear_decay_slopes(h)
    want_o, want_s = recurrence(q.astype(np.float64), k.astype(np.float64),
                                v.astype(np.float64), slopes)
    state = jnp.zeros((1, h, d, d))
    got = []
    for start in range(0, t, chunk):
        n = min(chunk, t - start)
        part = [jnp.zeros((1, chunk, h, d)).at[0, :n].set(x[start:start + n])
                for x in (q, k, v)]
        o, state = hybrid.lightning_prefill(
            *part, state, slopes, jnp.asarray([n]), mode=mode)
        got.append(np.asarray(o[0, :n]))
    assert np.abs(np.concatenate(got) - want_o).max() < 1e-4
    assert np.abs(np.asarray(state[0]) - want_s).max() < 1e-4


@pytest.mark.parametrize("mode", ["jax", "interpret"])
def test_a_decode_step_is_one_step_of_the_recurrence(mode):
    s, h, d = 5, 4, 16
    ks = jax.random.split(jax.random.key(4), 4)
    q, k, v = (jax.random.normal(kk, (s, h, d)) for kk in ks[:3])
    state = jax.random.normal(ks[3], (s + 1, h, d, d))
    slopes = hybrid.linear_decay_slopes(h)
    lane = jnp.asarray([0, 5, 2, 5, 4])      # lanes 1 and 3 are parked
    o, new = hybrid.lightning_decode(q, k, v, state, lane, slopes, mode=mode)
    lam = np.exp(-np.asarray(slopes))[:, None, None]
    for i in (0, 2, 4):
        want = lam * np.asarray(state[i]) + (
            np.asarray(k[i])[:, :, None] * np.asarray(v[i])[:, None, :])
        assert np.abs(np.asarray(new[i]) - want).max() < 1e-5
        assert np.abs(np.asarray(o[i]) - np.einsum(
            "hd,hde->he", np.asarray(q[i]), want)).max() < 1e-4
    for i in (1, 3):                          # bit for bit as they were
        assert (np.asarray(new[i]) == np.asarray(state[i])).all()


# -- (c) the selection ---------------------------------------------------------
def blocks_by_definition(q, kc_rows, pos, h_kv):
    """The blocks one query reads, from the definitions in plain numpy:
    q [H, D]; kc_rows [Hkv, R, D]; -> a set of blocks per KV group."""
    sp, d = SPARSE, q.shape[-1]
    n_kernels = (pos + 1 - sp["kernel_size"]) // sp["kernel_stride"] + 1
    group = q.shape[0] // h_kv
    first = max(pos - (sp["window_size"] - 1), 0) // sp["block_size"]
    last = pos // sp["block_size"]
    out = []
    for g in range(h_kv):
        z = q[g * group:(g + 1) * group] @ kc_rows[g, :n_kernels].T / d ** 0.5
        p = np.exp(z - z.max(-1, keepdims=True))
        share = (p / p.sum(-1, keepdims=True)).sum(0)
        score = {}
        for b in range(sp["init_blocks"], first):
            over = [j for j in range(n_kernels)
                    if sp["kernel_stride"] * j < sp["block_size"] * (b + 1)
                    and sp["kernel_stride"] * j + sp["kernel_size"]
                    > sp["block_size"] * b]
            score[b] = max(share[j] for j in over)
        top = sorted(score, key=score.get, reverse=True)[:sp["topk"]]
        out.append(set(range(sp["init_blocks"])) | set(range(first, last + 1))
                   | set(top))
    return out


@pytest.mark.parametrize("pos", [32, 47, 63, 88, 127])
def test_the_selection_picks_the_definitions_blocks(pos):
    """Random q and K^c (no near ties): the decode list and a prefill
    token's mask both name the blocks the definition gives; block 0 and
    the window's blocks are always among them."""
    h_kv, group, d, t = 2, 2, 16, 128
    ks = jax.random.split(jax.random.key(pos), 2)
    q = jax.random.normal(ks[0], (h_kv * group, d)) * 3.0
    kc = jax.random.normal(ks[1], (h_kv, t // 2 + 1, d))
    want = blocks_by_definition(np.asarray(q, np.float64),
                                np.asarray(kc, np.float64), pos, h_kv)
    at = jnp.asarray([[pos]])
    scores = hybrid.block_scores(
        q.reshape(1, 1, h_kv, group, d), kc[None], at, scale=d ** -0.5,
        kernel=4, stride=2, block=8)
    idx, ok = hybrid.select_block_list(scores[:, :, 0], at[0], **SIZES)
    mask = hybrid.select_blocks(scores, at, **SIZES)
    for g in range(h_kv):
        listed = set(np.asarray(idx[0, g])[np.asarray(ok[0, g])].tolist())
        assert listed == want[g]
        seen = set(np.flatnonzero(np.asarray(mask[0, g, 0])).tolist())
        assert {b for b in seen if b <= pos // 8} == want[g]
        assert 0 in listed and set(range((pos - 15) // 8, pos // 8 + 1)) \
            <= listed


# -- the kernels against the plain path ----------------------------------------
@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_sparse_attention_kernels_match_the_plain_path(kernel):
    s, h_kv, t, d, group = 4, 2, 128, 16, 2
    ks = jax.random.split(jax.random.key(9), 5)
    kc, vc = (jax.random.normal(kk, (s, h_kv, t, d)) for kk in ks[:2])
    comp = jax.random.normal(ks[2], (s, h_kv, t // 2 + 1, d))
    size = dict(scale=0.25, kernel=4, stride=2, block=8)
    if kernel == "decode":
        q = jax.random.normal(ks[3], (s, h_kv * group, d))
        pos = jnp.asarray([100, 20, 127, 64])
        scores = hybrid.block_scores(q.reshape(s, 1, h_kv, group, d), comp,
                                     pos[:, None], **size)[:, :, 0]
        idx, ok = hybrid.select_block_list(scores, pos, **SIZES)
        got, want = (hybrid.sparse_decode_attention(
            q, kc, vc, idx, ok, pos, scale=0.25, block=8, mode=m)
            for m in ("interpret", "jax"))
    else:
        p, c = 3, 16
        q = jax.random.normal(ks[3], (p, c, h_kv * group, d))
        slots, starts = jnp.asarray([2, 0, 2]), jnp.asarray([64, 16, 64])
        qpos = starts[:, None] + jnp.arange(c)[None, :]
        scores = hybrid.block_scores(q.reshape(p, c, h_kv, group, d),
                                     comp[slots], qpos, **size)
        sel = hybrid.select_blocks(scores, qpos, **SIZES)
        got, want = (hybrid.sparse_prefill_attention(
            q, kc, vc, sel, slots, starts + c, scale=0.25, block=8, mode=m)
            for m in ("interpret", "jax"))
    assert float(jnp.abs(got - want).max()) < 1e-5


# -- (f) below dense_len a sparse layer is dense causal attention --------------
@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_below_dense_len_the_sparse_layer_attends_every_key(kernel):
    s, h_kv, t, d, group, c = 2, 2, 128, 16, 2, 16
    ks = jax.random.split(jax.random.key(11), 4)
    kc, vc = (jax.random.normal(kk, (s, h_kv, t, d)) for kk in ks[:2])
    comp = jax.random.normal(ks[2], (s, h_kv, t // 2 + 1, d))
    size = dict(scale=0.25, kernel=4, stride=2, block=8)

    def dense(q, slot, pos):                  # q [H, D] at position pos
        z = np.einsum("ghd,gtd->ght", np.asarray(q).reshape(h_kv, group, d),
                      np.asarray(kc[slot])[:, :pos + 1]) * 0.25
        p = np.exp(z - z.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        return np.einsum("ght,gtd->ghd", p,
                         np.asarray(vc[slot])[:, :pos + 1]).reshape(-1, d)

    if kernel == "decode":
        q = jax.random.normal(ks[3], (s, h_kv * group, d))
        pos = jnp.asarray([31, 9])
        scores = hybrid.block_scores(q.reshape(s, 1, h_kv, group, d), comp,
                                     pos[:, None], **size)[:, :, 0]
        idx, ok = hybrid.select_block_list(scores, pos, **SIZES)
        got = hybrid.sparse_decode_attention(q, kc, vc, idx, ok, pos,
                                             scale=0.25, block=8)
        for i in range(s):
            assert np.abs(np.asarray(got[i])
                          - dense(q[i], i, int(pos[i]))).max() < 1e-5
    else:
        q = jax.random.normal(ks[3], (1, c, h_kv * group, d))
        starts, slots = jnp.asarray([16]), jnp.asarray([1])
        qpos = starts[:, None] + jnp.arange(c)[None, :]
        scores = hybrid.block_scores(q.reshape(1, c, h_kv, group, d),
                                     comp[slots], qpos, **size)
        sel = hybrid.select_blocks(scores, qpos, **SIZES)
        assert bool(sel.all())
        got = hybrid.sparse_prefill_attention(q, kc, vc, sel, slots,
                                              starts + c, scale=0.25, block=8)
        for i in range(c):
            assert np.abs(np.asarray(got[0, i])
                          - dense(q[0, i], 1, 16 + i)).max() < 1e-5


# -- (d) a reused slot reads nothing of its last tenant ------------------------
@pytest.mark.parametrize("lengths", [(90, 37), (70, 70), (45, 88)])
def test_a_reused_slot_reads_nothing_of_its_last_tenant(model, lengths):
    """One slot, two tenants one after the other: the second request's
    tokens are those of an engine whose slot never held another state or
    K^c, whether it is shorter, as long or longer than the first."""
    tcfg, fused = program(model)
    first, second = prompts(lengths, seed=5)
    engine = ServingEngine(fused, tcfg, slots=1, max_len=128,
                           prefill_chunk=16)
    serve(engine, [first], 24)
    after = serve(engine, [second], 24)[0]
    fresh = serve(ServingEngine(fused, tcfg, slots=1, max_len=128,
                                prefill_chunk=16), [second], 24)[0]
    assert (after == fresh).all()


# -- (d') with decode iterations pipelined one deep ----------------------------
@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "t0.8"])
@pytest.mark.parametrize("window", [1, 2], ids=["window1", "window2"])
def test_tokens_are_those_of_an_engine_drained_after_every_step(
        model, window, temperature):
    """The state and the selection's K^c advance on the device from one
    launched window to the next: every request's tokens are those of the
    engine that reads each iteration back before it launches the next.
    Greedy requests reuse two slots; sampled ones have a slot each."""
    from test_unfenced_rounds import serve_pipelined_and_drained

    tcfg, fused = program(model)
    lens, budgets = (40, 9, 70, 33), (9, 5, 2, 7)
    eng, reqs, ref, ref_reqs = serve_pipelined_and_drained(
        lambda **kw: ServingEngine(fused, tcfg, max_len=128, prefill_chunk=16,
                                   slots=4 if temperature else 2, **kw),
        window=window, temperature=temperature, prompt_lens=lens,
        budgets=budgets, vocab=TINY["vocab_size"])
    assert [r.tokens for r in reqs] == [r.tokens for r in ref_reqs]
    assert [len(r.tokens) for r in reqs] == list(budgets)
    decode = eng.stats()["dispatch"]["decode"]
    assert 0 < decode["pipelined"] <= decode["calls"]
    assert ref.stats()["dispatch"]["decode"]["pipelined"] == 0
    # the device's own count of the keys its selection listed, against
    # the positions the lanes were LAUNCHED at
    assert eng.stats()["sparse"] == ref.stats()["sparse"]


@pytest.mark.parametrize("window", [1, 2], ids=["window1", "window2"])
def test_a_slot_freed_by_a_late_eos_holds_a_zero_state(model, window):
    """The window launched for a lane that had ended moved its slot's
    state and K^c once more; the next tenant's first chunk reads zeros
    all the same."""
    from test_unfenced_rounds import reused_slot_after_a_late_eos

    tcfg, fused = program(model)
    got, fresh, engine = reused_slot_after_a_late_eos(
        lambda: ServingEngine(fused, tcfg, slots=2, max_len=128,
                              prefill_chunk=16, decode_window=window),
        prompts([20, 50, 37], seed=9))
    assert got == fresh
    assert engine.stats()["state"]["slots_reset"] == 3


# -- (e) a decode dispatch leaves a slot in mid-prefill as it is ---------------
@pytest.mark.parametrize("steps", [1, 3])
def test_decode_leaves_a_slot_in_mid_prefill_bit_for_bit(model, steps):
    """Slot 1 has prefilled two chunks of its prompt; slot 0 decodes. The
    decode dispatch runs ALL slots: slot 1's lane (and the free slot 2's)
    is parked, and its state, its K^c rows and its K/V rows before the
    parking row are afterwards what they were, bit for bit."""
    tcfg, fused = program(model)
    chunk, t_max = 16, 128
    k, v = engine_lib.init_slot_cache(tcfg, 3, t_max, prefill_chunk=chunk)
    key = jax.random.key(0)
    a, b = prompts([16, 48], seed=7)
    for start in (0, 16):
        toks = np.stack([a if start == 0 else b[start:start + 16],
                         b[start:start + 16]])
        slots = np.asarray([0, 1] if start == 0 else [1, 1], np.int32)
        k, v, *_ = engine_lib.prefill_chunks(
            fused, k, v, toks, slots, np.asarray([start] * 2, np.int32)
            if start else np.asarray([0, 0], np.int32),
            np.asarray([16, 16], np.int32), np.zeros(2, np.float32), key,
            np.int32(0), cfg=tcfg)
    before = jax.tree.map(np.asarray, (k, v))
    pos = np.asarray([16, 0, 0], np.int32)
    wpos = np.asarray([16, t_max - 1, t_max - 1], np.int32)
    k, v, toks, _ = engine_lib.decode_window(
        fused, k, v, pos, wpos, np.asarray([3, 0, 0], np.int32),
        np.zeros(3, np.float32), key, np.int32(0), cfg=tcfg, steps=steps)
    after = jax.tree.map(np.asarray, (k, v))
    for layer in range(3):
        was, now = before[0]["linear"][layer], after[0]["linear"][layer]
        assert (now[1] == was[1]).all() and (now[2] == was[2]).all()
        assert not (now[0] == was[0]).all()       # slot 0 did move
    assert (after[0]["sparse_kc"][0][1:, :, :-1]
            == before[0]["sparse_kc"][0][1:, :, :-1]).all()
    for side in (0, 1):
        assert (after[side]["sparse"][0][1:, :, :-1]
                == before[side]["sparse"][0][1:, :, :-1]).all()


# -- the row exchange of disaggregation refuses such a model -------------------
@pytest.mark.parametrize("how", ["prefill_only", "submit_with_kv",
                                 "inject_rows", "export_rows"])
def test_the_row_exchange_refuses_a_model_with_state(model, how):
    tcfg, fused = program(model)
    engine = ServingEngine(fused, tcfg, slots=1, max_len=128,
                           prefill_chunk=16)
    with pytest.raises(ValueError, match="linear, sparse or conv"):
        if how == "prefill_only":
            engine.prefill_only(prompts([20])[0], 4)
        elif how == "submit_with_kv":
            engine.submit_with_kv(np.zeros((1, 4, 2, 16)),
                                  np.zeros((1, 4, 2, 16)), 1, 4, 4)
        elif how == "inject_rows":
            engine_lib.cache_inject_rows(engine._k, 0, {})
        else:
            engine_lib.cache_export_rows(engine._k, 0, 4)


@pytest.mark.parametrize("length,chunk,want", [
    (40, 16, [(0, 16), (16, 16), (32, 8)]),
    (32, 16, [(0, 16), (16, 16)]),
    (9, 16, [(0, 9)]),
])
def test_a_state_models_chunks_are_aligned_and_the_last_one_pads(
        length, chunk, want):
    assert _chunk_plan(length, chunk, aligned=True) == want
