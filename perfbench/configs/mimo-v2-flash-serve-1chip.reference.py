"""Plain reference for ``mimo-v2-flash-serve-1chip``: MiMo-V2-Flash's
forward pass in straightforward ``jax.numpy`` and float32 at ``highest``
matmul precision — no kernels, no cache, no ring, no grouping of tokens by
expert, no batching of requests. The layer, from the published
``config.json`` (x [T, d], layer l; a_l = ``hybrid_layer_pattern[l]``: 0
full, 1 window; m_l = ``moe_layer_freq[l]``):

  h = rmsnorm(x; w_in, eps);  q = h Wq [T, H, Dk];  k = h Wk [T, Hkv, Dk];
  v = attention_value_scale * (h Wv) [T, Hkv, Dv];  Hkv, the rope base by a_l.
  Rotary on the first floor(partial_rotary_factor * Dk) dims of q and k.
  s_ij = q_i k_j / sqrt(Dk) for j <= i (full) or i - window < j <= i (window);
  query head g reads KV head g // (H / Hkv).
  full:   p = softmax_j(s).
  window: p_ij = exp(s_ij - m) / (exp(b_g - m) + sum_j exp(s_ij - m)),
          m = max(b_g, max_j s_ij): the sink b_g takes mass, adds no value.
  x <- x + (sum_j p_ij v_j) Wo.
  h2 = rmsnorm(x; w_post, eps).
  m_l = 0: x <- x + (silu(h2 Wg) * (h2 Wu)) Wd.
  m_l = 1: z = h2 Wr (float32, E wide); sigma = sigmoid(z); sel = the k
          largest of sigma + c (c chooses and does not weigh);
          w_e = sigma_e / sum_{e' in sel} sigma_e';
          x <- x + sum_{e in sel, e HELD} w_e E_e(h2), E_e a SwiGLU.
  After the last layer: rmsnorm, the head over the held vocabulary rows.

ON A SHARE (the configuration's ``deployment``): the experts held are
``deployment.experts_first`` .. + ``n_routed_experts``; the router stays
``published.n_routed_experts`` wide, w is normalised over all k choices,
and what the absent experts would add is left out, here as in the program.

Assumed, since ``config.json`` does not say (the configuration's
``assumed``): the rotary PAIRS are interleaved (2i, 2i+1), as the program
rotates them — the family's code rotates halves (i, i + r/2), which is the
same function under a fixed permutation of each head's rotating columns of
Wq and Wk, and the seeded weights have no column order to keep; the value
scale multiplies V; a window counts the query's own position; the sink is
one more softmax column; ``attention_chunk_size`` is unused. Left out (its
``departures``): the multi-token-prediction layers, trained weights.

It imports nothing of the program and takes nothing the program made: the
weights come again from the seed through the model module's leaf table,
one layer at a time (the served bfloat16 values, upcast), attention runs in
blocks of queries, and the experts one at a time, so that a 6,912-position
row fits beside a layer's float32 weights."""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from yardstick import spec, weights
from yardstick.precision import OPERAND

QUERY_BLOCK = 256


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, theta, rot):
    """x [B, T, H, Dk]: rotate interleaved pairs of the first ``rot`` dims
    by position; the rest pass."""
    t = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    turned = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                       axis=-1).reshape(x.shape[:-1] + (rot,))
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def attention(x, p, cfg, kind, op):
    """x [B, T, d] float32; ``kind``: "full" or "window"."""
    model = model_of(cfg)
    m = model.model_dims(cfg)
    h, hkv, dk = m["h"], m["hkv"][kind], m["dk"]
    names = model.ATTENTION_LEAVES[kind]
    theta = cfg["swa_rope_theta"] if kind == "window" else cfg["rope_theta"]
    b, t, _ = x.shape
    a = rms_norm(x, p["input_norm"], cfg["layernorm_epsilon"])
    q = jnp.einsum("btd,dhk->bthk", op(a), op(p["q_proj"]))
    k = jnp.einsum("btd,dhk->bthk", op(a), op(p[names["wk"]]))
    v = cfg["attention_value_scale"] * jnp.einsum(
        "btd,dhk->bthk", op(a), op(p[names["wv"]]))
    rot = model.rotary_dims(cfg)
    q, k = rope(q, theta, rot), rope(k, theta, rot)
    sink = p[names["sink"]] if "sink" in names else None
    pad = -t % QUERY_BLOCK
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, -1, QUERY_BLOCK, hkv, h // hkv, dk).swapaxes(0, 1)
    starts = jnp.arange(qb.shape[0]) * QUERY_BLOCK

    def block(args):
        q_blk, start = args                       # [B, Q, Hkv, G, Dk]
        i = start + jnp.arange(QUERY_BLOCK)[:, None]
        j = jnp.arange(t)[None, :]
        seen = j <= i
        if kind == "window":
            seen &= j > i - m["window"]
        s = jnp.einsum("bqhgk,bshk->bhgqs", op(q_blk), op(k)) * dk ** -0.5
        s = jnp.where(seen[None, None, None], s, -1e30)
        if sink is None:
            pr = jax.nn.softmax(s, axis=-1)
        else:
            bg = sink.reshape(1, hkv, h // hkv, 1, 1)
            top = jnp.maximum(s.max(-1, keepdims=True), bg)
            e = jnp.exp(s - top)
            pr = e / (jnp.exp(bg - top) + e.sum(-1, keepdims=True))
        return jnp.einsum("bhgqs,bshk->bqhgk", op(pr), op(v))

    o = jax.lax.map(block, (qb, starts))          # [N, B, Q, Hkv, G, Dv]
    o = o.swapaxes(0, 1).reshape(b, -1, h, m["dv"])[:, :t]
    return x + jnp.einsum("bthk,hkd->btd", op(o), op(p["o_proj"]))


def dense_mlp(x, p, cfg, op):
    hid = rms_norm(x, p["post_norm"], cfg["layernorm_epsilon"])
    g = jnp.einsum("btd,df->btf", op(hid), op(p["gate_proj"]))
    u = jnp.einsum("btd,df->btf", op(hid), op(p["up_proj"]))
    return x + jnp.einsum("btf,fd->btd", op(jax.nn.silu(g) * u),
                          op(p["down_proj"]))


def router_shares(hid, p, cfg):
    """[B, T, E]: each token's weight on each of the E experts (0 for one
    it did not choose). Float32 in the control too: only the experts'
    products are rounded there."""
    e, k = spec_dims(cfg)["e"], cfg["num_experts_per_tok"]
    z = jnp.einsum("btd,de->bte", hid, p["router"])
    sigma = jax.nn.sigmoid(z)
    _, sel = jax.lax.top_k(sigma + p["router_bias"], k)
    chosen = jax.nn.one_hot(sel, e, dtype=jnp.float32).sum(2)   # [B, T, E]
    picked = sigma * chosen
    return picked / picked.sum(-1, keepdims=True)


def experts(x, p, cfg, op, first=None):
    """The expert layer's part that the experts in ``p`` give: ``first`` is
    the index of the first of them among all E (the configuration's share
    by default); one expert at a time over every token, weighed by the
    token's share of it."""
    hid = rms_norm(x, p["post_norm"], cfg["layernorm_epsilon"])
    if first is None:
        first = model_of(cfg).experts_held(cfg)[0]
    held = p["experts_gate"].shape[0]
    share = router_shares(hid, p, cfg)[..., first:first + held]

    def one(total, expert):
        gate, up, down, w = expert
        g = jnp.einsum("btd,df->btf", op(hid), op(gate))
        u = jnp.einsum("btd,df->btf", op(hid), op(up))
        y = jnp.einsum("btf,fd->btd", op(jax.nn.silu(g) * u), op(down))
        return total + w[..., None] * y, None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (p["experts_gate"], p["experts_up"], p["experts_down"],
         jnp.moveaxis(share, -1, 0)))
    return x + total


def decoder_layer(x, p, cfg, kinds, op):
    attn, mlp = kinds
    x = attention(x, p, cfg, attn, op)
    return dense_mlp(x, p, cfg, op) if mlp == "dense" else experts(
        x, p, cfg, op)


# -- the model, one layer at a time -------------------------------------------
def model_of(cfg: dict):
    return spec.load_model(cfg["model"])


def spec_dims(cfg: dict) -> dict:
    return model_of(cfg).model_dims(cfg)


def leaf_table(cfg: dict) -> dict:
    """The leaves as the configuration's model module states them."""
    return model_of(cfg).leaf_table(cfg)


SIZE_KEYS = (
    "model", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "num_key_value_heads", "swa_num_key_value_heads",
    "head_dim", "v_head_dim", "vocab_size", "num_hidden_layers",
    "layernorm_epsilon", "rope_theta", "swa_rope_theta",
    "partial_rotary_factor", "attention_value_scale", "sliding_window",
    "hybrid_layer_pattern", "moe_layer_freq", "n_routed_experts",
    "num_experts_per_tok", "add_swa_attention_sink_bias", "published",
    "deployment")


def model_key(cfg: dict) -> str:
    """The sizes the forward pass needs, hashable for jit."""
    return json.dumps({k: cfg[k] for k in SIZE_KEYS if k in cfg},
                      sort_keys=True)


@functools.partial(jax.jit,
                   static_argnames=("cfg_key", "dtype", "lowp", "like"))
def _layer_step(x, key, layer, cfg_key, dtype, lowp, like):
    """Layer ``layer`` (traced) of the kinds of layer ``like`` (static)."""
    cfg = json.loads(cfg_key)
    p = jax.tree.map(lambda w: w.astype(jnp.float32), weights.layer_tree(
        key, leaf_table(cfg), layer, jnp.dtype(dtype), like=like))
    return decoder_layer(x, p, cfg, model_of(cfg).layer_kinds(cfg)[like],
                         OPERAND[lowp])


@functools.partial(jax.jit, static_argnames=("cfg_key", "dtype"))
def _embed(tokens, key, cfg_key, dtype):
    cfg = json.loads(cfg_key)
    e = weights.leaf(key, leaf_table(cfg), "embed", 0, jnp.dtype(dtype))
    return e[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg_key", "dtype", "lowp"))
def _head(x, key, cfg_key, dtype, lowp):
    cfg = json.loads(cfg_key)
    op = OPERAND[lowp]
    top = weights.top_tree(key, leaf_table(cfg), jnp.dtype(dtype))
    x = rms_norm(x, top["final_norm"].astype(jnp.float32),
                 cfg["layernorm_epsilon"])
    return jnp.einsum("btd,dv->btv", op(x),
                      op(top["lm_head"].astype(jnp.float32)))


def logits(cfg: dict, seed: int, tokens, *, dtype: str = "bfloat16",
           lowp: str = "float32"):
    """tokens [B, T] int32 -> logits [B, T, V] float32, layer by layer."""
    ck = model_key(cfg)
    key = weights.seed_key(seed)
    kinds = model_of(cfg).layer_kinds(cfg)
    with jax.default_matmul_precision("highest"):
        x = _embed(tokens, key, ck, dtype)
        for layer, kind in enumerate(kinds):
            x = _layer_step(x, key, jnp.int32(layer), ck, dtype, lowp,
                            kinds.index(kind))
        return _head(x, key, ck, dtype, lowp)


@jax.jit
def _gaps(ref_logits, tokens, lens_prompt, lens_total):
    """For every served token: how far its reference logit lies below the
    reference's best at that position. Position t predicts token t + 1."""
    best = ref_logits.max(-1)[:, :-1]
    picked = jnp.take_along_axis(ref_logits[:, :-1], tokens[:, 1:, None],
                                 axis=-1)[..., 0]
    t = jnp.arange(tokens.shape[1] - 1)[None, :]
    served = (t >= lens_prompt[:, None] - 1) & (t < lens_total[:, None] - 1)
    gap = jnp.where(served, best - picked, 0.0)
    top1 = jnp.where(served, ref_logits[:, :-1].argmax(-1) == tokens[:, 1:],
                     False)
    return gap.max(), gap.sum() / served.sum(), top1.sum(), served.sum()


def served_token_gaps(cfg, seed, tokens, lens_prompt, lens_total, *,
                      dtype="bfloat16", block: int = 1, lowp_control=None):
    """Run the reference once over each prompt with its served tokens (rows
    of ``tokens``, padded to one length; causal, so padding changes nothing
    before it), ``block`` rows at a time. Returns the widest and the mean
    gap, and how many served tokens are the reference's own first choice.
    With ``lowp_control`` the tokens judged are NOT the served ones but the
    ones the lower precision puts first at each position."""
    widest, total_gap, agree, count = 0.0, 0.0, 0, 0
    for i in range(0, tokens.shape[0], block):
        tk = jnp.asarray(tokens[i:i + block])
        lp = jnp.asarray(lens_prompt[i:i + block])
        lt = jnp.asarray(lens_total[i:i + block])
        ref = logits(cfg, seed, tk, dtype=dtype)
        judged = tk
        if lowp_control:
            low = logits(cfg, seed, tk, dtype=dtype, lowp=lowp_control)
            # the lower precision's first choice at t, judged as token t+1
            judged = jnp.concatenate(
                [tk[:, :1], low.argmax(-1)[:, :-1].astype(tk.dtype)], axis=1)
            del low
        w, mean, top1, n = _gaps(ref, judged, lp, lt)
        del ref
        widest = max(widest, float(w))
        total_gap += float(mean) * int(n)
        agree += int(top1)
        count += int(n)
    return {"widest_gap": widest, "mean_gap": total_gap / max(count, 1),
            "top1_agree": agree, "tokens": count}
