"""Plain reference for ``lfm2-8b-a1b-serve-1chip``: LFM2-8B-A1B's forward
pass in straightforward ``jax.numpy`` and float32 at ``highest`` matmul
precision — no kernels, no cache, no conv state, no grouping of tokens by
expert, no batching of requests. The layer, from the published
``config.json`` (x [T, d], layer l; every norm an RMSNorm with a learnt
weight, eps ``norm_eps``):

  h = x + Op_l(norm_op(x));  y = h + FF_l(norm_ff(h)).
  Op_l by ``layer_types[l]``.
  conv, L = ``conv_L_cache``, no bias:
      [B | C | z] = u W_in   (W_in [d, 3d], split in that order);
      g_t = B_t * z_t;
      c_t = sum_{j < L} w[:, j] * g_{t - (L - 1) + j}   (g before 0 is zero);
      Op(u)_t = (C_t * c_t) W_out.
  full_attention: q = u Wq as H heads of D = d / H, k = u Wk and v = u Wv
      as Hkv heads; RMSNorm over each head's D dims of q and of k; rope
      over all D dims; causal softmax(q k^T / sqrt(D)) v, query head g
      reading KV head g // (H / Hkv); times Wo.
  FF_l, l < ``num_dense_layers``: W2(silu(W1 a) * W3 a), width
      ``intermediate_size``. Else ``num_experts`` experts of width
      ``moe_intermediate_size``: s = sigmoid(a Wr) in float32; sel = the
      ``num_experts_per_tok`` largest of s + bias (the bias chooses and
      does not weigh); w_e = s_e / (sum_{sel} s + 1e-6), times
      ``routed_scaling_factor``; FF(a) = sum_{e in sel} w_e E_e(a), E_e a
      SwiGLU.
  After the last layer kept: RMSNorm, logits = x E^T (the head is tied to
  the embedding).

Assumed, since ``config.json`` does not say (the configuration's
``assumed``): D = d / H; the tied head; the rotary PAIRS interleaved
(2i, 2i+1), as the program rotates them — the family's code rotates halves,
which is the same function under a fixed permutation of each head's columns
of Wq, Wk and of the two norms' weights, and seeded weights have no column
order to keep.

It imports nothing of the program and takes nothing the program made: the
weights come again from the seed through the model module's leaf table, one
layer at a time (the served bfloat16 values, upcast), attention runs in
blocks of queries, and the experts one at a time.

``state_reset``, a control for the tests and the builder's readings on the
chip, is the WRONG mechanism in the reference's place: the convolution
reads zeros for g before every multiple of that many positions, what an
engine would compute that dropped the conv state between prefill rounds
(``no_state``: before EVERY position, an engine that kept no state at
all)."""

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


def rope(x, theta):
    """x [B, T, H, D]: rotate interleaved pairs of all D dims by
    position."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def short_conv(x, p, cfg, op, state_reset=0):
    """x [B, T, d] float32 -> x + Conv(norm(x))."""
    taps = cfg["conv_L_cache"]
    t, d = x.shape[1], x.shape[2]
    u = rms_norm(x, p["input_norm"], cfg["norm_eps"])
    flat = jnp.einsum("btd,df->btf", op(u), op(p["conv_in_proj"]))
    b_in, c_in, z = flat[..., :d], flat[..., d:2 * d], flat[..., 2 * d:]
    g = b_in * z
    at = jnp.arange(t)
    conv = jnp.zeros_like(g)
    for j in range(taps):
        back = taps - 1 - j                  # tap j reads g at t - back
        moved = jnp.pad(g, ((0, 0), (back, 0), (0, 0)))[:, :t]
        if state_reset:                      # the control: module docstring
            moved = jnp.where((at % state_reset >= back)[None, :, None],
                              moved, 0.0)
        conv = conv + p["conv_weight"][:, j] * moved
    return x + jnp.einsum("btd,de->bte", op(c_in * conv),
                          op(p["conv_out_proj"]))


def attention(x, p, cfg, op):
    """x [B, T, d] float32 -> x + Attn(norm(x))."""
    m = model_of(cfg).model_dims(cfg)
    h, hkv, dh = m["h"], m["hkv"], m["dh"]
    b, t, _ = x.shape
    u = rms_norm(x, p["input_norm"], cfg["norm_eps"])
    q = jnp.einsum("btd,dhk->bthk", op(u), op(p["q_proj"]))
    k = jnp.einsum("btd,dhk->bthk", op(u), op(p["k_proj"]))
    v = jnp.einsum("btd,dhk->bthk", op(u), op(p["v_proj"]))
    q = rope(rms_norm(q, p["q_norm"], cfg["norm_eps"]), cfg["rope_theta"])
    k = rope(rms_norm(k, p["k_norm"], cfg["norm_eps"]), cfg["rope_theta"])
    pad = -t % QUERY_BLOCK
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, -1, QUERY_BLOCK, hkv, h // hkv, dh).swapaxes(0, 1)
    starts = jnp.arange(qb.shape[0]) * QUERY_BLOCK

    def block(args):
        q_blk, start = args                       # [B, Q, Hkv, G, D]
        i = start + jnp.arange(QUERY_BLOCK)[:, None]
        seen = jnp.arange(t)[None, :] <= i
        s = jnp.einsum("bqhgk,bshk->bhgqs", op(q_blk), op(k)) * dh ** -0.5
        s = jnp.where(seen[None, None, None], s, -1e30)
        return jnp.einsum("bhgqs,bshk->bqhgk", op(jax.nn.softmax(s, axis=-1)),
                          op(v))

    o = jax.lax.map(block, (qb, starts))          # [N, B, Q, Hkv, G, D]
    o = o.swapaxes(0, 1).reshape(b, -1, h, dh)[:, :t]
    return x + jnp.einsum("bthk,hkd->btd", op(o), op(p["o_proj"]))


def dense_mlp(x, p, cfg, op):
    a = rms_norm(x, p["post_norm"], cfg["norm_eps"])
    g = jnp.einsum("btd,df->btf", op(a), op(p["gate_proj"]))
    u = jnp.einsum("btd,df->btf", op(a), op(p["up_proj"]))
    return x + jnp.einsum("btf,fd->btd", op(jax.nn.silu(g) * u),
                          op(p["down_proj"]))


def router_shares(a, p, cfg):
    """[B, T, E]: each token's weight on each expert (0 for one it did
    not choose). Float32 in the control too: only the experts' products
    are rounded there."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.einsum("btd,de->bte", a, p["router"]))
    _, sel = jax.lax.top_k(s + p["router_bias"], k)
    picked = s * jax.nn.one_hot(sel, e, dtype=jnp.float32).sum(2)
    return (picked / (picked.sum(-1, keepdims=True) + 1e-6)
            * cfg["routed_scaling_factor"])


def experts(x, p, cfg, op):
    """One expert at a time over every token, weighed by the token's
    share of it."""
    a = rms_norm(x, p["post_norm"], cfg["norm_eps"])
    share = router_shares(a, p, cfg)

    def one(total, expert):
        gate, up, down, w = expert
        g = jnp.einsum("btd,df->btf", op(a), op(gate))
        u = jnp.einsum("btd,df->btf", op(a), op(up))
        y = jnp.einsum("btf,fd->btd", op(jax.nn.silu(g) * u), op(down))
        return total + w[..., None] * y, None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (p["experts_gate"], p["experts_up"], p["experts_down"],
         jnp.moveaxis(share, -1, 0)))
    return x + total


def decoder_layer(x, p, cfg, kinds, op, state_reset=0):
    attn, mlp = kinds
    x = (short_conv(x, p, cfg, op, state_reset) if attn == "conv"
         else attention(x, p, cfg, op))
    return dense_mlp(x, p, cfg, op) if mlp == "dense" else experts(
        x, p, cfg, op)


# -- the model, one layer at a time -------------------------------------------
def model_of(cfg: dict):
    return spec.load_model(cfg["model"])


def leaf_table(cfg: dict) -> dict:
    """The leaves as the configuration's model module states them."""
    return model_of(cfg).leaf_table(cfg)


SIZE_KEYS = (
    "model", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "num_key_value_heads", "vocab_size",
    "num_hidden_layers", "num_dense_layers", "layer_types", "norm_eps",
    "rope_theta", "conv_L_cache", "conv_bias", "num_experts",
    "num_experts_per_tok", "norm_topk_prob", "use_expert_bias",
    "routed_scaling_factor")


def model_key(cfg: dict) -> str:
    """The sizes the forward pass needs, hashable for jit."""
    return json.dumps({k: cfg[k] for k in SIZE_KEYS if k in cfg},
                      sort_keys=True)


@functools.partial(jax.jit, static_argnames=(
    "cfg_key", "dtype", "lowp", "like", "state_reset"))
def _layer_step(x, key, layer, cfg_key, dtype, lowp, like, state_reset=0):
    """Layer ``layer`` (traced) of the kinds of layer ``like`` (static)."""
    cfg = json.loads(cfg_key)
    p = jax.tree.map(lambda w: w.astype(jnp.float32), weights.layer_tree(
        key, leaf_table(cfg), layer, jnp.dtype(dtype), like=like))
    return decoder_layer(x, p, cfg, model_of(cfg).layer_kinds(cfg)[like],
                         OPERAND[lowp], state_reset)


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
    x = rms_norm(x, top["final_norm"].astype(jnp.float32), cfg["norm_eps"])
    return jnp.einsum("btd,vd->btv", op(x),
                      op(top["embed"].astype(jnp.float32)))


def logits(cfg: dict, seed: int, tokens, *, dtype: str = "bfloat16",
           lowp: str = "float32", state_reset: int = 0):
    """tokens [B, T] int32 -> logits [B, T, V] float32, layer by layer."""
    ck = model_key(cfg)
    key = weights.seed_key(seed)
    kinds = model_of(cfg).layer_kinds(cfg)
    with jax.default_matmul_precision("highest"):
        x = _embed(tokens, key, ck, dtype)
        for layer, kind in enumerate(kinds):
            x = _layer_step(x, key, jnp.int32(layer), ck, dtype, lowp,
                            kinds.index(kind), state_reset)
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


# A control by name: the lower precision of every matmul's operands, or
# the wrong mechanism (module docstring) at the configuration's chunk.
def _control(cfg: dict, name: str) -> dict:
    if name == "state_reset":
        return {"state_reset": int(
            cfg["conf"]["tony.serving.prefill-chunk"])}
    if name == "no_state":       # a state never carried: one tap is left
        return {"state_reset": 1}
    return {"lowp": name}


def served_token_gaps(cfg, seed, tokens, lens_prompt, lens_total, *,
                      dtype="bfloat16", block: int = 1, lowp_control=None):
    """Run the reference once over each prompt with its served tokens (rows
    of ``tokens``, padded to one length; causal, so padding changes nothing
    before it), ``block`` rows at a time. Returns the widest and the mean
    gap, and how many served tokens are the reference's own first choice.
    With ``lowp_control`` the tokens judged are NOT the served ones but the
    ones the control (``fp8``, ``state_reset`` or ``no_state``) puts first
    at each position."""
    widest, total_gap, agree, count = 0.0, 0.0, 0, 0
    for i in range(0, tokens.shape[0], block):
        tk = jnp.asarray(tokens[i:i + block])
        lp = jnp.asarray(lens_prompt[i:i + block])
        lt = jnp.asarray(lens_total[i:i + block])
        ref = logits(cfg, seed, tk, dtype=dtype)
        judged = tk
        if lowp_control:
            low = logits(cfg, seed, tk, dtype=dtype,
                         **_control(cfg, lowp_control))
            # the control's first choice at t, judged as token t + 1
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
