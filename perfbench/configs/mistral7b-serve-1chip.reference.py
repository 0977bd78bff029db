"""Plain reference for ``mistral7b-serve-1chip``: Mistral-7B's forward pass
in straightforward ``jax.numpy`` and float32 at ``highest`` matmul
precision — no kernels, no cache, no batching of requests. It follows the
published description (mistral-src ``model.py``: pre-norm RMSNorm, rotary
embedding over interleaved pairs, grouped-query attention, SwiGLU, untied
head). Departures, each forced by the program and each without effect
here: the program's RMSNorm uses eps 1e-6 where the config publishes 1e-5
(the reference keeps 1e-5; on unit-variance activations the two differ by
5e-6 relative); the program has no sliding window, and every sequence here
is at most 2,048 positions, inside the published 4,096 window, so full
causal attention is the same function.

It imports nothing of the program and takes nothing the program made: the
weights come again from the seed, one layer at a time (the served bfloat16
values, upcast), so a 16-layer model never exists in float32."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from yardstick import spec, weights
from yardstick.precision import OPERAND


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, theta):
    """x [B, T, H, Dh]; rotate interleaved pairs by position."""
    _, t, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def decoder_layer(x, p, cfg, op):
    """x [B, T, d] float32; ``op`` rounds each matmul operand (identity
    for the reference itself, fp8 for its control)."""
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    t = x.shape[1]
    a = rms_norm(x, p["input_norm"], eps)
    q = jnp.einsum("btd,dhk->bthk", op(a), op(p["q_proj"]))
    k = jnp.einsum("btd,dhk->bthk", op(a), op(p["k_proj"]))
    v = jnp.einsum("btd,dhk->bthk", op(a), op(p["v_proj"]))
    q, k = rope(q, theta), rope(k, theta)
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", op(q), op(k)) * dh ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal[None, None], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqs,bshk->bqhk", op(pr), op(v))
    x = x + jnp.einsum("bthk,hkd->btd", op(o), op(p["o_proj"]))
    m = rms_norm(x, p["post_norm"], eps)
    g = jnp.einsum("btd,df->btf", op(m), op(p["gate_proj"]))
    u = jnp.einsum("btd,df->btf", op(m), op(p["up_proj"]))
    return x + jnp.einsum("btf,fd->btd", op(jax.nn.silu(g) * u),
                          op(p["down_proj"]))


@functools.partial(jax.jit, static_argnames=("cfg_key", "dtype", "lowp"))
def _layer_step(x, key, layer, cfg_key, dtype, lowp):
    cfg = dict(cfg_key)
    p = jax.tree.map(lambda w: w.astype(jnp.float32), weights.layer_tree(
        key, leaf_table(cfg), layer, jnp.dtype(dtype), like=0))
    return decoder_layer(x, p, cfg, OPERAND[lowp])


@functools.partial(jax.jit, static_argnames=("cfg_key", "dtype"))
def _embed(tokens, key, cfg_key, dtype):
    cfg = dict(cfg_key)
    e = weights.leaf(key, leaf_table(cfg), "embed", 0, jnp.dtype(dtype))
    return e[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg_key", "dtype", "lowp"))
def _head(x, key, cfg_key, dtype, lowp):
    cfg = dict(cfg_key)
    op = OPERAND[lowp]
    top = weights.top_tree(key, leaf_table(cfg), jnp.dtype(dtype))
    x = rms_norm(x, top["final_norm"].astype(jnp.float32),
                 cfg["rms_norm_eps"])
    return jnp.einsum("btd,dv->btv", op(x),
                      op(top["lm_head"].astype(jnp.float32)))


def model_key(cfg: dict) -> tuple:
    """The sizes the forward pass needs, hashable for jit."""
    names = ("model", "hidden_size", "intermediate_size",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "vocab_size", "num_hidden_layers", "rms_norm_eps", "rope_theta")
    return tuple((n, cfg[n]) for n in names)


def leaf_table(cfg: dict) -> dict:
    """The leaves as the configuration's model module states them."""
    return spec.load_model(cfg["model"]).leaf_table(cfg)


def logits(cfg: dict, seed: int, tokens, *, dtype: str = "bfloat16",
           lowp: str = "float32"):
    """tokens [B, T] int32 -> logits [B, T, V] float32, layer by layer."""
    ck = model_key(cfg)
    key = weights.seed_key(seed)
    with jax.default_matmul_precision("highest"):
        x = _embed(tokens, key, ck, dtype)
        for layer in range(cfg["num_hidden_layers"]):
            x = _layer_step(x, key, jnp.int32(layer), ck, dtype, lowp)
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
                      dtype="bfloat16", block: int = 4, lowp_control=None):
    """Run the reference once over each prompt with its served tokens (rows
    of ``tokens``, padded to one length; causal, so padding changes nothing
    before it) in blocks of ``block`` rows. Returns the widest and the mean
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
