"""Plain reference for ``minicpm-sala-serve-1chip``: MiniCPM-SALA's forward
pass in straightforward ``jax.numpy`` and float32 at ``highest`` matmul
precision — no kernels, no cache, no chunked algebra, no batching of
requests. From the published ``config.json`` and, where it is silent, the
configuration's ``sparse_config`` and ``assumed`` (x [T, d], layer l of kind
``mixer_types[l]``; rs = scale_depth / sqrt(published depth)):

  x0 = scale_emb * E[token].
  h = rmsnorm(x; w_in, eps);  q = h Wq [T, H, D];  k, v = h Wk, h Wv at the
  kind's KV heads;  q, k <- rmsnorm over each head's D dims (w_q, w_k).

  lightning-attn (H KV heads): rope on q and k (all D dims, rope_theta);
    q <- q / sqrt(D);  lam_h = exp(-2^(-8h/H)), h = 1..H;
    S_t = lam_h S_{t-1} + k_t^T v_t (float32 [D, D] per head, S_0 = 0),
    o_t = q_t S_t — THE RECURRENCE, position by position;
    y = (rmsnorm(o over all H*D dims; w_o) * sigmoid(h Wg)) Wo.
  minicpm4 (Hkv KV heads, G = H / Hkv query heads a group, no rope):
    a query at position t < dense_len: causal softmax over all keys.
    else, per KV group g: K^c_j = mean(k[stride j : stride j + kernel]) for
    every kernel that lies wholly at or before t;
    p_hj = softmax_j(q_h K^c_j / sqrt(D));  s_j = sum_{h in g} p_hj;
    B_b = max of s_j over the kernels that overlap block b;
    selected = the first init_blocks blocks, the blocks that cover
    positions t - window_size + 1 .. t, and the topk highest B_b among the
    rest;  o_h = softmax over the keys of the selected blocks at positions
    <= t, scores q_h k / sqrt(D);
    y = (o * sigmoid(h Wg)) Wo.
  x <- x + rs y;  h2 = rmsnorm(x; w_post);
  x <- x + rs (silu(h2 Wgate) * (h2 Wup)) Wdown.
  After the last layer: logits = (rmsnorm(x; w_f) / (hidden_size /
  dim_model_base)) W_head.

Controls (``lowp``): "fp8" rounds both operands of every matmul to e4m3
(the selection's scoring stays float32, as a router's does); "no_decay"
is a WRONG MECHANISM at full precision, lam_h = 1; "dense" another, every
sparse layer attending all keys. Each must read as not correct.

It imports nothing of the program and takes nothing the program made: the
weights come again from the seed through the model module's leaf table,
one layer at a time (the served bfloat16 values, upcast); attention runs in
blocks of queries and the MLP in blocks of tokens, so that a 30,000-position
request fits beside a layer's float32 weights."""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import spec, weights
from yardstick.precision import OPERAND

QUERY_BLOCK = 128
TOKEN_BLOCK = 2048
CONTROLS = {"float32": "float32", "fp8": "fp8", "no_decay": "float32",
            "dense": "float32"}


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, theta):
    """x [B, T, H, D]: rotate interleaved pairs of all D dims by position."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def projections(x, p, cfg, kind, op):
    """(h, q, k, v): the layer's normed input and its normed q, k, v."""
    names = model_of(cfg).ATTENTION_LEAVES[kind]
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, p["input_norm"], eps)
    q = jnp.einsum("btd,dhk->bthk", op(h), op(p["q_proj"]))
    k = jnp.einsum("btd,dhk->bthk", op(h), op(p[names["wk"]]))
    v = jnp.einsum("btd,dhk->bthk", op(h), op(p[names["wv"]]))
    return (h, rms_norm(q, p["q_norm"], eps),
            rms_norm(k, p[names["k_norm"]], eps), v)


def lightning(x, p, cfg, op, control):
    """A lightning-attn layer's attention block, by the recurrence."""
    h, q, k, v = projections(x, p, cfg, "linear", op)
    b, t, n_h, d = q.shape
    theta = float(cfg["rope_theta"])
    q, k = rope(q, theta) * d ** -0.5, rope(k, theta)
    slopes = 2.0 ** (-8.0 * jnp.arange(1, n_h + 1, dtype=jnp.float32) / n_h)
    lam = jnp.ones_like(slopes) if control == "no_decay" else jnp.exp(-slopes)
    lam = lam[None, :, None, None]

    def step(state, qkv):
        q_t, k_t, v_t = qkv                                 # [B, H, D]
        state = lam * state + k_t[..., :, None] * v_t[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    _, o = jax.lax.scan(
        step, jnp.zeros((b, n_h, d, d), jnp.float32),
        tuple(jnp.moveaxis(op(a), 1, 0) for a in (q, k, v)), unroll=4)
    o = jnp.moveaxis(o, 0, 1).reshape(b, t, n_h * d)
    o = rms_norm(o, p["lightning_o_norm"], cfg["rms_norm_eps"])
    gate = jnp.einsum("btd,df->btf", op(h), op(p["o_gate"]))
    o = (o * jax.nn.sigmoid(gate)).reshape(b, t, n_h, d)
    return jnp.einsum("bthk,hkd->btd", op(o), op(p["o_proj"]))


def overlapping_kernels(n_blocks, n_kernels, sp):
    """[NB, W] int: for every block the kernels whose positions overlap
    it, from the definition, -1 where a block has fewer."""
    size, stride, block = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    rows = [[j for j in range(n_kernels)
             if stride * j < block * (b + 1) and stride * j + size > block * b]
            for b in range(n_blocks)]
    width = max(len(r) for r in rows)
    return np.asarray([r + [-1] * (width - len(r)) for r in rows], np.int32)


def sparse(x, p, cfg, op, control):
    """A minicpm4 layer's attention block: dense below ``dense_len``, the
    selected blocks above it, everything from the definitions."""
    model = model_of(cfg)
    sp = model.sparse_sizes(cfg)
    size, stride, block = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    h, q, k, v = projections(x, p, cfg, "sparse", op)
    b, t, n_h, d = q.shape
    h_kv = k.shape[2]
    n_kernels = max((t - size) // stride + 1, 1)
    n_blocks = -(-t // block)
    # compressed keys by their definition: the mean of each kernel's keys
    members = (np.arange(n_kernels)[:, None] * stride
               + np.arange(size)[None, :]).clip(max=t - 1)
    kc = k[:, members].mean(2)                          # [B, NK, Hkv, D]
    over = jnp.asarray(overlapping_kernels(n_blocks, n_kernels, sp))
    pad = -t % QUERY_BLOCK
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, -1, QUERY_BLOCK, h_kv, n_h // h_kv, d).swapaxes(0, 1)
    starts = jnp.arange(qb.shape[0]) * QUERY_BLOCK
    block_of_key = jnp.arange(t) // block

    def query_block(args):
        q_blk, start = args                             # [B, Q, Hkv, G, D]
        at = start + jnp.arange(QUERY_BLOCK)            # positions
        whole = (jnp.arange(n_kernels)[None, :] * stride + size
                 <= at[:, None] + 1)                    # [Q, NK]
        z = jnp.einsum("bqhgk,bjhk->bqhgj", q_blk, kc) * d ** -0.5
        z = jnp.where(whole[None, :, None, None, :], z, -jnp.inf)
        z = jnp.where(whole.any(-1)[None, :, None, None, None], z, 0.0)
        share = jnp.where(whole[None, :, None, None, :],
                          jax.nn.softmax(z, axis=-1), 0.0).sum(3)
        share = jnp.where(whole[None, :, None, :], share, -jnp.inf)
        best = jnp.where(over >= 0, share[..., over.clip(min=0)],
                         -jnp.inf).max(-1)              # [B, Q, Hkv, NB]
        blocks = jnp.arange(n_blocks)[None, :]
        first = jnp.maximum(at - (sp["window_size"] - 1), 0)[:, None] // block
        fixed = (blocks < sp["init_blocks"]) | (blocks >= first)  # [Q, NB]
        rest = jnp.where(fixed[None, :, None, :], -jnp.inf, best)
        n_top = min(sp["topk"], n_blocks)
        vals, picks = jax.lax.top_k(rest, n_top)
        hit = jax.nn.one_hot(picks, n_blocks, dtype=jnp.float32) \
            * (vals > -jnp.inf)[..., None]
        chosen = fixed[None, :, None, :] | (hit.sum(-2) > 0)
        dense = (at < sp["dense_len"]) | (control == "dense")
        chosen = chosen | dense[None, :, None, None]    # [B, Q, Hkv, NB]
        seen = chosen[..., block_of_key] \
            & (jnp.arange(t)[None, :] <= at[:, None])[None, :, None, :]
        s = jnp.einsum("bqhgk,bshk->bqhgs", op(q_blk), op(k)) * d ** -0.5
        s = jnp.where(seen[:, :, :, None, :], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bqhgs,bshk->bqhgk", op(pr), op(v))

    o = jax.lax.map(query_block, (qb, starts))          # [N, B, Q, Hkv, G, D]
    o = o.swapaxes(0, 1).reshape(b, -1, n_h * d)[:, :t]
    gate = jnp.einsum("btd,df->btf", op(h), op(p["o_gate"]))
    o = (o * jax.nn.sigmoid(gate)).reshape(b, t, n_h, d)
    return jnp.einsum("bthk,hkd->btd", op(o), op(p["o_proj"]))


def mlp(x, p, cfg, op):
    """The SwiGLU, ``TOKEN_BLOCK`` tokens at a time."""
    b, t, d = x.shape
    pad = -t % TOKEN_BLOCK
    xb = jnp.pad(x, ((0, 0), (0, pad), (0, 0))).reshape(
        b, -1, TOKEN_BLOCK, d).swapaxes(0, 1)
    gate, up, down = op(p["gate_proj"]), op(p["up_proj"]), op(p["down_proj"])

    def block(xs):
        hid = op(rms_norm(xs, p["post_norm"], cfg["rms_norm_eps"]))
        g = jnp.einsum("btd,df->btf", hid, gate)
        u = jnp.einsum("btd,df->btf", hid, up)
        return jnp.einsum("btf,fd->btd", op(jax.nn.silu(g) * u), down)

    y = jax.lax.map(block, xb)
    return y.swapaxes(0, 1).reshape(b, -1, d)[:, :t]


def decoder_layer(x, p, cfg, kind, control):
    op = OPERAND[CONTROLS[control]]
    rs = model_of(cfg).residual_scale(cfg)
    attention = lightning if kind == "linear" else sparse
    x = x + rs * attention(x, p, cfg, op, control)
    return x + rs * mlp(x, p, cfg, op)


# -- the model, one layer at a time -------------------------------------------
def model_of(cfg: dict):
    return spec.load_model(cfg["model"])


def leaf_table(cfg: dict) -> dict:
    """The leaves as the configuration's model module states them."""
    return model_of(cfg).leaf_table(cfg)


SIZE_KEYS = (
    "model", "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "lightning_nh", "lightning_nkv",
    "lightning_head_dim", "vocab_size", "num_hidden_layers", "mixer_types",
    "rms_norm_eps", "rope_theta", "scale_emb", "scale_depth",
    "dim_model_base", "sparse_config", "published", "seeded_weights")


def model_key(cfg: dict) -> str:
    """The sizes the forward pass needs, hashable for jit."""
    return json.dumps({k: cfg[k] for k in SIZE_KEYS if k in cfg},
                      sort_keys=True)


@functools.partial(jax.jit,
                   static_argnames=("cfg_key", "dtype", "control", "like"))
def _layer_step(x, key, layer, cfg_key, dtype, control, like):
    """Layer ``layer`` (traced) of the kind of layer ``like`` (static)."""
    cfg = json.loads(cfg_key)
    p = jax.tree.map(lambda w: w.astype(jnp.float32), weights.layer_tree(
        key, leaf_table(cfg), layer, jnp.dtype(dtype), like=like))
    return decoder_layer(x, p, cfg, model_of(cfg).layer_kinds(cfg)[like],
                         control)


@functools.partial(jax.jit, static_argnames=("cfg_key", "dtype"))
def _embed(tokens, key, cfg_key, dtype):
    cfg = json.loads(cfg_key)
    e = weights.leaf(key, leaf_table(cfg), "embed", 0, jnp.dtype(dtype))
    return cfg["scale_emb"] * e[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg_key", "dtype", "control"))
def _head(x, key, cfg_key, dtype, control):
    cfg = json.loads(cfg_key)
    op = OPERAND[CONTROLS[control]]
    top = weights.top_tree(key, leaf_table(cfg), jnp.dtype(dtype))
    x = rms_norm(x, top["final_norm"].astype(jnp.float32),
                 cfg["rms_norm_eps"])
    x = x / (cfg["hidden_size"] / cfg["dim_model_base"])
    return jnp.einsum("btd,dv->btv", op(x),
                      op(top["lm_head"].astype(jnp.float32)))


def hidden(cfg: dict, seed: int, tokens, *, dtype: str = "bfloat16",
           lowp: str = "float32"):
    """tokens [B, T] int32 -> the last layer's result [B, T, d] float32,
    layer by layer. ``lowp``: "float32", or one of the controls (module
    docstring)."""
    ck = model_key(cfg)
    key = weights.seed_key(seed)
    kinds = model_of(cfg).layer_kinds(cfg)
    with jax.default_matmul_precision("highest"):
        x = _embed(tokens, key, ck, dtype)
        for layer, kind in enumerate(kinds):
            x = _layer_step(x, key, jnp.int32(layer), ck, dtype, lowp,
                            kinds.index(kind))
    return x


def logits(cfg: dict, seed: int, tokens, *, dtype: str = "bfloat16",
           lowp: str = "float32", span: tuple | None = None):
    """tokens [B, T] int32 -> logits float32 [B, T, V], or of positions
    ``span`` = (lo, hi) only: a 30,000-position row's logits over the whole
    vocabulary would not fit, and only the served positions are judged."""
    x = hidden(cfg, seed, tokens, dtype=dtype, lowp=lowp)
    if span is not None:
        x = x[:, span[0]:span[1]]
    with jax.default_matmul_precision("highest"):
        return _head(x, weights.seed_key(seed), model_key(cfg), dtype, lowp)


@jax.jit
def _gaps(ref_logits, nxt, served):
    """For every served token: how far its reference logit lies below the
    reference's best at that position. ``ref_logits`` [B, W, V] of the
    positions whose NEXT tokens are ``nxt`` [B, W]; ``served`` [B, W]."""
    best = ref_logits.max(-1)
    picked = jnp.take_along_axis(ref_logits, nxt[..., None], axis=-1)[..., 0]
    gap = jnp.where(served, best - picked, 0.0)
    top1 = jnp.where(served, ref_logits.argmax(-1) == nxt, False)
    return gap.max(), gap.sum() / served.sum(), top1.sum(), served.sum()


def served_token_gaps(cfg, seed, tokens, lens_prompt, lens_total, *,
                      dtype="bfloat16", block: int = 1, lowp_control=None):
    """Run the reference once over each prompt with its served tokens (rows
    of ``tokens``, padded to one length; causal, so padding changes nothing
    before it; each row cut to its own length rounded up to a query block,
    so a short row does not pay for the longest), ``block`` rows at a time,
    and the head over the positions that predict a served token. Returns
    the widest and the mean gap, and how many served tokens are the
    reference's own first choice. With ``lowp_control`` the tokens judged
    are NOT the served ones but the ones the control puts first at each
    position."""
    widest, total_gap, agree, count = 0.0, 0.0, 0, 0
    for i in range(0, tokens.shape[0], block):
        lp = np.asarray(lens_prompt[i:i + block])
        lt = np.asarray(lens_total[i:i + block])
        width = min(-(-int(lt.max()) // QUERY_BLOCK) * QUERY_BLOCK,
                    tokens.shape[1])
        tk = jnp.asarray(tokens[i:i + block, :width])
        # position t predicts token t + 1: the served ones are predicted
        # by positions lens_prompt - 1 .. lens_total - 2
        lo, hi = int(lp.min()) - 1, int(lt.max()) - 1
        at = np.arange(lo, hi)[None, :]
        served = jnp.asarray((at >= lp[:, None] - 1) & (at < lt[:, None] - 1))
        ref = logits(cfg, seed, tk, dtype=dtype, span=(lo, hi))
        nxt = tk[:, lo + 1:hi + 1]
        if lowp_control:
            # the control's first choice at t, judged as token t + 1
            nxt = logits(cfg, seed, tk, dtype=dtype, lowp=lowp_control,
                         span=(lo, hi)).argmax(-1).astype(tk.dtype)
        w, mean, top1, n = _gaps(ref, nxt, served)
        del ref
        widest = max(widest, float(w))
        total_gap += float(mean) * int(n)
        agree += int(top1)
        count += int(n)
    return {"widest_gap": widest, "mean_gap": total_gap / max(count, 1),
            "top1_agree": agree, "tokens": count}
