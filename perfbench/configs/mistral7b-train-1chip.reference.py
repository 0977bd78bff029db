"""Plain reference for ``mistral7b-train-1chip``: Mistral-7B's forward
pass, next-token loss, gradients and the AdamW update in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision — no kernels, no
mixed precision, no sharding. The model follows the published description
(mistral-src ``model.py``); the optimizer is AdamW as Loshchilov & Hutter
give it with the job's stated settings (global-norm clip 1.0, b1 0.9,
b2 0.999, eps 1e-8, decoupled weight decay on every leaf). Departures: as
in the serving reference (eps 1e-5 kept against the program's 1e-6; no
sliding window needed at 2,048 positions).

It imports nothing of the program and takes nothing the program made: the
weights come again from the seed, the token rows are the ones the job fed
its step. One row at a time under ``lax.map`` with recomputation, so that
the float32 activations fit beside 16 bytes a parameter."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from yardstick import spec, weights
from yardstick.precision import OPERAND

B1, B2, EPS = 0.9, 0.999, 1e-8


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, theta):
    _, t, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def decoder_layer(x, p, cfg, op):
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


def loss_fn(params, tokens, cfg, op):
    """Mean next-token cross-entropy over every row and position.
    tokens [B, S + 1]."""
    layer = jax.checkpoint(functools.partial(decoder_layer, cfg=cfg, op=op))

    def row_nll(row):
        x = params["embed"][row[:-1]][None]
        for i in range(cfg["num_hidden_layers"]):
            x = layer(x, jax.tree.map(lambda w: w[i], params["layers"]))
        x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
        logits = jnp.einsum("btd,dv->btv", op(x), op(params["lm_head"]))[0]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, row[1:, None], axis=-1)[:, 0]
        return jnp.sum(lse - picked)

    total = jnp.sum(jax.lax.map(jax.checkpoint(row_nll), tokens))
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def model_key(cfg: dict) -> tuple:
    names = ("model", "hidden_size", "intermediate_size",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "vocab_size", "num_hidden_layers", "rms_norm_eps", "rope_theta")
    return tuple((n, cfg[n]) for n in names)


def leaf_table(cfg: dict) -> dict:
    """The leaves as the configuration's model module states them."""
    return spec.load_model(cfg["model"]).leaf_table(cfg)


def _leaves(tree: dict) -> dict:
    """Flat name -> leaf; a layer leaf is the whole [L, ...] stack, which is
    what the optimizer sees as one leaf."""
    flat = {k: v for k, v in tree.items() if k != "layers"}
    flat.update(tree["layers"])
    return flat


def _norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(v * v)) for k, v in _leaves(tree).items()}


@functools.partial(jax.jit, static_argnames=("cfg_key",))
def init_params(key, cfg_key):
    cfg = dict(cfg_key)
    table = leaf_table(cfg)
    params = weights.top_tree(key, table, jnp.float32)
    params["layers"] = weights.stacked_layers(key, table, jnp.float32)
    return params


@functools.partial(jax.jit, static_argnames=("cfg_key", "lowp", "hp"),
                   donate_argnums=(0, 1, 2))
def adamw_step(params, m, v, count, tokens, cfg_key, lowp, hp):
    cfg, hp = dict(cfg_key), dict(hp)
    loss, g = jax.value_and_grad(loss_fn)(params, tokens, cfg, OPERAND[lowp])
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * (hp["grad_clip"]
                                    / jnp.maximum(gnorm, hp["grad_clip"])), g)
    grad_norms = _norms(g)          # the gradient as the optimizer gets it
    t = count + 1
    m = jax.tree.map(lambda a, b: B1 * a + (1 - B1) * b, m, g)
    v = jax.tree.map(lambda a, b: B2 * a + (1 - B2) * b * b, v, g)
    c1, c2 = 1 - B1 ** t, 1 - B2 ** t
    params = jax.tree.map(
        lambda p, a, b: p - hp["learning_rate"] * (
            (a / c1) / (jnp.sqrt(b / c2) + EPS) + hp["weight_decay"] * p),
        params, m, v)
    return params, m, v, loss, grad_norms, gnorm


@functools.partial(jax.jit, static_argnames=("cfg_key",))
def change_norms(params, key, cfg_key):
    """||p - p0|| per leaf, p0 made again from the seed."""
    p0 = init_params.__wrapped__(key, cfg_key)
    return _norms(jax.tree.map(lambda a, b: a - b, params, p0))


def first_steps(cfg: dict, seed: int, batches, hp: dict, *,
                lowp: str = "float32") -> dict:
    """Follow the job's first steps: ``batches`` is the list of token
    arrays [B, S + 1] the job fed its step, in order. Returns each step's
    loss, the first step's per-leaf gradient norms (after the clip, as the
    optimizer gets them), and the per-leaf norm of the parameters' change
    after the last step."""
    ck = model_key(cfg)
    key = weights.seed_key(seed)
    hpk = tuple(sorted(hp.items()))
    with jax.default_matmul_precision("highest"):
        params = init_params(key, ck)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms = [], None
        for i, tokens in enumerate(batches):
            params, m, v, loss, gn, _ = adamw_step(
                params, m, v, jnp.float32(i), jnp.asarray(tokens, jnp.int32),
                ck, lowp, hpk)
            losses.append(float(loss))
            if i == 0:
                grad_norms = {k: float(x) for k, x in gn.items()}
        del m, v
        change = {k: float(x)
                  for k, x in change_norms(params, key, ck).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}
