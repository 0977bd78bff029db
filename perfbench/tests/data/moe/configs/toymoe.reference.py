"""Plain reference for the toy mixture of experts (``toymoe-train`` and
``toymoe-serve``; ``tinyrepo`` copies it beside both): forward pass,
next-token loss with the router's two auxiliary terms, gradients and the
AdamW update in straightforward ``jax.numpy`` and float32 at ``highest``
matmul precision — no kernels, no dispatch, no capacity. Every layer:
pre-norm attention (rotary over interleaved pairs, grouped-query), then a
softmax router over ``num_local_experts`` SwiGLU experts; a token's output
is the sum over its top-k experts (top-k on the probabilities) weighted by
the chosen probabilities normalised to 1. Every token reaches its top-k
experts. The loss is the mean cross-entropy + ``router_aux_loss_coef`` x
the Switch balance term (E · Σ_e f_e·P_e, f the share of (token, choice)
pairs of the whole batch that chose e, P the mean probability of e; f
carries no gradient) + ``router_z_loss_coef`` x mean logsumexp(router
logits)², each term averaged over layers.

It imports nothing of the program and takes nothing the program made: the
weights come again from the seed, the token rows are the ones the job fed
its step. Toy sizes: the whole batch and the whole model at once."""

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


def attention(x, p, cfg, op):
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    t = x.shape[1]
    a = rms_norm(x, p["input_norm"], cfg["rms_norm_eps"])
    q = jnp.einsum("btd,dhk->bthk", op(a), op(p["q_proj"]))
    k = jnp.einsum("btd,dhk->bthk", op(a), op(p["k_proj"]))
    v = jnp.einsum("btd,dhk->bthk", op(a), op(p["v_proj"]))
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // hkv, axis=2)
    v = jnp.repeat(v, h // hkv, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", op(q), op(k)) * dh ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    pr = jax.nn.softmax(jnp.where(causal[None, None], s, -1e30), axis=-1)
    o = jnp.einsum("bhqs,bshk->bqhk", op(pr), op(v))
    return x + jnp.einsum("bthk,hkd->btd", op(o), op(p["o_proj"]))


def experts(x, p, cfg, op):
    """Returns the layer's output and its two router terms. The router is
    float32 in the control too: only the experts' matmuls are rounded."""
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    m = rms_norm(x, p["post_norm"], cfg["rms_norm_eps"])
    logits = jnp.einsum("btd,de->bte", m, p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    chosen = jax.nn.one_hot(top_i, e, dtype=jnp.float32)       # [b, t, k, E]
    share = (chosen * (top_p / top_p.sum(-1, keepdims=True))[..., None]).sum(2)
    g = jnp.einsum("btd,edf->btef", op(m), op(p["experts_gate"]))
    u = jnp.einsum("btd,edf->btef", op(m), op(p["experts_up"]))
    y = jnp.einsum("btef,efd->bted", op(jax.nn.silu(g) * u),
                   op(p["experts_down"]))
    balance = e * jnp.sum(chosen.mean((0, 1, 2)) * probs.mean((0, 1)))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return x + jnp.einsum("bted,bte->btd", y, share), balance, z


def forward(params, tokens, cfg, op):
    """tokens [B, T] -> logits [B, T, V], and the router terms' layer means."""
    x = params["embed"][tokens]
    balance = z = 0.0
    n = cfg["num_hidden_layers"]
    for i in range(n):
        p = jax.tree.map(lambda w: w[i], params["layers"])
        x, b_i, z_i = experts(attention(x, p, cfg, op), p, cfg, op)
        balance, z = balance + b_i / n, z + z_i / n
    x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    return jnp.einsum("btd,dv->btv", op(x), op(params["lm_head"])), balance, z


def loss_fn(params, tokens, cfg, op):
    logits, balance, z = forward(params, tokens[:, :-1], cfg, op)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return (jnp.mean(lse - picked) + cfg["router_aux_loss_coef"] * balance
            + cfg["router_z_loss_coef"] * z)


def model_key(cfg: dict) -> tuple:
    names = ("model", "hidden_size", "intermediate_size",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "vocab_size", "num_hidden_layers", "num_local_experts",
             "num_experts_per_tok", "router_aux_loss_coef",
             "router_z_loss_coef", "rms_norm_eps", "rope_theta")
    return tuple((n, cfg[n]) for n in names)


@functools.partial(jax.jit, static_argnames=("cfg_key", "dtype"))
def init_params(key, cfg_key, dtype="float32"):
    """The seeded leaves in ``dtype`` (what is served), upcast."""
    cfg = dict(cfg_key)
    table = spec.load_model(cfg["model"]).leaf_table(cfg)
    params = weights.top_tree(key, table, jnp.dtype(dtype))
    params["layers"] = weights.stacked_layers(key, table, jnp.dtype(dtype))
    return jax.tree.map(lambda w: w.astype(jnp.float32), params)


def _norms(tree: dict) -> dict:
    flat = {k: v for k, v in tree.items() if k != "layers"}
    flat.update(tree["layers"])
    return {k: jnp.sqrt(jnp.sum(v * v)) for k, v in flat.items()}


@functools.partial(jax.jit, static_argnames=("cfg_key", "lowp", "hp"))
def adamw_step(params, m, v, count, tokens, cfg_key, lowp, hp):
    cfg, hp = dict(cfg_key), dict(hp)
    loss, g = jax.value_and_grad(loss_fn)(params, tokens, cfg, OPERAND[lowp])
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * (hp["grad_clip"]
                                    / jnp.maximum(gnorm, hp["grad_clip"])), g)
    t = count + 1
    m = jax.tree.map(lambda a, b: B1 * a + (1 - B1) * b, m, g)
    v = jax.tree.map(lambda a, b: B2 * a + (1 - B2) * b * b, v, g)
    c1, c2 = 1 - B1 ** t, 1 - B2 ** t
    params = jax.tree.map(
        lambda p, a, b: p - hp["learning_rate"] * (
            (a / c1) / (jnp.sqrt(b / c2) + EPS) + hp["weight_decay"] * p),
        params, m, v)
    return params, m, v, loss, _norms(g)


def first_steps(cfg: dict, seed: int, batches, hp: dict, *,
                lowp: str = "float32") -> dict:
    """Follow the job's first steps on the rows it fed: each step's loss,
    the first step's per-leaf gradient norms (after the clip), and the
    per-leaf norm of the parameters' change after the last step."""
    ck, key = model_key(cfg), weights.seed_key(seed)
    hpk = tuple(sorted(hp.items()))
    with jax.default_matmul_precision("highest"):
        p0 = init_params(key, ck)
        params = p0
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms = [], None
        for i, tokens in enumerate(batches):
            params, m, v, loss, gn = adamw_step(
                params, m, v, jnp.float32(i), jnp.asarray(tokens, jnp.int32),
                ck, lowp, hpk)
            losses.append(float(loss))
            if i == 0:
                grad_norms = {k: float(x) for k, x in gn.items()}
        change = _norms(jax.tree.map(lambda a, b: a - b, params, p0))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": {k: float(x) for k, x in change.items()}}


@functools.partial(jax.jit, static_argnames=("cfg_key", "lowp"))
def _logits(params, tokens, cfg_key, lowp):
    return forward(params, tokens, dict(cfg_key), OPERAND[lowp])[0]


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
    return gap.max(), gap.sum(), top1.sum(), served.sum()


def served_token_gaps(cfg, seed, tokens, lens_prompt, lens_total, *,
                      dtype="float32", lowp_control=None):
    """Run the reference once over each prompt with its served tokens (rows
    of ``tokens``, padded to one length; causal, so padding changes nothing
    before it). With ``lowp_control`` the tokens judged are NOT the served
    ones but the ones the lower precision puts first at each position."""
    ck = model_key(cfg)
    tk = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        params = init_params(weights.seed_key(seed), ck, dtype)
        ref = _logits(params, tk, ck, "float32")
        judged = tk
        if lowp_control:
            low = _logits(params, tk, ck, lowp_control)
            judged = jnp.concatenate(
                [tk[:, :1], low.argmax(-1)[:, :-1].astype(tk.dtype)], axis=1)
        widest, total, top1, n = _gaps(ref, judged, jnp.asarray(lens_prompt),
                                       jnp.asarray(lens_total))
    return {"widest_gap": float(widest), "mean_gap": float(total) / int(n),
            "top1_agree": int(top1), "tokens": int(n)}
