"""Inference for the flagship transformer: the weights layout, THE
decoder layer, and single-shot generation over a KV cache.

The reference is a training orchestrator with no model code at all; this
inference path completes the model family the rebuild adds. What lives
here, and why here:

* ``decode_weights`` re-packs the fp32 training masters: downcast to the
  compute dtype, qkv and gate|up fused — decode at small batch is
  bandwidth/op-count-bound, so fewer, wider matmuls win. Same trunk
  layout as training, so trained checkpoints drop in.
* The decoder layer of inference is defined ONCE, beside the layout it
  reads: ``serve_layer`` (pre-norm attention of the layer's kind, then
  dense SwiGLU or the experts), ``run_layers`` (one ``lax.scan`` over a
  uniform model's stacked layers with the caches as carry, a static loop
  over a layered model's tuple), ``lm_head`` (final norm, unembed,
  float32). A layer is one decision: ``advance`` here and the serving
  engine's ``decode_window`` / ``prefill_chunks``
  (``serving/engine.py``) all run it, and each brings its CACHE POLICY
  as an ``attend(q, k_new, v_new, attn, sink) -> o`` closure: how the
  new K/V rows are written and how the cache is read is all they do
  differently. Training's ``models/transformer.py::_decoder_layer`` is
  deliberately another definition (unfused fp32 masters, a backward,
  remat, partitioning): the independent reference the parity tests hold
  this one to.
* Expert layers go through ``_moe_mlp_decode``: one dropless grouped
  path for prefill and decode alike — the (token, choice) pairs that
  land on the experts held here, sorted by expert, through two grouped
  matrix products (``ops.grouped_matmul``: on a TPU a Pallas kernel
  whose work follows the rows that are there and whose tiles are sized
  to stream each visited expert's weights once, the rows resident
  beside them) and added back under their router weights. Static
  shapes, no capacity, no pair dropped under any routing; running every
  held expert on every token would be 32x the needed FLOPs where a token
  uses 0.5 of 16 held experts. Where the worst case's pair rows would be
  a real share of the weights' bytes (a prefill round over a small share
  of the experts) the sorted pairs go in passes of a static number of
  rows, as many passes as the pairs that landed need: one at any routing
  near uniform, and the arrays follow the pairs, not the worst case.
* ``advance``'s cache policy: one jittable call handles both prefill
  (S = prompt length) and single-token steps (S = 1), static shapes per
  call site, so XLA compiles exactly two executables for a whole
  generation loop. The cache is a stacked [L, B, Tmax, Hkv, Dh] pair
  updated with ``dynamic_update_slice`` at a traced offset; Hkv < H
  under GQA — the n_heads/n_kv_heads cache shrink is the main
  decode-bandwidth lever. A prompt into an empty cache attends through
  the flash kernel over its own tokens; a step attends the cache by
  ``ops.grouped_cache_attention`` (q regrouped [B, S, Hkv, G, Dh] so the
  cache is never head-repeated, read in the stored dtype with fp32 MXU
  accumulation and fp32 softmax).
* ``generate`` / ``DecodeSession``: greedy at ``temperature=0``, else
  temperature sampling with a caller-provided key. ``DecodeSession``
  holds the fused pack so repeated ``generate`` calls pay fusion once
  (module-level ``generate`` on raw params re-fuses per call).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.models.transformer import TransformerConfig
from tony_tpu.ops import (
    apply_rope,
    flash_attention,
    grouped_cache_attention,
    grouped_matmul,
    rms_norm,
    rope_frequencies,
)
from tony_tpu.ops.grouped import ROW_TILE

NEG_INF = -1e30


def is_fused(params: dict) -> bool:
    """Whether ``params`` is the ``decode_weights`` layout: a stacked
    dict with ``qkv``, or (a layered configuration) a tuple of layers."""
    layers = params["layers"]
    return isinstance(layers, tuple) or "qkv" in layers


def decode_weights(params: dict, cfg: TransformerConfig) -> dict:
    """Re-pack training params for the decode loop: cast fp32 masters to the
    compute dtype and fuse the per-layer projections (wq|wk|wv on the head
    axis, w_gate|w_up on the feature axis) so each decode step runs one
    matmul where training runs three/two. Decode is bandwidth- and
    op-count-bound at batch sizes the MXU can't fill; the fusion runs once
    per ``generate`` call (XLA hoists it out of the token loop).

    MoE configs keep the router and fuse gate|up per expert
    ([L, E, d, 2F]); see ``_moe_mlp_decode``.

    A layered configuration (groups of stacks by kind) comes back as a
    TUPLE of layers in model order, each an array set of its own: the
    serving programs walk it in a static loop, so no layer is ever
    sliced out of a stack on the device. Its q|k|v fuse on the FEATURE
    axis ([d, H*Dk + Hkv*Dk + Hkv*Dv]: the widths differ, the head axis
    cannot hold them), per kind at that kind's KV head count.

    ``advance`` accepts either this fused layout or raw training params
    (fusing on the fly), so eager chat-style callers need not care."""
    dt = cfg.compute_dtype

    def c(x):
        return x.astype(dt)

    top = {
        "embed": c(params["embed"]),
        "final_norm": c(params["final_norm"]),
    }
    if not cfg.tie_embeddings:
        # a tied head reads ``embed``: one matrix on the device
        top["unembed"] = c(params["unembed"])
    if cfg.layered:
        layers: list = [None] * cfg.n_layers
        for name, members in cfg.layer_groups.items():
            for i, layer in enumerate(members):
                layers[layer] = _fuse_layer(
                    jax.tree.map(lambda p: p[i], params["layers"][name]), dt)
        return {**top, "layers": tuple(layers)}
    lp = params["layers"]
    layers = {
        "ln1": c(lp["ln1"]),
        "ln2": c(lp["ln2"]),
        # [L, d, H + 2*Hkv, Dh]
        "qkv": jnp.concatenate(
            [c(lp["wq"]), c(lp["wk"]), c(lp["wv"])], axis=2
        ),
        "wo": c(lp["wo"]),
        # dense: [L, d, 2F]; MoE: [L, E, d, 2F]
        "gate_up": jnp.concatenate(
            [c(lp["w_gate"]), c(lp["w_up"])], axis=-1
        ),
        "w_down": c(lp["w_down"]),
    }
    if cfg.n_experts:
        # Router stays fp32 (it is tiny): training routes from fp32
        # masters, and a bf16 router could flip near-tie gate logits at
        # decode — the token-exact-parity guarantee would silently narrow
        # to fp32 configs (ADVICE r3).
        layers["router"] = lp["router"].astype(jnp.float32)
    return {**top, "layers": layers}


def _fuse_layer(lp: dict, dt) -> dict:
    """One layer of a layered configuration in the serving layout."""
    out = {
        "ln1": lp["ln1"].astype(dt),
        "ln2": lp["ln2"].astype(dt),
        "gate_up": jnp.concatenate(
            [lp["w_gate"].astype(dt), lp["w_up"].astype(dt)], axis=-1),
        "w_down": lp["w_down"].astype(dt),
    }
    if "in_proj" in lp:
        # a conv layer: no q/k/v/o
        for name in ("in_proj", "conv_w", "out_proj"):
            out[name] = lp[name].astype(dt)
    else:
        d = lp["wq"].shape[0]
        out["qkv"] = jnp.concatenate(
            [lp[n].astype(dt).reshape(d, -1) for n in ("wq", "wk", "wv")],
            axis=1)
        out["wo"] = lp["wo"].astype(dt)
    if "w_ogate" in lp:
        # a gated kind: q|k|v|gate, the gate H * Dv wide behind v
        out["qkv"] = jnp.concatenate(
            [out["qkv"], lp["w_ogate"].astype(dt)], axis=1)
    # Router, its selection bias and the sinks stay float32 (tiny, and a
    # near tie must not flip on a rounding the model never had).
    for name in ("router", "router_bias", "sink"):
        if name in lp:
            out[name] = lp[name].astype(jnp.float32)
    for name in ("q_norm", "k_norm", "o_norm"):
        if name in lp:
            out[name] = lp[name].astype(dt)
    return out


def decode_param_specs(cfg: TransformerConfig) -> dict:
    """PartitionSpecs for the FUSED ``decode_weights`` layout — the
    serving twin of training's ``param_roles`` (transformer.py): tp
    megatron-splits the packed head axis of qkv, the head axis of wo, the
    fused ff axis of gate|up and w_down, and the vocab axis of unembed;
    MoE experts split over ep. Norms, embed, and the (tiny, fp32) router
    replicate. ``DecodeSession(mesh=...)`` places weights with these; a
    dim a mesh axis doesn't divide falls back to replicated at placement
    time (sharding is an optimization, never a correctness requirement —
    same rule as train._sharding_for_tree). A layered configuration gets
    one spec set per layer, by its kind: its feature-fused qkv
    replicates (q, k and v columns of one head do not lie together)."""
    from jax.sharding import PartitionSpec as P

    top = {"embed": P(), "final_norm": P(),
           **({} if cfg.tie_embeddings else {"unembed": P(None, "tp")})}
    if cfg.layered:
        def one(attn, mlp):
            spec = {"ln1": P(), "ln2": P()}
            if attn == "conv":
                spec.update(in_proj=P(), conv_w=P(), out_proj=P())
            else:
                spec.update(qkv=P(), wo=P("tp", None, None))
            if mlp == "moe":
                spec.update(gate_up=P("ep", None, "tp"),
                            w_down=P("ep", "tp", None), router=P())
                if cfg.router_bias:
                    spec["router_bias"] = P()
            else:
                spec.update(gate_up=P(None, "tp"), w_down=P("tp", None))
            if attn == "window" and cfg.window_sink:
                spec["sink"] = P()
            if cfg.qk_norm and attn != "conv":
                spec.update(q_norm=P(), k_norm=P())
            if attn in cfg.out_norm_kinds:
                spec["o_norm"] = P()
            return spec

        return {**top, "layers": tuple(one(a, m) for a, m in cfg.layer_kinds)}
    layers = {
        "ln1": P(),
        "ln2": P(),
        "qkv": P(None, None, "tp", None),     # [L, d, H+2Hkv, Dh]
        "wo": P(None, "tp", None, None),      # [L, H, Dh, d]
        "gate_up": (
            P(None, "ep", None, "tp")          # [L, E, d, 2F]
            if cfg.n_experts else P(None, None, "tp")  # [L, d, 2F]
        ),
        "w_down": (
            P(None, "ep", "tp", None)          # [L, E, F, d]
            if cfg.n_experts else P(None, "tp", None)  # [L, F, d]
        ),
    }
    if cfg.n_experts:
        layers["router"] = P()
    return {**top, "layers": layers}


def _cache_spec(abstract_mesh, batch: int, kv_heads: int):
    """KV-cache PartitionSpec under the active mesh (None outside one):
    batch over dp, kv heads over tp — the cache is the decode-bandwidth
    budget, so it must live sharded next to the qkv weights that feed it.
    Axes that don't divide the dim replicate."""
    from jax.sharding import PartitionSpec as P

    if abstract_mesh is None or abstract_mesh.empty:
        return None
    sizes = dict(zip(abstract_mesh.axis_names, abstract_mesh.axis_sizes))
    dp = "dp" if sizes.get("dp", 1) > 1 and batch % sizes["dp"] == 0 else None
    tp = ("tp" if sizes.get("tp", 1) > 1 and kv_heads % sizes["tp"] == 0
          else None)
    if dp is None and tp is None:
        return None
    return P(None, dp, None, tp, None)


def init_cache(cfg: TransformerConfig, batch: int, max_len: int) -> dict:
    # kv_heads (not n_heads): GQA caches only the shared K/V heads — an
    # n_heads/n_kv_heads shrink in both HBM footprint and per-step traffic.
    shape = (cfg.n_layers, batch, max_len, cfg.kv_heads, cfg.head_dim)
    dt = cfg.compute_dtype
    k = jnp.zeros(shape, dt)
    v = jnp.zeros(shape, dt)
    spec = _cache_spec(
        jax.sharding.get_abstract_mesh(), batch, cfg.kv_heads
    )
    if spec is not None:
        # Inside a mesh context (DecodeSession(mesh=...) serving): pin the
        # cache sharding rather than leaving it to GSPMD propagation —
        # the carry of the token-loop scan is the one place a bad
        # propagation choice would replicate the whole cache per device.
        k = lax.with_sharding_constraint(k, spec)
        v = lax.with_sharding_constraint(v, spec)
    return {
        "k": k,
        "v": v,
        "length": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# The inference layer (module docstring): one definition, three callers
# ---------------------------------------------------------------------------

def _rope(x, tables, positions, rot: int):
    """Rotary embedding on the first ``rot`` dims of the head; the rest
    pass."""
    cos, sin = tables
    if rot == x.shape[-1]:
        return apply_rope(x, cos, sin, positions=positions)
    return jnp.concatenate(
        [apply_rope(x[..., :rot], cos, sin, positions=positions),
         x[..., rot:]], axis=-1)


def rope_tables(cfg: TransformerConfig) -> dict:
    """(cos, sin) per attention kind the model has, in the layers' order
    (a set's order follows the process's hash seed, and with it the
    program's text and its key in the compile cache)."""
    return {
        kind: rope_frequencies(cfg.rot_dim, cfg.max_seq,
                               theta=cfg.rope_theta_of(kind))
        for kind in dict.fromkeys(a for a, _ in cfg.layer_kinds)
        if kind not in cfg.no_rope_kinds and kind != "conv"
    }


# The expert layer takes its pairs in passes from where ONE array of the
# worst case's pair rows is a 48th of the held weights' bytes: the layer
# reads or writes about six such arrays (the gathered rows, gate|up's
# result and the activation each written and read, the down product's
# result and its un-sort), an eighth of what the weights stream.
_PASSES_FROM = 48


def _pass_rows(nk: int, d: int, held: int, n_experts: int, itemsize: int,
               weight_bytes: int) -> int:
    """Pair rows one pass of the expert layer takes. All ``nk`` — one
    pass whatever the routing, arrays of the worst case's size — unless
    those arrays are a real share of the held weights' bytes AND the
    experts held here see a small share of the pairs; then four times
    the mean that lands here, in whole row tiles of the grouped product
    (1,024 of a 4,096-row prefill round over 16 of 256 experts; a decode
    iteration's 512 rows, 4 MB arrays against 805 MB of weights, stay
    one pass)."""
    rows = -(-4 * nk * held // (n_experts * ROW_TILE)) * ROW_TILE
    if 2 * rows > nk or _PASSES_FROM * nk * d * itemsize < weight_bytes:
        return nk
    return rows


def _moe_mlp_decode(x, lp, cfg, token_mask=None, count_mask=None):
    """An expert layer for prefill and decode alike: dropless, grouped.

    x [b, t, d]. The router scores all ``n_experts`` and picks top-k
    (``_route_tokens``: softmax or sigmoid, a selection bias where the
    model has one); the (token, choice) pairs that land on the experts
    HELD here (``cfg.held``; all of them by default) are sorted by
    expert, the rest last, and go through two grouped matrix products
    over ``gate_up`` [held, d, 2F] and ``w_down`` [held, F, d]
    (``ops.grouped_matmul``), then back to their tokens under the
    router's weights — normalised over all k choices, so a share of the
    experts gives its own part of the layer's result and nothing stands
    in for the rest.

    Shapes are static and no pair is dropped under any routing. Where
    the b*t*k pair rows of the worst case are small beside the weights
    (``_pass_rows``: a decode batch, every test-sized model) every array
    has that many rows and the grouped products' work follows the rows
    really there. Where they are not — a prefill round's 4,096 rows
    moved ~180 MB a layer beside 805 MB of weights, for the ~256 pairs
    that land on 16 of 256 experts — the sorted pairs are taken in
    PASSES of a static ``C`` rows: gather the pass's ``C`` token rows,
    the two products with each group's size clipped to the pass, add
    the results to their tokens. ``ceil(pairs here / C)`` passes run, a
    traced count: all b*t*k pairs can land here and then all are
    computed, in ``b*t*k / C`` passes, so there is no capacity; at any
    routing near uniform ``C`` is four times what lands here, one pass
    runs and the weights stream once.

    ``token_mask`` [b, t]: tokens whose pairs take no part (idle lanes
    of a decode batch). ``count_mask`` [b, t]: tokens that are COMPUTED
    but not counted (a prefill batch's padding rows, which must write
    the K/V of the row they duplicate). Returns (out [b, t, d], counts:
    ``pairs`` [held] int32, the counted pairs each held expert received,
    and ``passes`` int32, the passes the layer ran)."""
    from tony_tpu.models.transformer import _route_tokens

    dt = cfg.compute_dtype
    b, t, d = x.shape
    n, k = b * t, cfg.expert_top_k
    first, held = cfg.held
    hn32 = rms_norm(x.astype(jnp.float32), lp["ln2"], eps=cfg.rms_eps)
    _, _, gvals, gidx = _route_tokens(
        hn32, lp["router"], k, scoring=cfg.router_scoring,
        bias=lp.get("router_bias"))
    local = gidx.reshape(-1) - first                      # [n*k]
    here = (local >= 0) & (local < held)
    if token_mask is not None:
        here &= jnp.repeat(token_mask.reshape(-1), k)
    # Pairs sorted by held expert; the rest sort last, into no group.
    key = jnp.where(here, local, held)
    on_expert = key[:, None] == jnp.arange(held)           # [n*k, held]
    sizes = on_expert.sum(0, dtype=jnp.int32)
    pairs = sizes
    if count_mask is not None:
        counted = jnp.repeat(count_mask.reshape(-1), k)
        pairs = (on_expert & counted[:, None]).sum(0, dtype=jnp.int32)
    rows_of = hn32.astype(dt).reshape(n, d)
    f = lp["w_down"].shape[1]

    def experts(rows, group):
        gu = grouped_matmul(rows, lp["gate_up"], group)
        act = (
            jax.nn.silu(gu[:, :f].astype(jnp.float32)).astype(dt) * gu[:, f:]
        )
        return grouped_matmul(act, lp["w_down"], group)

    c = _pass_rows(n * k, d, held, cfg.n_experts, dt.itemsize,
                   sum(w.size for w in (lp["gate_up"], lp["w_down"]))
                   * dt.itemsize)
    if c == n * k:
        order = jnp.argsort(key, stable=True)
        y = experts(rows_of[order // k], sizes)            # [n*k, d]
        # Back in (token, choice) order; rows of no group carry whatever
        # the grouped product left there, so they are selected out, not
        # weighed.
        y = y[jnp.argsort(order)]
        w = jnp.where(here, gvals.reshape(-1), 0.0)
        out = jnp.where(here[:, None], y.astype(jnp.float32) * w[:, None],
                        0.0).reshape(b, t, k, d).sum(2)
        passes = jnp.ones((), jnp.int32)
    else:
        # One sort carries each pair's index and router weight along.
        _, order, weight = lax.sort(
            (key, jnp.arange(n * k, dtype=jnp.int32), gvals.reshape(-1)),
            num_keys=1, is_stable=True)
        whole = (0, -(n * k) % c)                 # the last pass, in full
        order, weight = jnp.pad(order, whole), jnp.pad(weight, whole)
        ends = jnp.cumsum(sizes)

        def one_pass(i, out):
            lo = i * c
            token = lax.dynamic_slice(order, (lo,), (c,)) // k
            w = lax.dynamic_slice(weight, (lo,), (c,))
            y = experts(rows_of[token],
                        jnp.diff(jnp.clip(ends - lo, 0, c), prepend=0))
            # Back to the tokens in ONE product: row r's router weight
            # stands in its token's row of a [n, c] matrix (rows past
            # the pairs that landed: nowhere, and their undefined result
            # selected out). ``HIGH``: three bfloat16 passes carry the
            # weight and the result to 16 bits and more, where the
            # layer's result keeps 8. n*c*d multiply-adds: cheaper than
            # an un-sort of b*t*k rows while c is a few tokens' worth
            # (PERF.md section 7).
            landed = lo + jnp.arange(c) < ends[-1]
            back = jnp.where(
                (token == jnp.arange(n)[:, None]) & landed, w, 0.0)
            y = jnp.where(landed[:, None], y, 0).astype(jnp.float32)
            return out + jnp.dot(back, y, precision=lax.Precision.HIGH)

        passes = (ends[-1] + c - 1) // c
        out = lax.fori_loop(0, passes, one_pass,
                            jnp.zeros((n, d), jnp.float32)).reshape(b, t, d)
    return out.astype(dt), {"pairs": pairs, "passes": passes}


def _mlp(x, lp, cfg, token_mask=None, count_mask=None):
    """SwiGLU over the fused gate|up projection (training's
    ``_dense_mlp`` in one matmul instead of two), or the grouped expert
    layer (``_moe_mlp_decode``: dropless, the held experts' part).
    Returns (x, counts): the expert layer's counters (``pairs`` each
    held expert received and ``passes``), None for a dense layer."""
    dt = cfg.compute_dtype
    if "router" in lp:
        out, counts = _moe_mlp_decode(x, lp, cfg, token_mask, count_mask)
        return _residual(x, out, cfg), counts
    hn = rms_norm(x, lp["ln2"], eps=cfg.rms_eps).astype(dt)
    gu = jnp.einsum("btd,df->btf", hn, lp["gate_up"])
    f = gu.shape[-1] // 2
    act = (
        jax.nn.silu(gu[..., :f].astype(jnp.float32)).astype(dt)
        * gu[..., f:]
    )
    return _residual(x, jnp.einsum("btf,fd->btd", act, lp["w_down"]),
                     cfg), None


def _residual(x, y, cfg):
    """x + residual_scale * y (a muP model scales every sub-block's
    result into the residual stream)."""
    if cfg.residual_scale == 1.0:
        return x + y
    return x + (y.astype(jnp.float32) * cfg.residual_scale).astype(x.dtype)


def embed_scaled(x, cfg):
    """The embedding rows as the first layer reads them."""
    if cfg.embed_scale == 1.0:
        return x
    return (x.astype(jnp.float32) * cfg.embed_scale).astype(x.dtype)


def _gated_output(o, gate, lp, attn, cfg):
    """What the output projection of a gated kind reads: the attention
    result, RMSNorm'd over all heads' dims where the kind has an output
    norm, times sigmoid(h Wg). Float32 until the product is made."""
    b, t, n_h, d_v = o.shape
    o = o.astype(jnp.float32).reshape(b, t, n_h * d_v)
    if attn in cfg.out_norm_kinds:
        o = rms_norm(o, lp["o_norm"], eps=cfg.rms_eps, force_jax=True)
    if gate is not None:
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32))
    return o.astype(cfg.compute_dtype).reshape(b, t, n_h, d_v)


def _short_conv(h, lp, attend, dt):
    """A conv layer's operator on the normed input ``h`` [b, t, d]:
    ``[B | C | z] = h W_in``, ``g = B * z``, ``c_t = sum_j w[:, j] *
    g_{t - (L - 1) + j}``, ``(C * c) W_out``. ``attend(g, None, None,
    "conv", None)`` does for g what it does for K/V elsewhere: it
    returns the L - 1 rows of g before the first of these (the slot's
    state; zeros at a prompt's start) and writes the state that follows
    the last VALID one. Elementwise work in float32."""
    d = h.shape[-1]
    flat = jnp.einsum("btd,df->btf", h, lp["in_proj"])
    b_in, c_in, z = flat[..., :d], flat[..., d:2 * d], flat[..., 2 * d:]
    g = (b_in.astype(jnp.float32) * z.astype(jnp.float32)).astype(dt)
    before = attend(g, None, None, "conv", None)           # [b, L - 1, d]
    rows = jnp.concatenate([before, g], axis=1).astype(jnp.float32)
    w = lp["conv_w"].astype(jnp.float32)                   # [d, L]
    t = g.shape[1]
    conv = sum(rows[:, j:j + t] * w[:, j] for j in range(w.shape[1]))
    y = (c_in.astype(jnp.float32) * conv).astype(dt)
    return jnp.einsum("btd,de->bte", y, lp["out_proj"])


def serve_layer(x, lp, attn, cfg, ropes, positions, attend, *,
                token_mask=None, count_mask=None):
    """THE decoder layer of inference: pre-norm attention of kind
    ``attn`` (its own KV head count and rope base; q/k width
    ``head_dim`` of which ``rot_dim`` rotate, v width ``v_dim`` scaled
    by ``v_scale``) or, for the kind ``conv``, the gated short
    convolution (``_short_conv``), then the layer's MLP by what ``lp``
    holds (dense SwiGLU or experts). ``attend(q, k_new, v_new, attn,
    sink) -> o`` writes the new rows into the caller's cache and reads
    it: the one thing ``advance``, decode and prefill do differently.
    Returns (x, counts): the expert layer's counters, None for a dense
    layer."""
    dt = cfg.compute_dtype
    h = rms_norm(x, lp["ln1"], eps=cfg.rms_eps).astype(dt)
    if attn == "conv":
        x = _residual(x, _short_conv(h, lp, attend, dt), cfg)
        return _mlp(x, lp, cfg, token_mask, count_mask)
    b, t, _ = x.shape
    n_h, h_kv = cfg.n_heads, cfg.kv_heads_of(attn)
    gate = None
    if lp["qkv"].ndim == 2:
        # layered: q|k|v fused on the feature axis, widths of their own
        # (and a gated kind's output gate behind them)
        flat = jnp.einsum("btd,df->btf", h, lp["qkv"])
        n_q, n_k = n_h * cfg.head_dim, h_kv * cfg.head_dim
        n_v = h_kv * cfg.v_dim
        q = flat[..., :n_q].reshape(b, t, n_h, cfg.head_dim)
        k_new = flat[..., n_q:n_q + n_k].reshape(b, t, h_kv, cfg.head_dim)
        v_new = flat[..., n_q + n_k:n_q + n_k + n_v].reshape(
            b, t, h_kv, cfg.v_dim)
        if attn in cfg.gated_kinds:
            gate = flat[..., n_q + n_k + n_v:]
        if cfg.v_scale != 1.0:
            v_new = (v_new.astype(jnp.float32) * cfg.v_scale).astype(dt)
    else:
        qkv = jnp.einsum("btd,dhk->bthk", h, lp["qkv"])
        q = qkv[:, :, :n_h]
        k_new = qkv[:, :, n_h:n_h + h_kv]
        v_new = qkv[:, :, n_h + h_kv:]
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], eps=cfg.rms_eps, force_jax=True)
        k_new = rms_norm(k_new, lp["k_norm"], eps=cfg.rms_eps,
                         force_jax=True)
    if attn not in cfg.no_rope_kinds:
        q = _rope(q, ropes[attn], positions, cfg.rot_dim)
        k_new = _rope(k_new, ropes[attn], positions, cfg.rot_dim)
    o = attend(q, k_new, v_new, attn, lp.get("sink"))
    if gate is not None or attn in cfg.out_norm_kinds:
        o = _gated_output(o, gate, lp, attn, cfg)
    x = _residual(x, jnp.einsum("bthk,hkd->btd", o, lp["wo"]), cfg)
    return _mlp(x, lp, cfg, token_mask, count_mask)


def run_layers(x, params, k_all, v_all, cfg, layer):
    """Every layer in model order. ``layer(x, lp, attn, at, k_all,
    v_all) -> (x, k_all, v_all, counts)`` with ``at`` the layer's index
    among the layers of its attention kind (a Python int in a layered
    model's static loop, traced under a uniform model's scan: how a
    caller's cache is indexed by it is the caller's). A uniform model: one
    ``lax.scan`` over the stacked layers, the caches as CARRY (as xs/ys
    the scan slices every layer's cache out and re-stacks it each call,
    the whole cache re-written per token; as carry a layer's update is
    one small aliased write). A layered model: a static loop over its
    tuple of layers. Returns (x, k_all, v_all, the expert layers'
    counters summed or None)."""
    if isinstance(params["layers"], tuple):
        seen: dict = {}
        total = None
        for lp, (attn, _) in zip(params["layers"], cfg.layer_kinds):
            at = seen.get(attn, 0)
            seen[attn] = at + 1
            x, k_all, v_all, counts = layer(x, lp, attn, at, k_all, v_all)
            if counts is not None:
                total = (counts if total is None
                         else jax.tree.map(jnp.add, total, counts))
        return x, k_all, v_all, total
    attn = cfg.layer_kinds[0][0]

    def body(carry, layer_in):
        x, k_all, v_all = carry
        lp, at = layer_in
        x, k_all, v_all, counts = layer(x, lp, attn, at, k_all, v_all)
        return (x, k_all, v_all), counts

    (x, k_all, v_all), counts = lax.scan(
        body, (x, k_all, v_all),
        (params["layers"], jnp.arange(cfg.n_layers)),
    )
    return x, k_all, v_all, jax.tree.map(lambda c: c.sum(0), counts)


def lm_head(x, params, cfg):
    """Final norm -> unembed (the embedding's transpose where the model
    ties them) -> float32 logits [B, V] of ``x`` [B, 1, d].
    Only one position per row is ever sampled: callers slice it out
    BEFORE this, so no [B, S, V] logits are ever materialized."""
    x = rms_norm(x, params["final_norm"],
                 eps=cfg.rms_eps).astype(cfg.compute_dtype)
    if cfg.logit_scale != 1.0:
        x = (x.astype(jnp.float32) * cfg.logit_scale).astype(x.dtype)
    if cfg.tie_embeddings:
        logits = jnp.einsum("btd,vd->btv", x, params["embed"])
    else:
        logits = jnp.einsum("btd,dv->btv", x, params["unembed"])
    return logits[:, 0].astype(jnp.float32)


def advance(params: dict, cache: dict, tokens: jax.Array,
            cfg: TransformerConfig, *, checked: bool = False,
            prefill: bool = False):
    """Feed ``tokens`` [B, S] at the cache's current length; returns
    (last-position logits [B, V] fp32, updated cache).

    Capacity contract under jit: with a traced ``cache["length"]`` the
    cumulative bound cannot be checked eagerly, and an overflowing
    ``dynamic_update_slice`` clamps its start index — wrong-position K/V,
    silently. Jitted callers must pre-validate their loop the way
    ``generate()`` does (prompt + max_new_tokens ≤ capacity), or pass
    ``checked=True`` and wrap the call in ``jax.experimental.checkify``
    to turn overflow into a checked runtime error.

    ``prefill=True`` (static) selects the flash-attention fast path for
    long prompts and PROMISES the cache is empty (length == 0): the flash
    branch attends only over the new tokens, so on a non-empty cache it
    would silently ignore all cached context. Checked eagerly for
    concrete lengths, via checkify with ``checked=True`` for traced
    ones."""
    cfg.refuse_layered("advance / generate")
    capacity = cache["k"].shape[2]
    if tokens.shape[1] > capacity:
        # RoPE tables and the cache are both static; overflow would clamp
        # indices and silently corrupt instead of erroring.
        raise ValueError(
            f"{tokens.shape[1]} tokens cannot fit a {capacity}-position "
            f"cache"
        )
    if not isinstance(cache["length"], jax.core.Tracer):
        # Eager incremental use (chat-style repeated advance calls): the
        # cumulative check is only possible with a concrete length — under
        # jit the caller owns capacity (generate() pre-validates its loop,
        # see the capacity contract in the docstring).
        if int(cache["length"]) + tokens.shape[1] > capacity:
            raise ValueError(
                f"cache at length {int(cache['length'])} cannot take "
                f"{tokens.shape[1]} more tokens (capacity {capacity})"
            )
        if prefill and int(cache["length"]) != 0:
            raise ValueError(
                f"prefill=True requires an empty cache, got length "
                f"{int(cache['length'])} — the flash prefill branch would "
                f"silently ignore the cached context"
            )
    elif checked:
        from jax.experimental import checkify

        checkify.check(
            cache["length"] + tokens.shape[1] <= capacity,
            "KV cache overflow: length {l} + {s} new tokens exceeds "
            "capacity {c}", l=cache["length"],
            s=jnp.int32(tokens.shape[1]), c=jnp.int32(capacity),
        )
        if prefill:
            checkify.check(
                cache["length"] == 0,
                "prefill=True on a non-empty cache (length {l})",
                l=cache["length"],
            )
    if not is_fused(params):
        # Raw training params from an eager caller: fuse per call (generate
        # fuses once, outside its token loop).
        params = decode_weights(params, cfg)
    dt = cfg.compute_dtype
    s = tokens.shape[1]
    length = cache["length"]
    positions = length + jnp.arange(s)
    # Global causal mask [1, S, Tmax]; it also hides the cache tail past
    # length + S (those positions are > every query position).
    mask = (positions[:, None] >= jnp.arange(capacity)[None, :])[None]
    ropes = rope_tables(cfg)
    x = params["embed"][tokens].astype(dt)

    def layer(x, lp, attn, at, k_all, v_all):
        # This caller's cache policy: the S new rows go in at
        # (layer, :, length) by a small ``dynamic_update_slice`` that XLA
        # aliases in place, and attention reads that layer's cache.
        at = jnp.int32(at)     # a layered model's loop counts in Python

        def attend(q, k_new, v_new, attn, sink):
            nonlocal k_all, v_all
            k_all = lax.dynamic_update_slice(
                k_all, k_new.astype(k_all.dtype)[None], (at, 0, length, 0, 0))
            v_all = lax.dynamic_update_slice(
                v_all, v_new.astype(v_all.dtype)[None], (at, 0, length, 0, 0))
            if prefill and s > 1:
                # Empty cache: self-attention over the prompt only (flash
                # handles the GQA head grouping internally); the dense
                # path's [S, Tmax] fp32 scores are quadratic-memory for
                # long prompts.
                return flash_attention(q, k_new, v_new, causal=True)
            return grouped_cache_attention(
                q, lax.dynamic_index_in_dim(k_all, at, 0, keepdims=False),
                lax.dynamic_index_in_dim(v_all, at, 0, keepdims=False),
                mask)

        x, _ = serve_layer(x, lp, attn, cfg, ropes, positions, attend)
        return x, k_all, v_all, None

    x, k_all, v_all, _ = run_layers(x, params, cache["k"], cache["v"], cfg,
                                    layer)
    logits = lm_head(x[:, -1:], params, cfg)
    new_cache = {"k": k_all, "v": v_all, "length": length + s}
    return logits, new_cache


def _sample(logits, temperature, top_k, top_p, key):
    """Greedy at temperature 0; else temperature sampling with optional
    top-k truncation and/or top-p (nucleus) filtering, both applied to the
    scaled logits before the categorical draw (the standard order:
    truncate, then renormalize implicitly via categorical-over-masked)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / temperature
    if top_k > 0 and top_k < scaled.shape[-1]:
        kth = lax.top_k(scaled, top_k)[0][..., -1:]
        scaled = jnp.where(scaled < kth, NEG_INF, scaled)
    if top_p < 1.0:
        # Mask tokens outside the smallest prefix of the sorted
        # distribution whose cumulative probability reaches top_p (the
        # first token always survives).
        sorted_logits = jnp.sort(scaled, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p                   # prefix BEFORE token
        threshold = jnp.min(
            jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
        )
        scaled = jnp.where(scaled < threshold, NEG_INF, scaled)
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


class GenerateResult(NamedTuple):
    """``generate(..., eos_id=)`` result: ``tokens`` [B, max_new_tokens]
    with every position from a row's first EOS onward forced to
    ``eos_id``, and ``lengths`` [B] — generated tokens up to and
    INCLUDING the EOS (``max_new_tokens`` when a row never stops).
    ``tokens[b, :lengths[b]]`` is row b's effective output."""

    tokens: jax.Array
    lengths: jax.Array


def generate(
    params: dict,
    prompt: jax.Array,
    cfg: TransformerConfig,
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    eos_token: int | None = None,
    eos_id: int | None = None,
    pad_token: int = 0,
    key: jax.Array | None = None,
) -> jax.Array | GenerateResult:
    """Autoregressive generation: prefill the prompt [B, T0], then decode
    ``max_new_tokens`` greedily (temperature 0) or by temperature sampling
    with optional ``top_k`` / ``top_p`` (nucleus) truncation. Returns the
    generated tokens [B, max_new_tokens].

    ``eos_id``: EOS-aware decoding. The loop carries a per-row done mask:
    finished rows stop sampling (their positions are forced to ``eos_id``)
    and the loop EXITS as soon as every row is done — a ``while_loop``
    with a dynamic trip count, so a batch whose rows all stop early stops
    paying for the full static horizon. Returns ``GenerateResult(tokens,
    lengths)``; unfinished rows still match the plain path token-for-token
    at a given step (the sampling key schedule is positional, and the
    categorical draw's noise is independent of other rows' logits).

    ``eos_token`` (legacy): positions after a sequence's first EOS come
    back as ``pad_token``. The masking is post-hoc: the loop still runs
    the full static horizon and finished sequences keep feeding their
    SAMPLED continuation internally — the mask only guarantees callers
    never see it. Mutually exclusive with ``eos_id``; serving-era callers
    want ``eos_id``.

    Two jitted executables: weight fusion (``decode_weights``) runs as its
    own dispatch, then the prefill+loop runs over the fused params. Fusing
    inside the loop jit is a trap — XLA sinks the loop-invariant concat
    into the while body and re-materializes it every token (measured 5
    extra DMA copies/step), so the split is deliberate."""
    b, t0 = prompt.shape
    if eos_token is not None and eos_id is not None:
        raise ValueError(
            "eos_token (post-hoc pad masking) and eos_id (done-mask early "
            "exit) are different contracts — pass one"
        )
    if t0 + max_new_tokens > cfg.max_seq:
        raise ValueError(
            f"prompt ({t0}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"cfg.max_seq ({cfg.max_seq}) — RoPE positions would clamp and "
            f"silently repeat"
        )
    if temperature != 0.0 and key is None:
        raise ValueError("temperature sampling needs an explicit PRNG key")
    if temperature == 0.0 and (top_k > 0 or top_p < 1.0):
        raise ValueError(
            "top_k/top_p truncate a SAMPLING distribution; greedy decoding "
            "(temperature=0) takes the argmax — set a temperature"
        )
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if key is None:
        key = jax.random.key(0)  # unused in greedy mode
    cfg.refuse_layered("generate")
    if not is_fused(params):
        params = _decode_weights_jit(params, cfg)
    if eos_id is not None:
        toks, lengths = _generate_loop_eos(
            params, prompt, cfg, max_new_tokens, temperature, top_k,
            top_p, key, jnp.int32(eos_id),
        )
        return GenerateResult(toks, lengths)
    toks = _generate_loop(params, prompt, cfg, max_new_tokens, temperature,
                          top_k, top_p, key)
    if eos_token is not None:
        seen = jnp.cumsum(
            (toks == eos_token).astype(jnp.int32), axis=1
        )
        # Keep the EOS itself (first position where the running count
        # becomes 1), pad everything after it.
        after = (seen - (toks == eos_token)) > 0
        toks = jnp.where(after, jnp.int32(pad_token), toks)
    return toks


@functools.partial(jax.jit, static_argnames=("cfg",))
def _decode_weights_jit(params: dict, cfg: TransformerConfig) -> dict:
    return decode_weights(params, cfg)


class DecodeSession:
    """Persistent serving session: fuse + downcast the weights ONCE and
    reuse the compiled generate loop across calls.

    ``generate()`` on raw training params re-runs the ``decode_weights``
    fusion every call — one extra jitted dispatch plus the fusion compute
    (once more than half the wall of a 128-token batch-8 call; not
    re-measured on the current chip). A served
    model pays fusion once; this class is that once. Subsequent calls
    dispatch only the cached ``_generate_loop`` executable.

        session = DecodeSession(params, cfg)
        out = session.generate(prompt, max_new_tokens=128)

    Call ``refresh(params)`` after a training step to re-fuse updated
    weights (e.g. periodic eval generation mid-training).

    **Sharded serving**: pass ``mesh=`` (a ``build_mesh`` result, e.g.
    ``MeshSpec(tp=4)``) and the fused weights are placed under
    ``decode_param_specs`` (heads/ff/vocab megatron-split over tp, experts
    over ep) and every ``generate`` runs inside the mesh context, with the
    KV cache sharded batch-over-dp / kv-heads-over-tp (``_cache_spec``).
    This is the serve-in-place path for models too big for one chip — the
    r4 TP-decode GSPMD parity test promoted to API surface."""

    def __init__(
        self, params: dict, cfg: TransformerConfig, mesh=None
    ) -> None:
        self.cfg = cfg
        self.mesh = mesh
        self.params: dict = {}
        # Compile instrumentation (parallel/plan.py): each distinct
        # generate signature compiles one executable; its first call is
        # timed and counted as a persistent-cache hit or miss.
        self._compiled: set[tuple] = set()
        # Measured-autotuner consumption: a persisted decode record for
        # this (config, topology, jax version) pins flash-attention
        # block sizes for the prefill pass; None on any miss.
        from tony_tpu.parallel import autotune as autotune_lib

        tuned = autotune_lib.lookup("decode_generate", config=cfg,
                                    mesh=mesh)
        if tuned is not None and (tuned.block_q or tuned.block_k):
            from tony_tpu.ops import attention as attention_lib

            attention_lib.set_tuned_blocks(tuned.block_q, tuned.block_k)
        self.refresh(params)

    def refresh(self, params: dict) -> None:
        """Re-fuse from (possibly updated) training params; accepts
        already-fused layouts as-is. Under a mesh, (re-)place the fused
        weights to their serving shardings."""
        if is_fused(params):
            fused = params
        elif self.mesh is not None:
            with jax.sharding.set_mesh(self.mesh):
                fused = _decode_weights_jit(params, self.cfg)
        else:
            fused = _decode_weights_jit(params, self.cfg)
        if self.mesh is not None:
            shardings = self._serving_shardings(fused)
            local = jax.process_index()
            if all(d.process_index == local
                   for d in self.mesh.devices.flat):
                fused = jax.device_put(fused, shardings)
            else:
                # Multi-process serving mesh: plain device_put of
                # differing per-process values is the known-flaky path
                # (build-state trap: "multihost device_put flaky");
                # a jitted identity with out_shardings is the blessed
                # global-array reshard.
                with jax.sharding.set_mesh(self.mesh):
                    fused = jax.jit(  # tony: noqa[TONY-X001] — one-shot reshard at weight refresh, not a step path
                        lambda x: x, out_shardings=shardings
                    )(fused)
        self.params = fused

    def _serving_shardings(self, fused: dict):
        """NamedShardings from ``decode_param_specs`` with the same
        divisibility fallback as training placement: any dim its mesh
        axis doesn't divide replicates instead of erroring."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        specs = decode_param_specs(self.cfg)

        def place(spec, leaf):
            fixed = [
                a if a is None or dim % self.mesh.shape[a] == 0 else None
                for a, dim in zip(spec, leaf.shape)
            ]
            return NamedSharding(self.mesh, P(*fixed))

        return jax.tree.map(
            place, specs, fused,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
        )

    def generate(self, prompt: jax.Array, max_new_tokens: int, **kwargs):
        """Same surface as module-level ``generate`` minus params/cfg."""
        # EVERY kwarg joins the signature: eos_token and the rest change
        # the traced program too, and a missed distinction would leave a
        # real compile uncounted (a false hit), never a wrong result.
        sig = (
            tuple(prompt.shape), str(prompt.dtype), max_new_tokens,
            tuple(sorted((k, repr(v)) for k, v in kwargs.items())),
        )
        if sig not in self._compiled:
            from tony_tpu.parallel import plan as plan_lib

            key = plan_lib.plan_cache_key(
                "decode_generate", config=self.cfg, mesh=self.mesh,
                extra={"sig": repr(sig)},
            )
            with plan_lib.timed_compile(key):
                out = self._generate(prompt, max_new_tokens, **kwargs)
            # Marked compiled only on success: a failed first call must
            # not exempt the next one from instrumentation.
            self._compiled.add(sig)
            return out
        return self._generate(prompt, max_new_tokens, **kwargs)

    def _generate(self, prompt: jax.Array, max_new_tokens: int, **kwargs):
        if self.mesh is not None:
            with jax.sharding.set_mesh(self.mesh):
                return generate(
                    self.params, prompt, self.cfg, max_new_tokens, **kwargs
                )
        return generate(
            self.params, prompt, self.cfg, max_new_tokens, **kwargs
        )


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_new_tokens", "temperature", "top_k",
                     "top_p"),
)
def _generate_loop(
    params: dict,
    prompt: jax.Array,
    cfg: TransformerConfig,
    max_new_tokens: int,
    temperature: float,
    top_k: int,
    top_p: float,
    key: jax.Array,
) -> jax.Array:
    b, t0 = prompt.shape
    if max_new_tokens == 0:
        return jnp.zeros((b, 0), jnp.int32)
    cache = init_cache(cfg, b, t0 + max_new_tokens)
    logits, cache = advance(params, cache, prompt, cfg, prefill=True)
    keys = jax.random.split(key, max_new_tokens)
    # Sample token 0 from the prefill logits, then advance-and-sample
    # max_new_tokens - 1 times: the last sampled token is never fed back,
    # so no trailing forward pass computes logits nobody reads.
    tok0 = _sample(logits, temperature, top_k, top_p, keys[0])

    def step(carry, step_key):
        cache, tok = carry
        logits, cache = advance(params, cache, tok[:, None], cfg)
        nxt = _sample(logits, temperature, top_k, top_p, step_key)
        return (cache, nxt), nxt

    (_, _), toks = lax.scan(step, (cache, tok0), keys[1:])
    return jnp.concatenate([tok0[:, None], toks.T], axis=1)  # [B, N]


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_new_tokens", "temperature", "top_k",
                     "top_p"),
)
def _generate_loop_eos(
    params: dict,
    prompt: jax.Array,
    cfg: TransformerConfig,
    max_new_tokens: int,
    temperature: float,
    top_k: int,
    top_p: float,
    key: jax.Array,
    eos_id: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """EOS-aware twin of ``_generate_loop``: a ``while_loop`` carrying a
    per-row done mask that exits when every row has emitted ``eos_id``
    (or the horizon runs out). Shapes stay static — the output buffer is
    the full [B, max_new_tokens], pre-filled with ``eos_id`` so
    never-written tail positions already carry the forced value — only
    the TRIP COUNT is dynamic, which is where the saving lives: a batch
    of short answers stops advancing the model the step its last row
    finishes. ``eos_id`` rides as a traced scalar so changing it never
    recompiles.

    Key schedule parity: ``keys[i]`` is indexed by absolute step, and
    the categorical draw's Gumbel noise is keyed per (row, vocab)
    position — so a still-running row samples exactly what the plain
    scan path would have sampled at that step, even though finished
    rows now feed ``eos_id`` instead of their sampled continuation."""
    b, t0 = prompt.shape
    if max_new_tokens == 0:
        return (jnp.zeros((b, 0), jnp.int32), jnp.zeros((b,), jnp.int32))
    cache = init_cache(cfg, b, t0 + max_new_tokens)
    logits, cache = advance(params, cache, prompt, cfg, prefill=True)
    keys = jax.random.split(key, max_new_tokens)
    tok0 = _sample(logits, temperature, top_k, top_p, keys[0])
    done0 = tok0 == eos_id
    out0 = jnp.full((b, max_new_tokens), eos_id, jnp.int32)
    out0 = lax.dynamic_update_slice(out0, tok0[:, None], (0, 0))
    lengths0 = jnp.ones((b,), jnp.int32)

    def cond(carry):
        _, _, done, _, _, i = carry
        return (i < max_new_tokens) & ~jnp.all(done)

    def body(carry):
        cache, tok, done, out, lengths, i = carry
        logits, cache = advance(params, cache, tok[:, None], cfg)
        step_key = lax.dynamic_index_in_dim(keys, i, 0, keepdims=False)
        nxt = _sample(logits, temperature, top_k, top_p, step_key)
        nxt = jnp.where(done, eos_id, nxt)
        out = lax.dynamic_update_slice(out, nxt[:, None], (0, i))
        lengths = jnp.where(done, lengths, i + 1)
        done = done | (nxt == eos_id)
        return (cache, nxt, done, out, lengths, i + 1)

    _, _, _, out, lengths, _ = lax.while_loop(
        cond, body, (cache, tok0, done0, out0, lengths0, jnp.int32(1))
    )
    return out, lengths
