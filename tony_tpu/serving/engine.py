"""Device half of the continuous-batching serving engine.

The single-shot ``generate`` path compiles one executable per (batch,
prompt width, horizon) signature and runs every row to the full static
horizon — fine for eval generation, a throughput wall for serving
(on the earlier platform a ``generate`` call's wall rate sat at under
half of what its marginal decode step sustained; the kernel is fine, the
orchestration is the tax — not re-measured on the current chip). This module is the orchestration fix: TWO
executables total, compiled once per engine lifetime, shared by every
request that ever passes through —

* ``decode_step`` — ONE token for ALL slots. The slot batch is a fixed
  [S] lane array; each slot owns a row of the stacked KV cache
  [L, S, Tmax, Hkv, Dh], its own position, and its own sampling
  temperature, so requests of different lengths share every decode
  iteration (Orca-style iteration-level scheduling). Per-slot cache
  writes are a vmapped ``dynamic_update_slice`` at each slot's own
  offset; attention masks per row with ``key_index <= pos[slot]``.
* ``prefill_chunk`` — a bounded chunk of ONE request's prompt into its
  slot's cache row. Chunking bounds how long a new prompt can stall the
  in-flight decode streams: the host interleaves one chunk per engine
  iteration, so time-to-first-token for the new request trades off
  against inter-token latency for everyone else at a fixed, configured
  granularity (``tony.serving.prefill-chunk``).

Both run over the fused ``decode_weights`` layout (weights fuse once per
engine, exactly like ``DecodeSession``) and carry the stacked caches as
scan CARRY (the xs/ys re-stack cost decode.py's docstring documents).
KV buffers are donated, so the two big cache arrays update in place.

Overwrite-before-read invariant: slot reuse never zeroes a cache row.
A freed slot's stale K/V rows are only ever unmasked after the new
request's own prefill/decode has written those positions (prefill
covers [0, P); each decode step writes index ``pos`` before attention
reads it), so stale data is structurally unreadable.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.models.decode import NEG_INF, _moe_mlp_decode
from tony_tpu.models.transformer import TransformerConfig
from tony_tpu.ops import apply_rope, rms_norm, rope_frequencies


class QuantizedKV(NamedTuple):
    """An int8-quantized KV cache buffer (``tony.tune.kv-quant=int8``):
    per-(position, kv-head) symmetric absmax quantization over the head
    dim — ``data * scale`` reconstructs the stored vectors. Decode is
    bandwidth-bound, so halving (vs bf16) the KV bytes read per step is
    the biggest serving-throughput lever; the scale plane adds
    1/head_dim overhead. A NamedTuple so the pair rides jit/donation as
    an ordinary pytree — the cache TYPE is part of the executable's
    trace, never a runtime branch."""

    data: jax.Array   # int8  [..., Dh]
    scale: jax.Array  # f32   [..., 1]


# One cache buffer is either a plain array (kv_quant="none") or a
# QuantizedKV. These helpers keep decode_window/prefill_chunks agnostic.


def _quantize(x: jax.Array) -> QuantizedKV:
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                     keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    data = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale), -127, 127
    ).astype(jnp.int8)
    return QuantizedKV(data, scale)


def _materialize(cache, dt) -> jax.Array:
    """Cache rows in compute dtype: identity for a plain buffer (the
    stored-dtype einsum path keeps its fp32 MXU accumulation), dequant
    for int8."""
    if isinstance(cache, QuantizedKV):
        return (cache.data.astype(jnp.float32) * cache.scale).astype(dt)
    return cache


def _cache_tmax(cache) -> int:
    return (cache.data if isinstance(cache, QuantizedKV) else cache).shape[2]


def _cache_layer(cache, layer):
    """One layer's rows [S, Tmax, Hkv, Dh] out of the stacked buffer."""
    if isinstance(cache, QuantizedKV):
        return QuantizedKV(
            lax.dynamic_index_in_dim(cache.data, layer, 0, keepdims=False),
            lax.dynamic_index_in_dim(cache.scale, layer, 0, keepdims=False),
        )
    return lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)


def _cache_store_layer(cache, layer_cache, layer):
    if isinstance(cache, QuantizedKV):
        return QuantizedKV(
            lax.dynamic_update_slice(
                cache.data, layer_cache.data[None], (layer, 0, 0, 0, 0)
            ),
            lax.dynamic_update_slice(
                cache.scale, layer_cache.scale[None], (layer, 0, 0, 0, 0)
            ),
        )
    return lax.dynamic_update_slice(
        cache, layer_cache[None], (layer, 0, 0, 0, 0)
    )


def _cache_gather(layer_cache, slots):
    if isinstance(layer_cache, QuantizedKV):
        return QuantizedKV(layer_cache.data[slots], layer_cache.scale[slots])
    return layer_cache[slots]


def _write_rows(layer_cache, new, wpos):
    """Per-slot vmapped write of ``new`` [S, 1, Hkv, Dh] at each slot's
    own offset (decode's one-token append)."""
    write = jax.vmap(
        lambda row, val, p: lax.dynamic_update_slice(row, val, (p, 0, 0))
    )
    if isinstance(layer_cache, QuantizedKV):
        q = _quantize(new)
        return QuantizedKV(
            write(layer_cache.data, q.data, wpos),
            write(layer_cache.scale, q.scale, wpos),
        )
    return write(layer_cache, new.astype(layer_cache.dtype), wpos)


def _write_chunk(layer_cache, chunk, at):
    """One prefill chunk [1, C, Hkv, Dh] at (slot, start, 0, 0)."""
    if isinstance(layer_cache, QuantizedKV):
        q = _quantize(chunk)
        return QuantizedKV(
            lax.dynamic_update_slice(layer_cache.data, q.data, at),
            lax.dynamic_update_slice(layer_cache.scale, q.scale, at),
        )
    return lax.dynamic_update_slice(
        layer_cache, chunk.astype(layer_cache.dtype), at
    )


def init_slot_cache(
    cfg: TransformerConfig, slots: int, max_len: int,
    kv_quant: str = "none",
):
    """Zeroed stacked KV cache pair [L, S, Tmax, Hkv, Dh] — one row per
    slot, sized once for the engine's lifetime. Serving HBM budget is
    2 · L · S · Tmax · Hkv · Dh · dtype bytes (``kv_quant="int8"``:
    1 + 4/Dh bytes per element instead of the compute dtype's 2); see
    docs/DEPLOY.md "Serving" for the sizing table and "Autotuning" for
    the quantization contract."""
    shape = (cfg.n_layers, slots, max_len, cfg.kv_heads, cfg.head_dim)
    if kv_quant == "int8":
        def one():
            return QuantizedKV(
                jnp.zeros(shape, jnp.int8),
                jnp.zeros(shape[:-1] + (1,), jnp.float32),
            )
        return one(), one()
    if kv_quant not in ("none", "", None):
        raise ValueError(f"unknown kv_quant mode {kv_quant!r}")
    dt = cfg.compute_dtype
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)


def cache_inject_rows(cache, slot: int, rows) -> "jax.Array | QuantizedKV":
    """Host-side write of FLOAT rows [L, P, Hkv, Dh] into one slot's
    prefix (the inject half of prefill/decode disaggregation). The
    cross-replica exchange format is always float — quantization is a
    per-engine storage decision, so a bf16 prefill replica can feed an
    int8 decode replica and vice versa."""
    p = rows.shape[1]
    if isinstance(cache, QuantizedKV):
        q = _quantize(jnp.asarray(rows, jnp.float32))
        return QuantizedKV(
            cache.data.at[:, slot, :p].set(q.data),
            cache.scale.at[:, slot, :p].set(q.scale),
        )
    return cache.at[:, slot, :p].set(jnp.asarray(rows, cache.dtype))


def cache_export_rows(cache, slot: int, length: int) -> jax.Array:
    """One slot's KV prefix as float rows [L, length, Hkv, Dh] — the
    export half of the exchange contract ``cache_inject_rows``
    documents (int8 storage dequantizes on the way out)."""
    if isinstance(cache, QuantizedKV):
        return _materialize(
            QuantizedKV(cache.data[:, slot, :length],
                        cache.scale[:, slot, :length]),
            jnp.float32,
        )
    return cache[:, slot, :length]


def _mlp(x, lp, cfg):
    """SwiGLU over the fused gate|up projection, or the dense MoE
    mixture for expert trunks — the same math as decode's
    ``_layer_decode`` MLP half (serving always takes the dense mixture:
    the measured winner at decode batch sizes, see decode.py)."""
    dt = cfg.compute_dtype
    if "router" in lp:
        return x + _moe_mlp_decode(x, lp, cfg)
    hn = rms_norm(x, lp["ln2"]).astype(dt)
    gu = jnp.einsum("btd,df->btf", hn, lp["gate_up"])
    f = gu.shape[-1] // 2
    act = (
        jax.nn.silu(gu[..., :f].astype(jnp.float32)).astype(dt)
        * gu[..., f:]
    )
    return x + jnp.einsum("btf,fd->btd", act, lp["w_down"])


def _attend_cache(q, k_cache, v_cache, mask, cfg):
    """Grouped attention against cache rows — q regrouped
    [B, S, Hkv, G, Dh] so GQA never head-repeats the cache, stored-dtype
    reads with fp32 MXU accumulation and fp32 softmax (the decode.py
    recipe). mask: [B, S_q, T] True where the key is visible."""
    dt = cfg.compute_dtype
    b, s, n_h, _ = q.shape
    h_kv = k_cache.shape[2]
    g = n_h // h_kv
    scale = cfg.head_dim ** -0.5
    qg = q.reshape(b, s, h_kv, g, cfg.head_dim)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k_cache,
        preferred_element_type=jnp.float32,
    ) * scale
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "bhgqk,bkhd->bqhgd", probs.astype(dt), v_cache,
        preferred_element_type=jnp.float32,
    ).astype(dt).reshape(b, s, n_h, cfg.head_dim)


def _sample_slots(logits, temp, key):
    """Per-slot sampling: greedy where ``temp == 0``, else temperature
    sampling. One key serves the whole slot batch — the Gumbel noise
    tensor is keyed per (row, vocab) position, so each row's draw is
    independent of every other row's logits. The categorical branch
    hides behind ``lax.cond``: threefry over [S, V] costs ~16% of a
    micro decode step on CPU, and an all-greedy slot batch (the common
    serving default) must not pay it."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sample(_):
        scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
        drawn = jax.random.categorical(key, scaled, axis=-1).astype(
            jnp.int32
        )
        return jnp.where(temp > 0.0, drawn, greedy)

    return lax.cond(jnp.any(temp > 0.0), sample, lambda _: greedy, None)


@functools.partial(
    jax.jit, static_argnames=("cfg", "steps"), donate_argnums=(1, 2)
)
def decode_window(params, k_all, v_all, pos, wpos, tokens, temp,
                  base_key, draw0, cfg: TransformerConfig,
                  steps: int = 1):
    """``steps`` decode iterations for every slot in ONE dispatch: feed
    ``tokens`` [S] at each slot's own ``pos``, write the new K/V row at
    ``wpos``, attend the slot's cache prefix, sample the next token per
    slot, advance, repeat. ``steps`` is the host-sync window — the
    throughput/latency knob (``tony.serving.decode-window``): 1 keeps
    admission and EOS retirement exactly per-token; a deeper window
    amortizes the per-dispatch host cost over ``steps`` tokens at the
    price of up to ``steps - 1`` wasted lane-steps per retiring stream
    (measured on the CPU micro bench: host dispatch + PRNG fold cost
    ~2× the model step itself at window 1).

    pos/wpos/temp live on the HOST between windows (tiny [S] arrays;
    the scheduler mutates them freely on admit/retire) and ride in as
    arguments; only the KV caches are device-resident state (donated —
    the caller must adopt the returned buffers). Sampling keys derive
    INSIDE the jit (``fold_in(base_key, draw0 + i)`` — a host-side
    fold_in is a whole extra dispatch per iteration), so the schedule
    is positional and reproducible from (seed, draw counter).

    Inactive slots still compute (the lane array is fixed) and still
    WRITE — the scheduler parks their ``wpos`` at ``Tmax - 1``, the one
    index the overwrite-before-read invariant protects unconditionally.
    Parking matters: an inactive lane writing at its stale ``pos``
    would clobber cache rows a CONCURRENT prefill into that slot
    already filled (the measured parity break that introduced
    ``wpos``). For active slots ``wpos == pos``; past a stream's
    retirement point mid-window its writes clamp at ``Tmax - 1`` too.

    Returns (k_all, v_all, window_tokens [S, steps] int32).
    """
    dt = cfg.compute_dtype
    t_max = _cache_tmax(k_all)
    n_h, h_kv = cfg.n_heads, cfg.kv_heads
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq,
                                theta=cfg.rope_theta)

    def one_step(carry, i):
        k_all, v_all, pos, wpos, tokens = carry
        x = params["embed"][tokens][:, None, :].astype(dt)  # [S, 1, d]
        # Visibility after the write: keys 0..pos inclusive (index pos
        # holds the token being fed this step). Inactive lanes' pos can
        # run past the table mid-window — clamp the RoPE gather (their
        # output is discarded; the mask itself cannot overflow).
        rp = jnp.minimum(pos, cfg.max_seq - 1)[:, None]
        mask = jnp.arange(t_max)[None, :] <= pos[:, None]   # [S, T]

        def body(carry, layer_in):
            x, k_all, v_all = carry
            lp, layer = layer_in
            h = rms_norm(x, lp["ln1"]).astype(dt)
            qkv = jnp.einsum("btd,dhk->bthk", h, lp["qkv"])
            q = qkv[:, :, :n_h]
            k_new = qkv[:, :, n_h:n_h + h_kv]
            v_new = qkv[:, :, n_h + h_kv:]
            q = apply_rope(q, cos, sin, positions=rp)
            k_new = apply_rope(k_new, cos, sin, positions=rp)
            k_layer = _cache_layer(k_all, layer)
            v_layer = _cache_layer(v_all, layer)
            k_layer = _write_rows(k_layer, k_new, wpos)
            v_layer = _write_rows(v_layer, v_new, wpos)
            k_all = _cache_store_layer(k_all, k_layer, layer)
            v_all = _cache_store_layer(v_all, v_layer, layer)
            o = _attend_cache(
                q, _materialize(k_layer, dt), _materialize(v_layer, dt),
                mask[:, None, :], cfg,
            )
            x = x + jnp.einsum("bthk,hkd->btd", o.astype(dt), lp["wo"])
            x = _mlp(x, lp, cfg)
            return (x, k_all, v_all), None

        (x, k_all, v_all), _ = lax.scan(
            body, (x, k_all, v_all),
            (params["layers"], jnp.arange(cfg.n_layers)),
        )
        x = rms_norm(x[:, -1:], params["final_norm"]).astype(dt)
        logits = jnp.einsum(
            "btd,dv->btv", x, params["unembed"]
        )[:, 0].astype(jnp.float32)
        nxt = _sample_slots(
            logits, temp, jax.random.fold_in(base_key, draw0 + i)
        )
        pos = pos + 1
        wpos = jnp.minimum(wpos + 1, t_max - 1)
        return (k_all, v_all, pos, wpos, nxt), nxt

    (k_all, v_all, _, _, _), toks = lax.scan(
        one_step, (k_all, v_all, pos, wpos, tokens), jnp.arange(steps)
    )
    return k_all, v_all, toks.T  # [S, steps]


@functools.partial(
    jax.jit, static_argnames=("cfg",), donate_argnums=(1, 2)
)
def prefill_chunks(params, k_all, v_all, tokens, slots, starts, n_valids,
                   temps, base_key, draw, cfg: TransformerConfig):
    """Prefill one chunk for EACH of P pending slots in one dispatch:
    ``tokens`` [P, C] row i is written into slot ``slots[i]`` at
    positions [starts[i], starts[i] + C). Batching the pending slots is
    the prefill twin of the slot-batch decode step — per-chunk batch-1
    dispatches measured ~3× the comparator's batched-prefill wall on
    the CPU micro bench (fixed dispatch + op overhead per chunk), and
    on TPU a [1, C] chunk cannot fill the MXU.

    The host guarantees distinct slots per batch and ``start + C <=
    Tmax``; it PADS short batches by duplicating row 0 — the duplicate
    rewrites identical K/V (idempotent), so one executable serves every
    pending count. Padded tails past ``n_valids[i]`` write garbage the
    overwrite-before-read invariant keeps unreadable.

    Returns (k_all, v_all, first_tokens [P], logits [P, V] fp32): row
    i's token samples from position ``n_valids[i] - 1`` — meaningful
    only on a request's FINAL chunk (earlier chunks' sample is
    discarded by the scheduler; computing it unconditionally keeps one
    executable)."""
    dt = cfg.compute_dtype
    p, c = tokens.shape
    t_max = _cache_tmax(k_all)
    n_h, h_kv = cfg.n_heads, cfg.kv_heads
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq,
                                theta=cfg.rope_theta)
    positions = starts[:, None] + jnp.arange(c)[None, :]       # [P, C]
    # Padded tail positions can run past the RoPE table; clamp the
    # gather (values are garbage, discarded) — the write offset itself
    # is host-validated.
    rope_pos = jnp.minimum(positions, cfg.max_seq - 1)
    x = params["embed"][tokens].astype(dt)                     # [P, C, d]
    mask = (positions[:, :, None]
            >= jnp.arange(t_max)[None, None, :])               # [P, C, T]

    def body(carry, layer_in):
        x, k_all, v_all = carry
        lp, layer = layer_in
        h = rms_norm(x, lp["ln1"]).astype(dt)
        qkv = jnp.einsum("btd,dhk->bthk", h, lp["qkv"])
        q = qkv[:, :, :n_h]
        k_new = qkv[:, :, n_h:n_h + h_kv]
        v_new = qkv[:, :, n_h + h_kv:]
        q = apply_rope(q, cos, sin, positions=rope_pos)
        k_new = apply_rope(k_new, cos, sin, positions=rope_pos)
        k_layer = _cache_layer(k_all, layer)
        v_layer = _cache_layer(v_all, layer)

        def write_one(i, kv):
            k_l, v_l = kv
            kc = lax.dynamic_index_in_dim(k_new, i, 0)   # [1, C, Hkv, Dh]
            vc = lax.dynamic_index_in_dim(v_new, i, 0)
            at = (slots[i], starts[i], 0, 0)
            return _write_chunk(k_l, kc, at), _write_chunk(v_l, vc, at)

        # Sequential writes, not a vmap-scatter: P is small and
        # duplicate (padding) rows must overwrite cleanly in order.
        k_layer, v_layer = lax.fori_loop(0, p, write_one,
                                         (k_layer, v_layer))
        k_all = _cache_store_layer(k_all, k_layer, layer)
        v_all = _cache_store_layer(v_all, v_layer, layer)
        o = _attend_cache(
            q, _materialize(_cache_gather(k_layer, slots), dt),
            _materialize(_cache_gather(v_layer, slots), dt), mask, cfg,
        )
        x = x + jnp.einsum("bthk,hkd->btd", o.astype(dt), lp["wo"])
        x = _mlp(x, lp, cfg)
        return (x, k_all, v_all), None

    (x, k_all, v_all), _ = lax.scan(
        body, (x, k_all, v_all),
        (params["layers"], jnp.arange(cfg.n_layers)),
    )
    last = jnp.take_along_axis(
        x, jnp.maximum(n_valids - 1, 0)[:, None, None], axis=1
    )                                                          # [P, 1, d]
    last = rms_norm(last, params["final_norm"]).astype(dt)
    logits = jnp.einsum(
        "btd,dv->btv", last, params["unembed"]
    )[:, 0].astype(jnp.float32)
    toks = _sample_slots(logits, temps,
                         jax.random.fold_in(base_key, draw))
    return k_all, v_all, toks, logits
