"""Device half of the continuous-batching serving engine.

The single-shot ``generate`` path compiles one executable per (batch,
prompt width, horizon) signature and runs every row to the full static
horizon — fine for eval generation, a throughput wall for serving
(on the earlier platform a ``generate`` call's wall rate sat at under
half of what its marginal decode step sustained; the kernel is fine, the
orchestration is the tax — not re-measured on the current chip). This module is the orchestration fix: TWO
executables total, compiled once per engine lifetime, shared by every
request that ever passes through —

* ``decode_window`` — ``steps`` tokens for ALL slots in one dispatch.
  The slot batch is a fixed [S] lane array; each slot owns a row of the
  stacked KV cache [L, S, Tmax, Hkv, Dh], its own position, and its own
  sampling temperature, so requests of different lengths share every
  decode iteration (Orca-style iteration-level scheduling). Each layer
  scatters the slots' new K/V rows into the stacked buffer at
  (layer, slot, wpos[slot]) and attention reads that layer where it
  lies (``ops.cache_decode_attention``), masked per slot with
  ``key_index <= pos[slot]``.
* ``prefill_chunks`` — one bounded chunk of the prompt of EACH of up to
  P pending slots into those slots' cache rows. Chunking bounds how long
  a new prompt can stall the in-flight decode streams: the host
  interleaves one round per engine iteration, so time-to-first-token for
  the new requests trades off against inter-token latency for everyone
  else at a fixed, configured granularity
  (``tony.serving.prefill-chunk``). Each layer writes the P chunks by
  ``dynamic_update_slice`` at (layer, slot, start) and attends over
  those P slots' rows only.

Both run over the fused ``decode_weights`` layout (weights fuse once per
engine, exactly like ``DecodeSession``) and carry the stacked caches as
scan CARRY (the xs/ys re-stack cost decode.py's docstring documents).
KV buffers are donated and no program ever holds a layer's slab apart
from them: per dispatch the cache takes S · Hkv · Dh elements per layer
per buffer in decode and P · C · Hkv · Dh in prefill (2 MB and 8 MB
over 16 layers of 8 × 128 bf16 heads at 32 slots, 4 × 32-token chunks),
and gives one pass over the layer's S · Tmax rows in decode (4.3 GB),
over P · Tmax in prefill (0.5 GB).

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

from tony_tpu.models.decode import _moe_mlp_decode
from tony_tpu.models.transformer import TransformerConfig
from tony_tpu.ops import (
    apply_rope,
    cache_decode_attention,
    grouped_cache_attention,
    rms_norm,
    rope_frequencies,
)


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


# The cache interface of decode_window / prefill_chunks: rows are written
# into the stacked [L, S, Tmax, Hkv, ·] buffer where they lie, and a
# layer is read out of that same buffer. ``jax.tree.map`` over the cache
# runs each plane of a QuantizedKV and a plain array through one path.


def _encode(cache, x):
    """``x`` in the cache's storage form (the cache's own pytree)."""
    if isinstance(cache, QuantizedKV):
        return _quantize(x)
    return x.astype(cache.dtype)


def _write_rows(cache, layer, rows, wpos):
    """Decode's one-token append: ``rows`` [S, Hkv, Dh] land at
    (layer, slot, wpos[slot]) by one scatter per plane. The indices are
    sorted and unique (every slot is its own row of the buffer) and the
    host keeps ``wpos`` inside [0, Tmax)."""
    slot = jnp.arange(wpos.shape[0])
    return jax.tree.map(
        lambda buf, val: buf.at[layer, slot, wpos].set(
            val, indices_are_sorted=True, unique_indices=True,
            mode="promise_in_bounds",
        ),
        cache, _encode(cache, rows),
    )


def _write_chunk(cache, layer, slot, start, chunk):
    """One prefill chunk [C, Hkv, Dh] at (layer, slot, start)."""
    return jax.tree.map(
        lambda buf, val: lax.dynamic_update_slice(
            buf, val[None, None], (layer, slot, start, 0, 0)
        ),
        cache, _encode(cache, chunk),
    )


def _layer_view(cache, layer, dt):
    """(stack, index) under which decode attention reads one layer in
    compute dtype: a plain cache is the stack, read where it lies; an
    int8 cache dequantizes the layer into a one-layer stack."""
    if isinstance(cache, QuantizedKV):
        one = jax.tree.map(
            lambda buf: lax.dynamic_slice_in_dim(buf, layer, 1, 0), cache
        )
        return _materialize(one, dt), jnp.int32(0)
    return cache, layer


def _read_slots(cache, layer, slots, dt):
    """The rows [P, Tmax, Hkv, Dh] of ``slots`` [P] in one layer, in
    compute dtype: P small dynamic slices (on the TPU a gather over the
    stacked buffer lowers to slices of the WHOLE buffer)."""
    def take(buf):
        return jnp.concatenate([
            lax.dynamic_slice(
                buf, (layer, slots[i], 0, 0, 0), (1, 1) + buf.shape[2:]
            )[0]
            for i in range(slots.shape[0])
        ])

    return _materialize(jax.tree.map(take, cache), dt)


def init_slot_cache(
    cfg: TransformerConfig, slots: int, max_len: int,
    kv_quant: str = "none",
):
    """Zeroed stacked KV cache pair [L, S, Tmax, Hkv, Dh] — one row per
    slot, sized once for the engine's lifetime, donated to every dispatch
    and rewritten in place a few rows at a time (module docstring).
    Serving HBM budget is 2 · L · S · Tmax · Hkv · Dh · dtype bytes
    (``kv_quant="int8"``: 1 + 4/Dh bytes per element instead of the
    compute dtype's 2); see docs/DEPLOY.md "Serving" for the sizing table
    and "Autotuning" for the quantization contract."""
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
            k_all = _write_rows(k_all, layer, k_new[:, 0], wpos)
            v_all = _write_rows(v_all, layer, v_new[:, 0], wpos)
            k_stack, at = _layer_view(k_all, layer, dt)
            v_stack, _ = _layer_view(v_all, layer, dt)
            o = cache_decode_attention(
                q[:, 0], k_stack, v_stack, at, pos
            )[:, None]
            x = x + jnp.einsum("bthk,hkd->btd", o, lp["wo"])
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

        def write_one(i, kv):
            at = (layer, slots[i], starts[i])
            return (_write_chunk(kv[0], *at, k_new[i]),
                    _write_chunk(kv[1], *at, v_new[i]))

        # Sequential writes, not a vmap-scatter: P is small and
        # duplicate (padding) rows must overwrite cleanly in order.
        k_all, v_all = lax.fori_loop(0, p, write_one, (k_all, v_all))
        o = grouped_cache_attention(
            q, _read_slots(k_all, layer, slots, dt),
            _read_slots(v_all, layer, slots, dt), mask,
        )
        x = x + jnp.einsum("bthk,hkd->btd", o, lp["wo"])
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
