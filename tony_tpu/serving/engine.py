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
  those P slots only: where the round's float32 scores against the
  whole reservation fit at once (``SCORES_LIMIT``), over the slots'
  rows read out, in one batched product; where they do not (a full
  layer at 8,192 positions under a 128-token chunk), through
  ``ops.cache_prefill_attention``, a kernel that reads each slot's K/V
  blocks out of the stacked buffer up to the chunk's last position and
  keeps the scores on the chip.

Both programs run ONE layer definition (``_serve_layer``), parameterised
by the layer's attention kind and MLP kind, over one cache interface:
what differs between them is how a layer's new rows are written and its
cache is read (``attend``). A uniform model's layers are one stacked
pytree walked by ``lax.scan`` with one stacked cache pair. A LAYERED
model (``TransformerConfig.layered``) comes as a tuple of layers walked
by a static loop, with one cache stack per ATTENTION KIND: full layers
keep ``Tmax`` positions, window layers a ring of the last positions
(``ring_rows``: window + one prefill chunk, so that a chunk can be
written before it is read) plus one parking row; position p of a slot
lies at ring row ``p % ring`` and every mask is by position, so slot
reuse never shows the last tenant's rows. K rows wider than 128 lanes
and no whole number of them are kept as a tuple of 128-lane tiles, the
last zero-filled (``lane_tiles``: a width of 192 lies in 256 lanes on
the device anyway, and as tiles the rows of 4 KV heads merge without a
copy of the cache; the decode kernel sums the tiles' products).

Both run over the fused ``decode_weights`` layout (weights fuse once per
engine, exactly like ``DecodeSession``) and carry the stacked caches as
scan CARRY (the xs/ys re-stack cost decode.py's docstring documents).
KV buffers are donated and no program ever holds a layer's slab apart
from them: per dispatch the cache takes S · Hkv · Dh elements per layer
per buffer in decode and P · C · Hkv · Dh in prefill (2 MB and 8 MB
over 16 layers of 8 × 128 bf16 heads at 32 slots, 4 × 32-token chunks),
and gives one pass over the layer's S · Tmax rows in decode (4.3 GB),
over P · Tmax in prefill (0.5 GB; through the kernel, over the P slots'
positions before each chunk's end, once per KV head).

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
    cache_prefill_attention,
    grouped_cache_attention,
    rms_norm,
    rope_frequencies,
)
from tony_tpu.ops.attention import (
    cache_rows_view,
    cache_slot_rows,
    cache_take,
    prefill_key_block,
    ring_positions,
    rowwise_cache_attention,
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
    for int8, the lane tiles side by side for a tiled one (the caller
    cuts the zero fill off)."""
    if isinstance(cache, QuantizedKV):
        return (cache.data.astype(jnp.float32) * cache.scale).astype(dt)
    if isinstance(cache, tuple):
        return jnp.concatenate(cache, axis=-1)
    return cache


def _cache_tmax(cache) -> int:
    return jax.tree.leaves(cache)[0].shape[2]


LANES = 128


def lane_tiles(d: int) -> int:
    """How many 128-lane tiles a cache row of width ``d`` is stored in:
    0 = as it is (a whole number of tiles, or under one); else the row
    is kept as that many [.., 128] buffers, the last zero-filled. The
    device gives a 192-wide row 256 lanes anyway; kept as one
    [Tmax, Hkv, 256] buffer, the rows of 4 KV heads do not merge to
    [Tmax * Hkv, 256] without a copy of the whole cache, which
    [Tmax, Hkv, 128] buffers do (compiled for a v5e)."""
    return 0 if d % LANES == 0 or d < LANES else -(-d // LANES)


def _lane_tiles(x, n: int) -> tuple:
    """``x`` [..., d] as ``n`` tiles [..., 128], zero-filled past d."""
    extra = n * LANES - x.shape[-1]
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, extra)])
    return tuple(x[..., i * LANES:(i + 1) * LANES] for i in range(n))


# The cache interface of decode_window / prefill_chunks: rows are written
# into the stacked [L, S, Tmax, Hkv, ·] buffer where they lie, and a
# layer is read out of that same buffer. ``jax.tree.map`` over the cache
# runs each plane of a QuantizedKV and a plain array through one path.


def _encode(cache, x):
    """``x`` in the cache's storage form (the cache's own pytree)."""
    if isinstance(cache, QuantizedKV):
        return _quantize(x)
    if isinstance(cache, tuple):
        return _lane_tiles(x.astype(cache[0].dtype), len(cache))
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


def _put_chunk(buf, layer, slot, start, val):
    """``val`` [C, Hkv, D] into one buffer at (layer, slot, start)."""
    rows = cache_rows_view(buf)
    if rows.ndim == buf.ndim:
        return lax.dynamic_update_slice(
            buf, val[None, None], (layer, slot, start, 0, 0))
    return lax.dynamic_update_slice(
        rows, val.reshape(1, 1, -1, val.shape[-1]),
        (layer, slot, start * buf.shape[3], 0)).reshape(buf.shape)


def _write_chunk(cache, layer, slot, start, chunk):
    """One prefill chunk [C, Hkv, Dh] at (layer, slot, start)."""
    return jax.tree.map(
        lambda buf, val: _put_chunk(buf, layer, slot, start, val),
        cache, _encode(cache, chunk))


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
    return _materialize(jax.tree.map(
        lambda buf: cache_slot_rows(buf, layer, slots), cache), dt)


def ring_rows(cfg: TransformerConfig, prefill_chunk: int) -> int:
    """Positions a window layer's ring holds: the window and one prefill
    chunk (a chunk is written before it is read, and its first query
    still needs the window behind it), in whole chunks so that an
    aligned chunk never wraps."""
    c = max(1, int(prefill_chunk))
    return c * -(-(cfg.window + c) // c)


def _kind(cache, attn: str):
    """One attention kind's stack of a cache: a layered model's cache is
    a dict of them, a uniform model's IS its one stack."""
    return cache[attn] if isinstance(cache, dict) else cache


def _with_kind(cache, attn: str, stack):
    return {**cache, attn: stack} if isinstance(cache, dict) else stack


def init_slot_cache(
    cfg: TransformerConfig, slots: int, max_len: int,
    kv_quant: str = "none", prefill_chunk: int = 32,
):
    """Zeroed stacked KV cache pair [L, S, Tmax, Hkv, Dh] — one row per
    slot, sized once for the engine's lifetime, donated to every dispatch
    and rewritten in place a few rows at a time (module docstring).
    Serving HBM budget is 2 · L · S · Tmax · Hkv · Dh · dtype bytes
    (``kv_quant="int8"``: 1 + 4/Dh bytes per element instead of the
    compute dtype's 2); see docs/DEPLOY.md "Serving" for the sizing table
    and "Autotuning" for the quantization contract.

    A layered model gets a dict pair, one stack per attention kind it
    has: ``full`` [Lf, S, Tmax, Hkv, ·] and ``window`` [Lw, S, ring + 1,
    Hkv_w, ·] (``ring_rows(cfg, prefill_chunk)`` positions and the
    parking row); a width that is more than one 128-lane tile and no
    whole number of them comes as a tuple of tiles (``lane_tiles``).
    The int8 cache refuses a layered model."""
    if kv_quant not in ("none", "", None, "int8"):
        raise ValueError(f"unknown kv_quant mode {kv_quant!r}")
    dt = cfg.compute_dtype
    if cfg.layered:
        if kv_quant == "int8":
            raise ValueError(
                "the int8 KV cache serves uniform layers only; this "
                "configuration has layer kinds")
        attn = [a for a, _ in cfg.layer_kinds]
        if "full" not in attn:
            raise ValueError(
                "a layered configuration needs a full-attention layer: "
                "idle lanes park at its last position")
        rows = {"full": max_len,
                "window": ring_rows(cfg, prefill_chunk) + 1}

        def stack(kind, width):
            shape = (attn.count(kind), slots, rows[kind],
                     cfg.kv_heads_of(kind))
            tiles = lane_tiles(width)
            if tiles:
                return tuple(jnp.zeros(shape + (LANES,), dt)
                             for _ in range(tiles))
            return jnp.zeros(shape + (width,), dt)

        def stacks(width):
            return {kind: stack(kind, width)
                    for kind in ("full", "window") if kind in attn}

        return stacks(cfg.head_dim), stacks(cfg.v_dim)
    shape = (cfg.n_layers, slots, max_len, cfg.kv_heads, cfg.head_dim)
    if kv_quant == "int8":
        def one():
            return QuantizedKV(
                jnp.zeros(shape, jnp.int8),
                jnp.zeros(shape[:-1] + (1,), jnp.float32),
            )
        return one(), one()
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)


def cache_inject_rows(cache, slot: int, rows) -> "jax.Array | QuantizedKV":
    """Host-side write of FLOAT rows [L, P, Hkv, Dh] into one slot's
    prefix (the inject half of prefill/decode disaggregation). The
    cross-replica exchange format is always float — quantization is a
    per-engine storage decision, so a bf16 prefill replica can feed an
    int8 decode replica and vice versa. A layered cache takes what
    ``cache_export_rows`` gave of one: a dict by attention kind, the
    window kind's rows being the LAST positions of the prefix."""
    if isinstance(cache, dict):
        length = rows["full"].shape[1]
        out = {}
        for kind, stack in cache.items():
            part = jnp.asarray(rows[kind])
            at = jnp.arange(length - part.shape[1], length)
            if kind == "window":
                at = at % (_cache_tmax(stack) - 1)
            out[kind] = jax.tree.map(
                lambda buf, val: buf.at[:, slot, at].set(val),
                stack, _encode(stack, part))
        return out
    p = rows.shape[1]
    if isinstance(cache, QuantizedKV):
        q = _quantize(jnp.asarray(rows, jnp.float32))
        return QuantizedKV(
            cache.data.at[:, slot, :p].set(q.data),
            cache.scale.at[:, slot, :p].set(q.scale),
        )
    return cache.at[:, slot, :p].set(jnp.asarray(rows, cache.dtype))


def cache_export_rows(cache, slot: int, length: int, width: int = 0):
    """One slot's KV prefix as float rows [L, length, Hkv, Dh] — the
    export half of the exchange contract ``cache_inject_rows``
    documents (int8 storage dequantizes on the way out). A layered
    cache exports a dict by attention kind at the logical ``width``:
    all ``length`` positions of its full layers, and of its window
    layers the last ``ring`` (or fewer), in position order."""
    if isinstance(cache, dict):
        out = {}
        for kind, stack in cache.items():
            at = jnp.arange(length)
            if kind == "window":
                ring = _cache_tmax(stack) - 1
                at = jnp.arange(max(0, length - ring), length) % ring
            out[kind] = _materialize(
                jax.tree.map(lambda buf: buf[:, slot, at], stack),
                None)[..., :width or None]
        return out
    if isinstance(cache, QuantizedKV):
        return _materialize(
            QuantizedKV(cache.data[:, slot, :length],
                        cache.scale[:, slot, :length]),
            jnp.float32,
        )
    return cache[:, slot, :length]


def _write_chunk_ring(cache, layer, slot, start, chunk):
    """One prefill chunk [C, Hkv, D] of positions [start, start + C) into
    a ring stack whose ``ring`` is whole chunks: the chunk lies in at
    most two ALIGNED blocks of C rows, each read, merged under a row
    mask and written back — two small dynamic slices either way, no
    scatter, and a block never wraps."""
    c = chunk.shape[0]
    ring = _cache_tmax(cache) - 1
    off = start % c
    row = jnp.arange(c)[:, None, None]

    def write(buf, val):
        rolled = jnp.roll(val, off, axis=0)
        for block, mine in ((0, row >= off), (1, row < off)):
            at = (layer, slot, (start - off + block * c) % ring)
            old = cache_take(buf, *at, c)
            buf = _put_chunk(buf, *at, jnp.where(mine, rolled, old))
        return buf

    return jax.tree.map(write, cache, _encode(cache, chunk))


# Float32 scores [P, Hq, C, T] of one layer a prefill round may hold at
# once, in bytes: under it the round attends in one batched product,
# over it chunk by chunk through ``cache_prefill_attention``.
SCORES_LIMIT = 2 ** 28


def _scores_fit(p: int, c: int, n_h: int, t: int) -> bool:
    return p * c * n_h * t * 4 <= SCORES_LIMIT


def prefill_read_block(cfg: TransformerConfig, k_all, p: int, c: int) -> int:
    """Positions in one key block where a round of ``p`` chunks of ``c``
    tokens attends its FULL layers through ``cache_prefill_attention``
    (a row then reads whole blocks up to its chunk's end); 0 where it
    reads every slot's whole reservation: scores that fit at once, or an
    int8 cache, whose rows are read out to be dequantized."""
    kc = _kind(k_all, "full")
    t = _cache_tmax(kc)
    if isinstance(kc, QuantizedKV) or _scores_fit(p, c, cfg.n_heads, t):
        return 0
    return prefill_key_block(t, cfg.kv_heads_of("full"))


def _attend_rows(q, kc, vc, at, slots, starts, mask, scale, sink, cfg, attn):
    """A round's chunks [P, C, Hq, Dk] against layer ``at`` of their
    slots' cache, the chunks already written. Where the whole batch's
    float32 scores fit (``SCORES_LIMIT``): the slots' rows read out and
    one ``grouped_cache_attention`` under ``mask``. Where they do not (a
    full layer at 8,192 positions under a 128-token chunk): through
    ``cache_prefill_attention``, which reads the stack where it lies and
    only up to each chunk's end; a ring or an int8 cache of that size
    keeps the plain path, row by row."""
    p, c, n_h, d_k = q.shape
    dt = cfg.compute_dtype
    if attn == "full" and prefill_read_block(cfg, kc, p, c):
        return cache_prefill_attention(q, kc, vc, at, slots, starts + c,
                                       scale=scale, sink=sink)
    k = _read_slots(kc, at, slots, dt)[..., :d_k]
    v = _read_slots(vc, at, slots, dt)
    attention = (grouped_cache_attention
                 if _scores_fit(p, c, n_h, k.shape[1])
                 else rowwise_cache_attention)
    return attention(q, k, v, mask, scale=scale, sink=sink)


def _rope(x, tables, positions, rot: int):
    """Rotary embedding on the first ``rot`` dims of the head; the rest
    pass."""
    cos, sin = tables
    if rot == x.shape[-1]:
        return apply_rope(x, cos, sin, positions=positions)
    return jnp.concatenate(
        [apply_rope(x[..., :rot], cos, sin, positions=positions),
         x[..., rot:]], axis=-1)


def _rope_tables(cfg: TransformerConfig) -> dict:
    """(cos, sin) per attention kind the model has, in the layers' order
    (a set's order follows the process's hash seed, and with it the
    program's text and its key in the compile cache)."""
    return {
        kind: rope_frequencies(cfg.rot_dim, cfg.max_seq,
                               theta=cfg.rope_theta_of(kind))
        for kind in dict.fromkeys(a for a, _ in cfg.layer_kinds)
    }


def _mlp(x, lp, cfg, token_mask=None, count_mask=None):
    """SwiGLU over the fused gate|up projection, or the grouped expert
    layer (``models.decode._moe_mlp_decode``: dropless, the held
    experts' part). Returns (x, pairs): the (token, choice) pairs each
    held expert received, None for a dense layer."""
    dt = cfg.compute_dtype
    if "router" in lp:
        out, pairs = _moe_mlp_decode(x, lp, cfg, token_mask, count_mask)
        return x + out, pairs
    hn = rms_norm(x, lp["ln2"], eps=cfg.rms_eps).astype(dt)
    gu = jnp.einsum("btd,df->btf", hn, lp["gate_up"])
    f = gu.shape[-1] // 2
    act = (
        jax.nn.silu(gu[..., :f].astype(jnp.float32)).astype(dt)
        * gu[..., f:]
    )
    return x + jnp.einsum("btf,fd->btd", act, lp["w_down"]), None


def _serve_layer(x, lp, attn, cfg, ropes, positions, attend, *,
                 token_mask=None, count_mask=None):
    """THE decoder layer of the serving programs: pre-norm attention of
    kind ``attn`` (its own KV head count and rope base; q/k width
    ``head_dim`` of which ``rot_dim`` rotate, v width ``v_dim`` scaled
    by ``v_scale``), then the layer's MLP by what ``lp`` holds (dense
    SwiGLU or experts). ``attend(q, k_new, v_new, attn, sink) -> o``
    writes the new rows into the caller's cache and reads it: the one
    thing decode and prefill do differently. Returns (x, pairs)."""
    dt = cfg.compute_dtype
    b, t, _ = x.shape
    n_h, h_kv = cfg.n_heads, cfg.kv_heads_of(attn)
    h = rms_norm(x, lp["ln1"], eps=cfg.rms_eps).astype(dt)
    if lp["qkv"].ndim == 2:
        # layered: q|k|v fused on the feature axis, widths of their own
        flat = jnp.einsum("btd,df->btf", h, lp["qkv"])
        n_q, n_k = n_h * cfg.head_dim, h_kv * cfg.head_dim
        q = flat[..., :n_q].reshape(b, t, n_h, cfg.head_dim)
        k_new = flat[..., n_q:n_q + n_k].reshape(b, t, h_kv, cfg.head_dim)
        v_new = flat[..., n_q + n_k:].reshape(b, t, h_kv, cfg.v_dim)
        if cfg.v_scale != 1.0:
            v_new = (v_new.astype(jnp.float32) * cfg.v_scale).astype(dt)
    else:
        qkv = jnp.einsum("btd,dhk->bthk", h, lp["qkv"])
        q = qkv[:, :, :n_h]
        k_new = qkv[:, :, n_h:n_h + h_kv]
        v_new = qkv[:, :, n_h + h_kv:]
    q = _rope(q, ropes[attn], positions, cfg.rot_dim)
    k_new = _rope(k_new, ropes[attn], positions, cfg.rot_dim)
    o = attend(q, k_new, v_new, attn, lp.get("sink"))
    x = x + jnp.einsum("bthk,hkd->btd", o, lp["wo"])
    return _mlp(x, lp, cfg, token_mask, count_mask)


def _run_layers(x, params, k_all, v_all, cfg, layer):
    """Every layer in model order. ``layer(x, lp, attn, at, k_all,
    v_all) -> (x, k_all, v_all, pairs)`` with ``at`` the layer's index
    in its attention kind's cache stack. A uniform model: one
    ``lax.scan`` over the stacked layers, the caches as carry. A layered
    model: a static loop over its tuple of layers. Returns (x, k_all,
    v_all, pairs summed over the expert layers or None)."""
    if isinstance(params["layers"], tuple):
        seen: dict = {}
        total = None
        for lp, (attn, _) in zip(params["layers"], cfg.layer_kinds):
            at = seen.get(attn, 0)
            seen[attn] = at + 1
            x, k_all, v_all, pairs = layer(x, lp, attn, jnp.int32(at),
                                           k_all, v_all)
            if pairs is not None:
                total = pairs if total is None else total + pairs
        return x, k_all, v_all, total
    attn = cfg.layer_kinds[0][0]

    def body(carry, layer_in):
        x, k_all, v_all = carry
        lp, at = layer_in
        x, k_all, v_all, pairs = layer(x, lp, attn, at, k_all, v_all)
        return (x, k_all, v_all), pairs

    (x, k_all, v_all), pairs = lax.scan(
        body, (x, k_all, v_all),
        (params["layers"], jnp.arange(cfg.n_layers)),
    )
    return x, k_all, v_all, None if pairs is None else pairs.sum(0)


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
    A window layer's ring derives its own write row from the same
    ``wpos``: ``wpos % ring``, and the ring's parking row for a parked
    lane (an active stream never writes at ``Tmax - 1``: its last fed
    token lies at ``Tmax - 2`` at most). Parked lanes send no pair to an
    expert.

    Returns (k_all, v_all, window_tokens [S, steps] int32, pairs): the
    (token, choice) pairs each held expert received over the window, or
    None for a model without experts.
    """
    dt = cfg.compute_dtype
    t_max = _cache_tmax(_kind(k_all, "full"))
    ropes = _rope_tables(cfg)
    scale = cfg.head_dim ** -0.5

    def one_step(carry, i):
        k_all, v_all, pos, wpos, tokens = carry
        x = params["embed"][tokens][:, None, :].astype(dt)  # [S, 1, d]
        # Visibility after the write: keys 0..pos inclusive (index pos
        # holds the token being fed this step). Inactive lanes' pos can
        # run past the table mid-window — clamp the RoPE gather (their
        # output is discarded; the mask itself cannot overflow).
        rp = jnp.minimum(pos, cfg.max_seq - 1)[:, None]
        parked = wpos >= t_max - 1

        def layer(x, lp, attn, at, k_all, v_all):
            def attend(q, k_new, v_new, attn, sink):
                nonlocal k_all, v_all
                kc, vc = _kind(k_all, attn), _kind(v_all, attn)
                window, w_at = 0, wpos
                if attn == "window":
                    window, ring = cfg.window, _cache_tmax(kc) - 1
                    w_at = jnp.where(parked, ring, wpos % ring)
                kc = _write_rows(kc, at, k_new[:, 0], w_at)
                vc = _write_rows(vc, at, v_new[:, 0], w_at)
                k_stack, idx = _layer_view(kc, at, dt)
                v_stack, _ = _layer_view(vc, at, dt)
                o = cache_decode_attention(
                    q[:, 0], k_stack, v_stack, idx, pos, scale=scale,
                    window=window, sink=sink,
                )[:, None]
                k_all = _with_kind(k_all, attn, kc)
                v_all = _with_kind(v_all, attn, vc)
                return o

            x, pairs = _serve_layer(x, lp, attn, cfg, ropes, rp, attend,
                                    token_mask=~parked[:, None])
            return x, k_all, v_all, pairs

        x, k_all, v_all, pairs = _run_layers(x, params, k_all, v_all, cfg,
                                             layer)
        x = rms_norm(x[:, -1:], params["final_norm"],
                     eps=cfg.rms_eps).astype(dt)
        logits = jnp.einsum(
            "btd,dv->btv", x, params["unembed"]
        )[:, 0].astype(jnp.float32)
        nxt = _sample_slots(
            logits, temp, jax.random.fold_in(base_key, draw0 + i)
        )
        pos = pos + 1
        wpos = jnp.minimum(wpos + 1, t_max - 1)
        return (k_all, v_all, pos, wpos, nxt), (nxt, pairs)

    (k_all, v_all, _, _, _), (toks, pairs) = lax.scan(
        one_step, (k_all, v_all, pos, wpos, tokens), jnp.arange(steps)
    )
    return (k_all, v_all, toks.T,   # [S, steps]
            None if pairs is None else pairs.sum(0))


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
    overwrite-before-read invariant keeps unreadable (in a ring too:
    its masks are by position, and a position past the prompt is
    written by decode before any query reaches it).

    A layer's chunks attend after all P were written, by
    ``_attend_rows``: the slots' rows read out and one batched product
    where the round's float32 scores fit ``SCORES_LIMIT``, else (full
    layers) ``ops.cache_prefill_attention`` over the stack where it
    lies, which reads no key past ``starts[i] + C``.

    Returns (k_all, v_all, first_tokens [P], logits [P, V] fp32, pairs):
    row i's token samples from position ``n_valids[i] - 1`` — meaningful
    only on a request's FINAL chunk (earlier chunks' sample is
    discarded by the scheduler; computing it unconditionally keeps one
    executable). ``pairs``: the (token, choice) pairs each held expert
    received from the rows' valid tokens, a duplicated row counted
    once; None for a model without experts."""
    dt = cfg.compute_dtype
    p, c = tokens.shape
    t_max = _cache_tmax(_kind(k_all, "full"))
    ropes = _rope_tables(cfg)
    scale = cfg.head_dim ** -0.5
    positions = starts[:, None] + jnp.arange(c)[None, :]       # [P, C]
    # Padded tail positions can run past the RoPE table; clamp the
    # gather (values are garbage, discarded) — the write offset itself
    # is host-validated.
    rope_pos = jnp.minimum(positions, cfg.max_seq - 1)
    x = params["embed"][tokens].astype(dt)                     # [P, C, d]
    masks = {"full": (positions[:, :, None]
                      >= jnp.arange(t_max)[None, None, :])}    # [P, C, T]
    if isinstance(k_all, dict) and "window" in k_all:
        n_rows = _cache_tmax(k_all["window"])
        held = ring_positions(starts + c - 1, n_rows, n_rows - 1)
        masks["window"] = (
            (held[:, None, :] >= 0)
            & (held[:, None, :] <= positions[:, :, None])
            & (held[:, None, :] > positions[:, :, None] - cfg.window))
    # Pairs are COUNTED over valid tokens of rows that are no duplicate
    # of row 0; every token is still computed (a duplicate must write
    # the K/V row 0 wrote, in every later layer too).
    counted = ((jnp.arange(c)[None, :] < n_valids[:, None])
               & ((slots != slots[0]) | (jnp.arange(p) == 0))[:, None])

    def layer(x, lp, attn, at, k_all, v_all):
        def attend(q, k_new, v_new, attn, sink):
            nonlocal k_all, v_all
            kc, vc = _kind(k_all, attn), _kind(v_all, attn)
            write = _write_chunk_ring if attn == "window" else _write_chunk

            def write_one(i, kv):
                where = (at, slots[i], starts[i])
                return (write(kv[0], *where, k_new[i]),
                        write(kv[1], *where, v_new[i]))

            # Sequential writes, not a vmap-scatter: P is small and
            # duplicate (padding) rows must overwrite cleanly in order.
            kc, vc = lax.fori_loop(0, p, write_one, (kc, vc))
            o = _attend_rows(q, kc, vc, at, slots, starts, masks[attn],
                             scale, sink, cfg, attn)
            k_all = _with_kind(k_all, attn, kc)
            v_all = _with_kind(v_all, attn, vc)
            return o

        x, pairs = _serve_layer(x, lp, attn, cfg, ropes, rope_pos, attend,
                                count_mask=counted)
        return x, k_all, v_all, pairs

    x, k_all, v_all, pairs = _run_layers(x, params, k_all, v_all, cfg, layer)
    last = jnp.take_along_axis(
        x, jnp.maximum(n_valids - 1, 0)[:, None, None], axis=1
    )                                                          # [P, 1, d]
    last = rms_norm(last, params["final_norm"], eps=cfg.rms_eps).astype(dt)
    logits = jnp.einsum(
        "btd,dv->btv", last, params["unembed"]
    )[:, 0].astype(jnp.float32)
    toks = _sample_slots(logits, temps,
                         jax.random.fold_in(base_key, draw))
    return k_all, v_all, toks, logits, pairs
