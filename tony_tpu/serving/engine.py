"""Device half of the continuous-batching serving engine: the KV cache
and the two programs that read and write it.

The decoder layer itself is not here: ``models/decode.py`` defines it
once (``serve_layer``, walked by ``run_layers``, ended by ``lm_head``)
for every inference program, and a program brings its cache policy as an
``attend(q, k_new, v_new, attn, sink)`` closure. This module owns what
is the engine's: the cache's storage forms and its interface (write
rows, write a chunk, read a layer, read some slots' rows, inject and
export), the attention of a prefill round, per-slot sampling, and TWO
executables, compiled once per engine lifetime and shared by every
request that ever passes through (``generate`` compiles one per (batch,
prompt width, horizon) and runs every row to the static horizon: fine
for eval generation, a wall for serving) —

* ``decode_window`` — ``steps`` tokens for ALL slots in one dispatch.
  The slot batch is a fixed [S] lane array; each slot owns a row of the
  stacked KV cache [L, S, Tmax, Hkv, Dh], its own position, and its own
  sampling temperature, so requests of different lengths share every
  decode iteration (Orca-style iteration-level scheduling). Its
  ``attend`` scatters the slots' new K/V rows into the stacked buffer at
  (layer, slot, wpos[slot]) and reads that layer where it lies
  (``ops.cache_decode_attention``), masked per slot with
  ``key_index <= pos[slot]``: a slot's key blocks up to the one that
  holds ``pos[slot]``, and nothing for a lane that does not decode.
* ``prefill_chunks`` — one bounded chunk of the prompt of EACH of up to
  P pending slots into those slots' cache rows. Chunking bounds how long
  a new prompt can stall the in-flight decode streams: the host
  interleaves one round per engine iteration, so time-to-first-token for
  the new requests trades off against inter-token latency for everyone
  else at a fixed, configured granularity
  (``tony.serving.prefill-chunk``). Its ``attend`` writes the P chunks
  by ``dynamic_update_slice`` at (layer, slot, start) and attends over
  those P slots only (``_attend_rows``): where the round's float32
  scores against the whole reservation fit at once (``SCORES_LIMIT``),
  over the slots' rows read out, in one batched product; where they do
  not (a full layer at 8,192 positions under a 128-token chunk), through
  ``ops.cache_prefill_attention``, a kernel that reads each slot's K/V
  blocks out of the stacked buffer up to the chunk's last position and
  keeps the scores on the chip.

The cache. A uniform model has one stacked pair [L, S, Tmax, Hkv, Dh]
in the compute dtype. A LAYERED model (``TransformerConfig.layered``)
has one stack per ATTENTION KIND: full layers keep ``Tmax`` positions,
window layers a ring of the last positions (``ring_rows``: window + one
prefill chunk, so that a chunk can be written before it is read) plus
one parking row; position p of a slot lies at ring row ``p % ring`` and
every mask is by position, so slot reuse never shows the last tenant's
rows. A buffer has one of two storage forms, and the row's WIDTH decides
which, not a caller: a plain array, or — K rows wider than 128 lanes and
no whole number of them — a tuple of 128-lane tiles, the last
zero-filled (``lane_tiles``: a width of 192 lies in 256 lanes on the
device anyway, and as tiles the rows of 4 KV heads merge without a copy
of the cache; the decode kernel sums the tiles' products). And heads of
64 lie TWO to a row, [.., Tmax, Hkv / 2, 128]
(``ops.attention.cache_heads_per_row``): the bytes of [.., Hkv, 64] in the
same order, but a buffer whose rows cost 64 lanes a head and merge as any
128-wide cache's do, so that the kernels read it, a slot's live key blocks
only; they score a query head against its own half of a row.

Two more kinds hold what is NOT a K/V row per position
(``ops/hybrid.py`` has their arithmetic and layouts), each as one buffer
per LAYER, not a stack: a layered model walks its layers in a static
loop, so a layer's buffer is an argument of its own and nothing is ever
sliced out of a stack (XLA copies a layer's slab out of a stacked buffer
for a batched contraction, PERF.md section 6, PR 26). ``linear`` layers
(lightning attention) keep a float32 recurrent state [S + 1, H, D, D]: it
is not indexed by position, so the overwrite-before-read invariant below
cannot protect it. Three rules stand in its place: a chunk whose
``start == 0`` reads a zero state whatever the slot held (slot reuse
still zeroes nothing); the state a round writes is the state after the
chunk's VALID positions, carried to the prompt's next round; and
``decode_window``, which runs all slots, maps every lane that is not
decoding (free, or in mid-prefill: the parked ones) to the buffer's
parking row S, so their states stay bit for bit. ``sparse`` layers
(block-sparse attention) keep K and V head-major [S, Hkv, Tmax, D] and
beside them ``K^c`` [S, Hkv, Tmax / stride + 1, D] float32, the means of
the keys over ``sparse_kernel`` positions every ``sparse_stride``: a row
is SET by the position that starts its kernel and added to by the rest,
and only a kernel that lies wholly at or before the query is ever read,
so K^c too is never zeroed. ``conv`` layers (gated short convolutions)
keep no K/V at all: per slot the gate g of its last ``conv_kernel - 1``
positions, [S, L - 1, d_model] in the compute dtype, under the state's
three rules (zeros at ``start == 0``; the rows after the chunk's VALID
positions; a lane that does not decode keeps its rows — by a select over
the lanes, which are the slots in order, so this buffer needs no parking
row). A model with any of the three kinds prefills in ALIGNED chunks, a
prompt's last one padded (``n_valids``), never overlapped: a position must
enter a state once.

Both programs run over the fused ``decode_weights`` layout (weights fuse
once per engine, exactly like ``DecodeSession``) and carry the stacked
caches through the layers as scan CARRY (``run_layers``). KV buffers are
donated and no program ever holds a layer's slab apart from them: per
dispatch the cache takes S · Hkv · Dh elements per layer per buffer in
decode and P · C · Hkv · Dh in prefill (2 MB and 8 MB over 16 layers of
8 × 128 bf16 heads at 32 slots, 4 × 32-token chunks), and gives one pass
over the decoding slots' live key blocks in decode (of 4.3 GB reserved,
what ``stats()["decode_keys"]`` counts), over P · Tmax in prefill
(0.5 GB; through the kernel, over the P slots' positions before each
chunk's end, once per KV head).

Overwrite-before-read invariant: slot reuse never zeroes a cache row.
A freed slot's stale K/V rows are only ever unmasked after the new
request's own prefill/decode has written those positions (prefill
covers [0, P); each decode step writes index ``pos`` before attention
reads it), so stale data is structurally unreadable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.models.decode import (
    embed_scaled,
    lm_head,
    rope_tables,
    run_layers,
    serve_layer,
)
from tony_tpu.models.transformer import TransformerConfig
from tony_tpu.ops import (
    cache_decode_attention,
    cache_prefill_attention,
    grouped_cache_attention,
)
from tony_tpu.ops import hybrid
from tony_tpu.ops.attention import (
    cache_heads_per_row,
    cache_rows_view,
    cache_slot_rows,
    cache_take,
    decode_key_block,
    decode_last_block,
    prefill_key_block,
    ring_positions,
    rowwise_cache_attention,
)


def _materialize(cache) -> jax.Array:
    """Cache rows as one array: a plain buffer as it is (the
    stored-dtype einsum path keeps its fp32 MXU accumulation), the lane
    tiles of a tiled one side by side (the caller cuts the zero fill
    off)."""
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
# layer is read out of that same buffer. A cache is a plain buffer or,
# where the row's width asks for it, a tuple of 128-lane tiles
# (``lane_tiles``): ``jax.tree.map`` runs both through one path.


def _encode(cache, x):
    """``x`` [.., Hkv, D] in the cache's storage form (the cache's own
    pytree): lane tiles, or heads 64 wide two to a row (the same bytes in
    the same order: a reshape), or as it is."""
    if isinstance(cache, tuple):
        return _lane_tiles(x.astype(cache[0].dtype), len(cache))
    if cache.shape[-1] == 2 * x.shape[-1]:
        x = x.reshape(x.shape[:-2] + (x.shape[-2] // 2, 2 * x.shape[-1]))
    return x.astype(cache.dtype)


def _logical(rows, width: int):
    """Rows read out of a cache at their logical ``width`` [.., Hkv, D]
    (0 = as stored): the tiles' zero fill cut off, paired heads apart."""
    if width and rows.shape[-1] == 2 * width:
        return rows.reshape(rows.shape[:-2] + (2 * rows.shape[-2], width))
    return rows[..., :width or None]


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


def _read_slots(cache, layer, slots, width: int):
    """The rows [P, Tmax, Hkv, Dh] of ``slots`` [P] in one layer: P
    small dynamic slices (on the TPU a gather over the stacked buffer
    lowers to slices of the WHOLE buffer). Tiles side by side, their
    zero fill past ``width`` cut off; paired heads as they lie
    (``grouped_cache_attention`` reads them so)."""
    rows = _materialize(jax.tree.map(
        lambda buf: cache_slot_rows(buf, layer, slots), cache))
    return rows[..., :width] if isinstance(cache, tuple) else rows


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
    prefill_chunk: int = 32,
):
    """Zeroed stacked KV cache pair [L, S, Tmax, Hkv, Dh] in the compute
    dtype — one row per slot, sized once for the engine's lifetime,
    donated to every dispatch and rewritten in place a few rows at a
    time (module docstring). Serving HBM budget is 2 · L · S · Tmax ·
    Hkv · Dh · dtype bytes; see docs/DEPLOY.md "Serving" for the sizing
    table.

    A layered model gets a dict pair, one stack per attention kind it
    has: ``full`` [Lf, S, Tmax, Hkv, ·] and ``window`` [Lw, S, ring + 1,
    Hkv_w, ·] (``ring_rows(cfg, prefill_chunk)`` positions and the
    parking row); a width that is more than one 128-lane tile and no
    whole number of them comes as a tuple of tiles (``lane_tiles``).
    Either model's heads of 64 lie two to a row, [.., Hkv / 2, 128]
    (``ops.attention.cache_heads_per_row``)."""
    dt = cfg.compute_dtype

    def paired(kind, width):
        """(rows a position, their width) of one kind's buffers."""
        h_kv = cfg.kv_heads_of(kind)
        n = cache_heads_per_row(h_kv, cfg.head_dim, cfg.v_dim)
        return h_kv // n, width * n

    if cfg.layered:
        attn = [a for a, _ in cfg.layer_kinds]
        if "full" not in attn and "sparse" not in attn:
            raise ValueError(
                "a layered configuration needs a full or a sparse "
                "attention layer: idle lanes park at its last position")
        rows = {"full": max_len,
                "window": ring_rows(cfg, prefill_chunk) + 1}

        def stack(kind, width):
            h_rows, width = paired(kind, width)
            shape = (attn.count(kind), slots, rows[kind], h_rows)
            tiles = lane_tiles(width)
            if tiles:
                return tuple(jnp.zeros(shape + (LANES,), dt)
                             for _ in range(tiles))
            return jnp.zeros(shape + (width,), dt)

        def stacks(width):
            return {kind: stack(kind, width)
                    for kind in ("full", "window") if kind in attn}

        if has_state(cfg):
            return _init_state_cache(cfg, slots, max_len, prefill_chunk,
                                     stacks)
        return stacks(cfg.head_dim), stacks(cfg.v_dim)
    shape = (cfg.n_layers, slots, max_len) + paired("full", cfg.head_dim)
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)


STATE_KINDS = ("linear", "sparse", "conv")


def has_state(cfg: TransformerConfig) -> bool:
    """Whether a model keeps per-slot state that is no K/V row of a
    position (linear, sparse or conv layers): it prefills in aligned
    chunks and its cache is not exchanged by rows."""
    return bool(set(STATE_KINDS) & {a for a, _ in cfg.layer_kinds})


def _init_state_cache(cfg, slots, max_len, prefill_chunk, stacks):
    """The cache pair of a model with linear, sparse or conv layers
    (module docstring): the K side holds per kind what the kind keeps —
    a full or window stack as ever, per sparse layer its K buffer and
    under ``sparse_kc`` its K^c buffer, per linear layer its state, per
    conv layer its last ``conv_kernel - 1`` rows of g — the V side the
    stacks and the sparse layers' V buffers."""
    attn = [a for a, _ in cfg.layer_kinds]
    dt = cfg.compute_dtype
    c, b, st = prefill_chunk, cfg.sparse_block, cfg.sparse_stride
    if "sparse" in attn and (c % b or max_len % c):
        raise ValueError(
            f"sparse layers prefill in whole blocks: prefill_chunk {c} "
            f"must be a multiple of sparse_block {b} and divide max_len "
            f"{max_len}")
    n_sp, n_lin = attn.count("sparse"), attn.count("linear")
    if (n_sp or n_lin) and cfg.head_dim != cfg.v_dim:
        raise ValueError("linear and sparse layers keep k and v at one "
                         "width")
    k, v = stacks(cfg.head_dim), stacks(cfg.v_dim)
    if n_sp:
        shape = (slots, cfg.kv_heads_of("sparse"), max_len, cfg.head_dim)
        k["sparse"] = tuple(jnp.zeros(shape, dt) for _ in range(n_sp))
        v["sparse"] = tuple(jnp.zeros(shape, dt) for _ in range(n_sp))
        rows = shape[:2] + (max_len // st + 1, cfg.head_dim)
        k["sparse_kc"] = tuple(jnp.zeros(rows, jnp.float32)
                               for _ in range(n_sp))
    if n_lin:
        shape = (slots + 1, cfg.n_heads, cfg.head_dim, cfg.head_dim)
        k["linear"] = tuple(jnp.zeros(shape, jnp.float32)
                            for _ in range(n_lin))
    if "conv" in attn:
        shape = (slots, cfg.conv_kernel - 1, cfg.d_model)
        k["conv"] = tuple(jnp.zeros(shape, dt)
                          for _ in range(attn.count("conv")))
    return k, v


def refuse_state_rows(cache, what: str) -> None:
    if isinstance(cache, dict) and set(STATE_KINDS) & set(cache):
        raise ValueError(
            f"{what}: a model with linear, sparse or conv layers keeps a "
            f"recurrent state, compressed keys or a convolution's last "
            f"rows beside its K/V rows, and the row exchange of "
            f"prefill/decode disaggregation does not carry them")


def _rows_of_slots(buf, slots):
    """``buf[slots]`` of a per-layer buffer as one small dynamic slice a
    row (a gather over a large buffer lowers to slices of all of it)."""
    return jnp.stack([lax.dynamic_index_in_dim(buf, slots[r], 0, False)
                      for r in range(slots.shape[0])])


def _set_at(buffers: tuple, i: int, new) -> tuple:
    return buffers[:i] + (new,) + buffers[i + 1:]


def _positions_kind(cache) -> str:
    """The attention kind whose buffers hold every position of a slot:
    idle lanes park at its last one."""
    return "full" if not isinstance(cache, dict) or "full" in cache \
        else "sparse"


def _sparse_sizes(cfg: TransformerConfig) -> dict:
    return dict(topk=cfg.sparse_topk, init=cfg.sparse_init_blocks,
                window=cfg.sparse_window, block=cfg.sparse_block,
                dense_len=cfg.sparse_dense_len)


def _head_rows(buf, rows, at, how: str = "set"):
    """``rows`` [S, Hkv, D] into a head-major buffer [S, Hkv, R, D] at
    row ``at[slot]`` of every head: one scatter over the buffer seen as
    [S * Hkv, R, D] — its window is the minor dim alone, so XLA updates
    in place (with the heads inside the window it re-lays the whole
    buffer out, twice a call). ``how``: set, add or multiply."""
    s, h = buf.shape[:2]
    flat = buf.reshape((s * h,) + buf.shape[2:])
    put = getattr(flat.at[jnp.arange(s * h), jnp.repeat(at, h)], how)
    return put(rows.reshape((s * h,) + rows.shape[2:]).astype(buf.dtype),
               indices_are_sorted=True, unique_indices=True,
               mode="promise_in_bounds").reshape(buf.shape)


def _sparse_decode(q, k_new, v_new, k_all, v_all, i, pos, wpos, parked, cfg,
                   scale):
    """A sparse layer's decode step for every slot: the new K/V rows
    into layer ``i``'s buffers at ``wpos``, the new key into the K^c rows
    of the kernels that hold its position (SET where it starts one,
    parked lanes into the parking row), the selection for each KV group
    from K^c, and attention over the selected blocks only."""
    kc, vc, comp = k_all["sparse"][i], v_all["sparse"][i], k_all["sparse_kc"][i]
    n_s = wpos.shape[0]
    kc = _head_rows(kc, k_new[:, 0], wpos)
    vc = _head_rows(vc, v_new[:, 0], wpos)
    kern, stride = cfg.sparse_kernel, cfg.sparse_stride
    park = comp.shape[2] - 1
    share = k_new[:, 0].astype(jnp.float32) / kern
    for back in range(kern // stride):
        row = wpos // stride - back
        row = jnp.where(parked | (row < 0), park, row)
        if back == 0:
            keep = jnp.where(wpos % stride == 0, 0.0, 1.0)
            comp = _head_rows(comp, jnp.broadcast_to(
                keep[:, None, None], share.shape), row, "multiply")
        comp = _head_rows(comp, share, row, "add")
    h_kv = kc.shape[1]
    t_max = kc.shape[2]
    at = jnp.minimum(pos, t_max - 1)
    qg = q.reshape(n_s, 1, h_kv, -1, q.shape[-1])
    scores = hybrid.block_scores(
        qg, comp, at[:, None], scale=scale, kernel=kern, stride=stride,
        block=cfg.sparse_block)[:, :, 0]
    idx, ok = hybrid.select_block_list(scores, at, **_sparse_sizes(cfg))
    o = hybrid.sparse_decode_attention(
        q[:, 0], kc, vc, idx, ok, at, scale=scale, block=cfg.sparse_block)
    # what the kernel was handed, counted where it was chosen: the listed
    # blocks' keys up to the query, per KV group, of the lanes that decode
    block = cfg.sparse_block
    keys = ok.sum(-1) * block - (block - 1 - at % block)[:, None]
    read = jnp.where(parked[:, None], 0, keys).sum().astype(jnp.int32)
    k_all = {**k_all, "sparse": _set_at(k_all["sparse"], i, kc),
             "sparse_kc": _set_at(k_all["sparse_kc"], i, comp)}
    v_all = {**v_all, "sparse": _set_at(v_all["sparse"], i, vc)}
    return o[:, None], k_all, v_all, read


def _sparse_prefill(q, k_new, v_new, k_all, v_all, i, slots, starts,
                    n_valids, cfg, scale):
    """A sparse layer's prefill round: each row's chunk into its slot's
    K/V at ``starts``, its share of K^c (the kernels that start in the
    chunk SET, the ones that started before it added to; a duplicated
    padding row adds to the parking row), then the chunk's queries
    against the slot's cache under each token's block mask — computed
    only where some row of the round has passed ``sparse_dense_len``."""
    kc, vc, comp = k_all["sparse"][i], v_all["sparse"][i], k_all["sparse_kc"][i]
    p, c = q.shape[:2]
    kern, stride, block = (cfg.sparse_kernel, cfg.sparse_stride,
                           cfg.sparse_block)
    park = comp.shape[2] - 1
    rows, behind = hybrid.compress_rows(k_new, n_valids, kernel=kern,
                                        stride=stride)
    first = (slots != slots[0]) | (jnp.arange(p) == 0)
    k_rows = k_new.astype(kc.dtype).transpose(0, 2, 1, 3)     # [P,Hkv,C,D]
    v_rows = v_new.astype(vc.dtype).transpose(0, 2, 1, 3)

    def write_one(r, bufs):
        kc, vc, comp = bufs
        kc = lax.dynamic_update_slice(kc, k_rows[r][None],
                                      (slots[r], 0, starts[r], 0))
        vc = lax.dynamic_update_slice(vc, v_rows[r][None],
                                      (slots[r], 0, starts[r], 0))
        comp = lax.dynamic_update_slice(
            comp, rows[r][None], (slots[r], 0, starts[r] // stride, 0))
        for back in range(1, kern // stride):
            row = starts[r] // stride - back
            row = jnp.where(first[r] & (row >= 0), row, park)
            old = lax.dynamic_slice(
                comp, (slots[r], 0, row, 0), (1, comp.shape[1], 1,
                                              comp.shape[3]))
            comp = lax.dynamic_update_slice(
                comp, old + behind[r, back - 1][None, :, None, :],
                (slots[r], 0, row, 0))
        return kc, vc, comp

    kc, vc, comp = lax.fori_loop(0, p, write_one, (kc, vc, comp))
    ends = starts + c
    qpos = starts[:, None] + jnp.arange(c)[None, :]
    h_kv = kc.shape[1]
    n_blocks = kc.shape[2] // block

    def chosen(_):
        scores = hybrid.block_scores(
            q.reshape(p, c, h_kv, -1, q.shape[-1]),
            _rows_of_slots(comp, slots), qpos, scale=scale,
            kernel=kern, stride=stride, block=block)
        return hybrid.select_blocks(scores, qpos, **_sparse_sizes(cfg))

    sel = lax.cond(jnp.any(ends > cfg.sparse_dense_len), chosen,
                   lambda _: jnp.ones((p, h_kv, c, n_blocks), bool), None)
    o = hybrid.sparse_prefill_attention(q, kc, vc, sel, slots, ends,
                                        scale=scale, block=block)
    k_all = {**k_all, "sparse": _set_at(k_all["sparse"], i, kc),
             "sparse_kc": _set_at(k_all["sparse_kc"], i, comp)}
    v_all = {**v_all, "sparse": _set_at(v_all["sparse"], i, vc)}
    return o, k_all, v_all


def _linear_prefill(q, k_new, v_new, k_all, i, slots, starts, n_valids, cfg,
                    scale):
    """A lightning layer's prefill round: each row's state read out of
    layer ``i``'s buffer (zero where the chunk starts a prompt), the
    chunk in the chunked form, the states written back in row order — a
    duplicated padding row read the same state and writes the same one."""
    state = k_all["linear"][i]
    p = q.shape[0]
    s_in = jnp.where((starts == 0)[:, None, None, None], 0.0,
                     _rows_of_slots(state, slots))
    o, s_out = hybrid.lightning_prefill(
        (q.astype(jnp.float32) * scale).astype(q.dtype), k_new, v_new, s_in,
        hybrid.linear_decay_slopes(cfg.n_heads), n_valids)
    state = lax.fori_loop(
        0, p, lambda r, st: lax.dynamic_update_slice(
            st, s_out[r][None], (slots[r], 0, 0, 0)), state)
    return o, {**k_all, "linear": _set_at(k_all["linear"], i, state)}


def _conv_decode(g, k_all, i, parked):
    """A conv layer's decode step: every lane's state rows (the g of its
    slot's last ``conv_kernel - 1`` positions) read, and shifted by the
    new row ``g`` [S, 1, d]. The lanes ARE the slots, in order, so a lane
    that does not decode (free, or in mid-prefill) keeps its rows by a
    select, bit for bit: the state needs no parking row, and no scatter
    (one over duplicate rows serialises on the TPU)."""
    states = k_all["conv"]
    before = states[i]                                      # [S, L - 1, d]
    after = jnp.concatenate([before, g.astype(before.dtype)], axis=1)[:, 1:]
    new = jnp.where(parked[:, None, None], before, after)
    return before, {**k_all, "conv": _set_at(states, i, new)}


def _conv_prefill(g, k_all, i, slots, starts, n_valids):
    """A conv layer's prefill round: each row's state read out of layer
    ``i``'s buffer (zeros where the chunk starts a prompt, whatever the
    slot held), and the state after the chunk's VALID positions — the
    ``conv_kernel - 1`` rows of (state, chunk) that end at its last valid
    one — written back in row order; a duplicated padding row read the
    same rows and writes the same ones."""
    state = k_all["conv"][i]
    p, keep = g.shape[0], state.shape[1]
    before = jnp.where((starts == 0)[:, None, None], 0,
                       _rows_of_slots(state, slots))       # [P, L - 1, d]
    rows = jnp.concatenate([before, g.astype(state.dtype)], axis=1)
    after = jax.vmap(lambda r, n: lax.dynamic_slice_in_dim(r, n, keep, 0))(
        rows, n_valids)
    state = lax.fori_loop(
        0, p, lambda r, st: lax.dynamic_update_slice(
            st, after[r][None], (slots[r], 0, 0)), state)
    return before, {**k_all, "conv": _set_at(k_all["conv"], i, state)}


def cache_inject_rows(cache, slot: int, rows):
    """Host-side write of float rows [L, P, Hkv, Dh] into one slot's
    prefix (the inject half of prefill/decode disaggregation; the
    exchange format is rows at their logical width, whatever the
    storage form). A layered cache takes what ``cache_export_rows``
    gave of one: a dict by attention kind, the window kind's rows being
    the LAST positions of the prefix. A model with linear or sparse
    layers is refused: rows do not carry its state or its K^c."""
    refuse_state_rows(cache, "cache_inject_rows")
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
    return cache.at[:, slot, :p].set(_encode(cache, jnp.asarray(rows)))


def cache_export_rows(cache, slot: int, length: int, width: int = 0):
    """One slot's KV prefix as float rows [L, length, Hkv, Dh] — the
    export half of the exchange contract ``cache_inject_rows``
    documents. A layered cache exports a dict by attention kind at the
    logical ``width``: all ``length`` positions of its full layers, and
    of its window layers the last ``ring`` (or fewer), in position
    order. A model with linear or sparse layers is refused, as in
    ``cache_inject_rows``."""
    refuse_state_rows(cache, "cache_export_rows")
    if isinstance(cache, dict):
        out = {}
        for kind, stack in cache.items():
            at = jnp.arange(length)
            if kind == "window":
                ring = _cache_tmax(stack) - 1
                at = jnp.arange(max(0, length - ring), length) % ring
            out[kind] = _logical(_materialize(
                jax.tree.map(lambda buf: buf[:, slot, at], stack)), width)
        return out
    return _logical(cache[:, slot, :length], width)


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
    reads every slot's whole reservation: scores that fit at once."""
    full = jax.tree.leaves(_kind(k_all, "full"))[0]
    t = full.shape[2]
    if _scores_fit(p, c, cfg.n_heads, t):
        return 0
    return prefill_key_block(t, full.shape[3])


def decode_read_block(k_all) -> int:
    """Positions in one key block of a decode step's read of the FULL
    layers (``cache_decode_attention``'s kernel)."""
    full = jax.tree.leaves(_kind(k_all, "full"))[0]
    return decode_key_block(full.shape[2], full.shape[3])


def decode_read_positions(pos, parked, t_max: int, block: int) -> int:
    """Key positions decode steps read in ONE full layer, over all lanes
    (host arrays ``pos`` and ``parked`` of one shape: [S], or [S, steps]
    for a window): a lane that decodes reads whole blocks of ``block``
    positions up to the one that holds its position — the kernel's own
    ``decode_last_block`` — and a parked lane none (``decode_window``
    hands the kernel -1 for it). Counted by the kernel's rule whatever
    path runs, as ``prefill_read_block`` counts."""
    blocks = decode_last_block(pos, t_max, block) + 1
    return int(blocks[~parked].sum()) * block


def _attend_rows(q, kc, vc, at, slots, starts, mask, scale, sink, cfg, attn):
    """A round's chunks [P, C, Hq, Dk] against layer ``at`` of their
    slots' cache, the chunks already written. Where the whole batch's
    float32 scores fit (``SCORES_LIMIT``): the slots' rows read out and
    one ``grouped_cache_attention`` under ``mask``. Where they do not (a
    full layer at 8,192 positions under a 128-token chunk): through
    ``cache_prefill_attention``, which reads the stack where it lies and
    only up to each chunk's end; a ring of that size keeps the plain
    path, row by row."""
    p, c, n_h, d_k = q.shape
    if attn == "full" and prefill_read_block(cfg, kc, p, c):
        return cache_prefill_attention(q, kc, vc, at, slots, starts + c,
                                       scale=scale, sink=sink)
    k = _read_slots(kc, at, slots, d_k)
    v = _read_slots(vc, at, slots, cfg.v_dim)
    attention = (grouped_cache_attention
                 if _scores_fit(p, c, n_h, k.shape[1])
                 else rowwise_cache_attention)
    return attention(q, k, v, mask, scale=scale, sink=sink)


def _sample_slots(logits, temp, key):
    """Per-slot sampling: greedy where ``temp == 0``, else temperature
    sampling. One key serves the whole slot batch — the Gumbel noise
    tensor is keyed per (row, vocab) position, so each row's draw is
    independent of every other row's logits. The categorical branch
    hides behind ``lax.cond``: an all-greedy slot batch (the common
    serving default) must not pay for threefry over [S, V]."""
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
                  base_key, draw0, prev=None, *, cfg: TransformerConfig,
                  steps: int = 1):
    """``steps`` decode iterations for every slot in ONE dispatch: feed
    ``tokens`` [S] at each slot's own ``pos``, write the new K/V row at
    ``wpos``, attend the slot's cache prefix, sample the next token per
    slot, advance, repeat. ``steps`` is the host-sync window — the
    throughput/latency knob (``tony.serving.decode-window``): 1 keeps
    admission and EOS retirement exactly per-token; a deeper window
    amortizes the per-dispatch host cost (launch, fenced readback) over
    ``steps`` tokens at the price of up to ``steps - 1`` wasted
    lane-steps per retiring stream.

    pos/wpos/temp live on the HOST between windows (tiny [S] arrays;
    the scheduler mutates them freely on admit/retire, and they advance
    by rule, not by what was sampled) and ride in as arguments. The fed
    tokens do so only where the host has one the device has not: with
    ``prev`` (the window before this one, ``[S, steps]`` as it was
    returned and never read back before this launch) a lane whose
    ``tokens`` entry is negative is fed ``prev[:, -1]``, so the next
    window can be launched while the last one is still on its way home;
    a lane that became active since (a prompt's first token, shipped KV)
    gives its token as a value >= 0. Without ``prev`` every entry of
    ``tokens`` is fed as it is. The KV caches are device-resident state
    (donated — the caller must adopt the returned buffers). Sampling
    keys derive INSIDE the jit (``fold_in(base_key, draw0 + i)`` — a
    host-side fold_in is a whole extra dispatch per iteration), so the
    schedule is positional and reproducible from (seed, draw counter).

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
    expert, and read no key of a stacked cache kind: the decode kernel
    is handed -1 for them (their stale ``pos`` would name a last
    tenant's blocks), and their rows of the result are discarded.

    Returns (k_all, v_all, window_tokens [S, steps] int32, counts): the
    expert layers' counters summed over the window (``pairs``: the
    (token, choice) pairs each held expert received; ``passes``: the
    passes the layers ran, ``_moe_mlp_decode``) and the sparse layers'
    (``sparse_keys``: the keys their selection listed, per KV group, for
    the lanes that decode), or None for a model with neither.
    """
    dt = cfg.compute_dtype
    t_max = _cache_tmax(_kind(k_all, _positions_kind(k_all)))
    ropes = rope_tables(cfg)
    scale = cfg.head_dim ** -0.5
    if prev is not None:
        tokens = jnp.where(tokens < 0, prev[:, -1], tokens)

    def one_step(carry, i):
        k_all, v_all, pos, wpos, tokens = carry
        x = params["embed"][tokens][:, None, :].astype(dt)  # [S, 1, d]
        x = embed_scaled(x, cfg)
        # Visibility after the write: keys 0..pos inclusive (index pos
        # holds the token being fed this step). Inactive lanes' pos can
        # run past the table mid-window — clamp the RoPE gather (their
        # output is discarded; the mask itself cannot overflow).
        rp = jnp.minimum(pos, cfg.max_seq - 1)[:, None]
        parked = wpos >= t_max - 1
        # What a stacked kind's lane attends up to: a parked lane (free
        # with a stale pos, or in prefill) reads no key at all.
        seen = jnp.where(parked, -1, jnp.minimum(pos, t_max - 1))

        def layer(x, lp, attn, at, k_all, v_all):
            sparse_keys = None

            def attend(q, k_new, v_new, attn, sink):
                nonlocal k_all, v_all, sparse_keys
                if attn == "conv":
                    before, k_all = _conv_decode(q, k_all, at, parked)
                    return before
                if attn == "linear":
                    # a lane that is not decoding reads and writes the
                    # parking row: its slot's state stays as it is
                    states = k_all["linear"]
                    lane = jnp.arange(parked.shape[0])
                    o, new = hybrid.lightning_decode(
                        (q[:, 0].astype(jnp.float32) * scale).astype(dt),
                        k_new[:, 0], v_new[:, 0], states[at],
                        jnp.where(parked, parked.shape[0], lane),
                        hybrid.linear_decay_slopes(cfg.n_heads))
                    k_all = {**k_all,
                             "linear": _set_at(states, at, new)}
                    return o[:, None]
                if attn == "sparse":
                    o, k_all, v_all, sparse_keys = _sparse_decode(
                        q, k_new, v_new, k_all, v_all, at, pos, wpos,
                        parked, cfg, scale)
                    return o
                # a stacked kind: its layer is indexed on the device
                kc, vc = _kind(k_all, attn), _kind(v_all, attn)
                row, window, w_at = jnp.int32(at), 0, wpos
                if attn == "window":
                    window, ring = cfg.window, _cache_tmax(kc) - 1
                    w_at = jnp.where(parked, ring, wpos % ring)
                kc = _write_rows(kc, row, k_new[:, 0], w_at)
                vc = _write_rows(vc, row, v_new[:, 0], w_at)
                o = cache_decode_attention(
                    q[:, 0], kc, vc, row, seen, scale=scale,
                    window=window, sink=sink,
                )[:, None]
                k_all = _with_kind(k_all, attn, kc)
                v_all = _with_kind(v_all, attn, vc)
                return o

            x, counts = serve_layer(x, lp, attn, cfg, ropes, rp, attend,
                                    token_mask=~parked[:, None])
            if sparse_keys is not None:
                counts = {**(counts or {}), "sparse_keys": sparse_keys}
            return x, k_all, v_all, counts

        x, k_all, v_all, counts = run_layers(x, params, k_all, v_all, cfg,
                                             layer)
        nxt = _sample_slots(
            lm_head(x[:, -1:], params, cfg), temp,
            jax.random.fold_in(base_key, draw0 + i)
        )
        pos = pos + 1
        wpos = jnp.minimum(wpos + 1, t_max - 1)
        return (k_all, v_all, pos, wpos, nxt), (nxt, counts)

    (k_all, v_all, _, _, _), (toks, counts) = lax.scan(
        one_step, (k_all, v_all, pos, wpos, tokens), jnp.arange(steps)
    )
    return (k_all, v_all, toks.T,   # [S, steps]
            jax.tree.map(lambda c: c.sum(0), counts))


@functools.partial(
    jax.jit, static_argnames=("cfg",), donate_argnums=(1, 2)
)
def prefill_chunks(params, k_all, v_all, tokens, slots, starts, n_valids,
                   temps, base_key, draw, cfg: TransformerConfig):
    """Prefill one chunk for EACH of P pending slots in one dispatch:
    ``tokens`` [P, C] row i is written into slot ``slots[i]`` at
    positions [starts[i], starts[i] + C). Batching the pending slots is
    the prefill twin of the slot-batch decode step: a dispatch per
    chunk pays the fixed launch and readback P times over, and on TPU a
    [1, C] chunk cannot fill the MXU.

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

    Returns (k_all, v_all, first_tokens [P], logits [P, V] fp32, counts):
    row i's token samples from position ``n_valids[i] - 1`` — meaningful
    only on a request's FINAL chunk (earlier chunks' sample is
    discarded by the scheduler; computing it unconditionally keeps one
    executable). ``counts``: the expert layers' counters summed
    (``pairs``: the (token, choice) pairs each held expert received from
    the rows' valid tokens, a duplicated row counted once; ``passes``:
    the passes the layers ran); None for a model without experts."""
    dt = cfg.compute_dtype
    p, c = tokens.shape
    t_max = _cache_tmax(_kind(k_all, _positions_kind(k_all)))
    ropes = rope_tables(cfg)
    scale = cfg.head_dim ** -0.5
    positions = starts[:, None] + jnp.arange(c)[None, :]       # [P, C]
    # Padded tail positions can run past the RoPE table; clamp the
    # gather (values are garbage, discarded) — the write offset itself
    # is host-validated.
    rope_pos = jnp.minimum(positions, cfg.max_seq - 1)
    x = embed_scaled(params["embed"][tokens].astype(dt), cfg)  # [P, C, d]
    masks = {}
    if _positions_kind(k_all) == "full":
        masks["full"] = (positions[:, :, None]
                         >= jnp.arange(t_max)[None, None, :])  # [P, C, T]
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
            if attn == "conv":
                before, k_all = _conv_prefill(q, k_all, at, slots, starts,
                                              n_valids)
                return before
            if attn == "linear":
                o, k_all = _linear_prefill(q, k_new, v_new, k_all, at,
                                           slots, starts, n_valids, cfg,
                                           scale)
                return o
            if attn == "sparse":
                o, k_all, v_all = _sparse_prefill(
                    q, k_new, v_new, k_all, v_all, at, slots, starts,
                    n_valids, cfg, scale)
                return o
            # a stacked kind: its layer is indexed on the device
            kc, vc = _kind(k_all, attn), _kind(v_all, attn)
            row = jnp.int32(at)
            write = _write_chunk_ring if attn == "window" else _write_chunk

            def write_one(i, kv):
                where = (row, slots[i], starts[i])
                return (write(kv[0], *where, k_new[i]),
                        write(kv[1], *where, v_new[i]))

            # Sequential writes, not a vmap-scatter: P is small and
            # duplicate (padding) rows must overwrite cleanly in order.
            kc, vc = lax.fori_loop(0, p, write_one, (kc, vc))
            o = _attend_rows(q, kc, vc, row, slots, starts, masks[attn],
                             scale, sink, cfg, attn)
            k_all = _with_kind(k_all, attn, kc)
            v_all = _with_kind(v_all, attn, vc)
            return o

        x, counts = serve_layer(x, lp, attn, cfg, ropes, rope_pos, attend,
                                count_mask=counted)
        return x, k_all, v_all, counts

    x, k_all, v_all, counts = run_layers(x, params, k_all, v_all, cfg, layer)
    last = jnp.take_along_axis(
        x, jnp.maximum(n_valids - 1, 0)[:, None, None], axis=1
    )                                                          # [P, 1, d]
    logits = lm_head(last, params, cfg)
    toks = _sample_slots(logits, temps,
                         jax.random.fold_in(base_key, draw))
    return k_all, v_all, toks, logits, counts
