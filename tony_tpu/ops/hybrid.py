"""Attention of the two layer kinds a hybrid model serves beside softmax
attention over K/V rows: lightning (linear) attention over a per-head
recurrent state, and InfLLM-V2 block-sparse attention that selects the
key blocks a query reads.

**Lightning attention.** Per head a float32 state S [D, D]: ``S_t = lam *
S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t``, no softmax; ``lam = exp(-slope)``
with the head's slope (``linear_decay_slopes``). Decode applies that one
step to every live slot's state where it lies (``lightning_decode``: the
state buffer is aliased in and out of the kernel, a slot that is not live
is mapped to the buffer's parking row and its state stays bit for bit).
Prefill runs a chunk of C positions in the chunked form
(``lightning_prefill``): inside the chunk ``o = ((Q K^T) * D) V + diag(lam^
(i+1)) Q S_in`` with ``D_ij = lam^(i-j)`` for j <= i, and ``S_out = lam^n
S_in + sum_{j<n} lam^(n-1-j) k_j^T v_j`` over the chunk's ``n`` valid
positions, so a padded tail leaves no trace in the state.

**Block-sparse attention.** Beside K and V a sparse layer keeps the means
of its keys over ``kernel`` positions every ``stride`` (``K^c``, float32).
``block_scores`` scores a query's KV group against them (softmax over the
complete kernels, summed over the group's heads, the maximum over the
kernels that overlap a block), ``select_blocks`` gives the blocks read: the
first ``init`` blocks, the blocks that cover the last ``window`` positions,
and the ``topk`` best of the rest; a query before ``dense_len`` reads every
block. ``sparse_decode_attention`` reads ONLY those blocks out of the cache
(their indices ride as scalar prefetch into the kernel's index maps);
``sparse_prefill_attention`` runs a chunk's queries against the cache up to
the chunk's end under the per-token block mask.

Cache layout of a sparse layer (one buffer per layer: a layered model
walks its layers in a static loop, so nothing ever slices a layer out of a
stack): K, V ``[S, Hkv, Tmax, D]`` head-major, so that a block of one KV
head is one contiguous [block, D] tile; K^c ``[S, Hkv, Tmax/stride + 1,
D]`` float32, the last row parking. A lightning layer: ``[S + 1, H, D, D]``
float32, the last row parking.

Every op has a plain ``jax`` path (the CPU's, and the definition the
kernels are tested against) and a Pallas path (``mode`` as in
``ops/attention.py``: "auto" takes the kernel on a TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from tony_tpu.ops.attention import (
    NEG_INF,
    _on_tpu,
    _online_softmax_step,
)


# The kernels' tile sizes (each clipped to what a small shape has): heads
# of one lightning decode program (16 x 64 KB of state a block), selected
# blocks one sparse decode program fetches, and of the sparse prefill a key
# tile's positions, the query heads that share it and the blocks one mask
# tile names.
LIGHTNING_DECODE_HEADS = 16
SPARSE_DECODE_BLOCKS = 16
SPARSE_PREFILL_KEYS = 512
SPARSE_PREFILL_HEADS = 8
SPARSE_PREFILL_MASK_BLOCKS = 128


def _mode(mode: str) -> str:
    if mode == "auto":
        return "pallas" if _on_tpu() else "jax"
    return mode


# ---------------------------------------------------------------------------
# Lightning attention
# ---------------------------------------------------------------------------

def linear_decay_slopes(n_heads: int) -> jax.Array:
    """The Lightning Attention slopes, float32 [H]: head h (1-based) of H
    decays by ``exp(-2^(-8h/H))`` a position."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / n_heads)


def _lightning_prefill_jax(q, k, v, s_in, slopes, n_valid):
    c = q.shape[1]
    qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (q, k, v))   # [P,H,C,D]
    i = jnp.arange(c, dtype=jnp.float32)
    sl = slopes[:, None, None]
    diff = i[:, None] - i[None, :]
    dec = jnp.where(diff >= 0, jnp.exp(-sl * jnp.maximum(diff, 0.0)), 0.0)
    a = jnp.einsum("phid,phjd->phij", qh, kh,
                   preferred_element_type=jnp.float32) * dec[None]
    o = jnp.einsum("phij,phjd->phid", a.astype(v.dtype), vh,
                   preferred_element_type=jnp.float32)
    qd = qh.astype(jnp.float32) * jnp.exp(-sl * (i + 1.0)[None, :, None])
    o = o + jnp.einsum("phid,phde->phie", qd, s_in,
                       precision=lax.Precision.HIGHEST)
    n = n_valid.astype(jnp.float32)[:, None, None]               # [P,1,1]
    w = jnp.where(i[None, None, :] < n,
                  jnp.exp(-slopes[None, :, None]
                          * jnp.maximum(n - 1.0 - i[None, None, :], 0.0)),
                  0.0)                                           # [P,H,C]
    kw = kh.astype(jnp.float32) * w[..., None]
    s_out = (jnp.exp(-slopes[None, :, None] * n)[..., None] * s_in
             + jnp.einsum("phjd,phje->phde", kw, vh.astype(jnp.float32),
                          precision=lax.Precision.HIGHEST))
    return o.transpose(0, 2, 1, 3), s_out


def _lightning_prefill_kernel(nv_ref, slope_ref, q_ref, k_ref, v_ref, s_ref,
                              o_ref, so_ref):
    """One program = one (row, head): the chunk's [C, D] q, k, v, the
    head's state in and out."""
    n = nv_ref[pl.program_id(0)].astype(jnp.float32)
    c, d = q_ref.shape
    # the head's slope in every lane of a row: Mosaic broadcasts a row
    # over sublanes, not a scalar over both
    sl_c, sl = slope_ref[0:1, :c], slope_ref[0:1, :d]
    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)      # [C, C]
    diff = (lax.broadcasted_iota(jnp.int32, (c, c), 0)
            - lax.broadcasted_iota(jnp.int32, (c, c), 1))
    dec = jnp.where(diff >= 0,
                    jnp.exp(-sl_c * jnp.maximum(diff, 0).astype(jnp.float32)),
                    0.0)
    o = jnp.dot((s * dec).astype(v.dtype), v,
                preferred_element_type=jnp.float32)
    at = lax.broadcasted_iota(jnp.int32, (c, 1), 0).astype(jnp.float32)
    s_in = s_ref[...]
    o = o + jnp.dot(q.astype(jnp.float32) * jnp.exp(-sl * (at + 1.0)), s_in,
                    preferred_element_type=jnp.float32,
                    precision=lax.Precision.HIGHEST)
    o_ref[...] = o
    w = jnp.where(at < n, jnp.exp(-sl * jnp.maximum(n - 1.0 - at, 0.0)), 0.0)
    so_ref[...] = jnp.exp(-sl * n) * s_in + lax.dot_general(
        k.astype(jnp.float32) * w, v.astype(jnp.float32),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST)


def _lightning_prefill_pallas(q, k, v, s_in, slopes, n_valid, interpret):
    from jax.experimental.pallas import tpu as pltpu

    p, c, h, d = q.shape
    qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    lanes = max(c, d)
    slope_rows = jnp.broadcast_to(slopes[:, None, None], (h, 8, lanes))

    def row_map(r, i, nv):
        return (r, i, 0, 0)

    chunk = pl.BlockSpec((None, None, c, d), row_map)
    state = pl.BlockSpec((None, None, d, d), row_map)
    o, s_out = pl.pallas_call(
        _lightning_prefill_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(p, h),
            in_specs=[pl.BlockSpec((None, 8, lanes),
                                   lambda r, i, nv: (i, 0, 0)),
                      chunk, chunk, chunk, state],
            out_specs=[chunk, state],
        ),
        out_shape=[jax.ShapeDtypeStruct((p, h, c, d), jnp.float32),
                   jax.ShapeDtypeStruct((p, h, d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(n_valid.astype(jnp.int32), slope_rows, qh, kh, vh, s_in)
    return o.transpose(0, 2, 1, 3), s_out


def lightning_prefill(q, k, v, s_in, slopes, n_valid, *, mode: str = "auto"):
    """One chunk per row in the chunked form. q (already scaled), k, v:
    [P, C, H, D]; ``s_in`` [P, H, D, D] float32, the state before the
    chunk (zero for a chunk that starts a prompt); ``n_valid`` [P]: the
    chunk's real positions, the rest padding. -> (o [P, C, H, D] float32,
    ``s_out`` [P, H, D, D] float32, the state after position
    ``n_valid - 1``). On a TPU result shapes (f32[P,H,C,D],
    f32[P,H,D,D]) name the call in a trace."""
    mode = _mode(mode)
    if mode == "jax":
        return _lightning_prefill_jax(q, k, v, s_in, slopes, n_valid)
    return _lightning_prefill_pallas(q, k, v, s_in, slopes, n_valid,
                                     mode == "interpret")


def _lightning_decode_jax(q, k, v, state, slot_of, slopes):
    s_in = state[slot_of]                                        # [S,H,D,D]
    lam = jnp.exp(-slopes)[None, :, None, None]
    new = lam * s_in + (k.astype(jnp.float32)[..., :, None]
                        * v.astype(jnp.float32)[..., None, :])
    o = jnp.einsum("shd,shde->she", q.astype(jnp.float32), new,
                   precision=lax.Precision.HIGHEST)
    return o, state.at[slot_of].set(new)


def _lightning_decode_kernel(slot_ref, slope_ref, q_ref, k_ref, v_ref, s_ref,
                             o_ref, so_ref, *, heads):
    """One program = one (slot, block of ``heads`` heads): the step
    ``S <- lam S + k^T v``, ``o = q S`` on the state where it lies. The
    outer product and the read run on the MXU over operands of 16 rows,
    the token in row 0 and zeros under it, in single bfloat16 passes
    that lose nothing: q, k and v ARE bfloat16 values (their products
    are exact in the float32 accumulator), and the float32 state is read
    as the sum of two bfloat16 halves (16 of its 24 bits; six passes of
    a float32 product took 2.4 times the state's own traffic)."""
    d = q_ref.shape[-1]
    first = lax.broadcasted_iota(jnp.int32, (16, d), 0) == 0
    low = q_ref.dtype
    # float32 tokens (a test's): the products in full float32 instead
    full = None if low == jnp.bfloat16 else lax.Precision.HIGHEST
    for i in range(heads):
        # (the mask is laid out for 32-bit rows: select there, then cast)
        k16 = jnp.where(first, k_ref[i:i + 1, :].astype(jnp.float32),
                        0.0).astype(low)
        v16 = jnp.broadcast_to(v_ref[i:i + 1, :], (16, d))
        outer = lax.dot_general(
            k16, v16, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=full)  # [D, D]
        new = jnp.exp(-slope_ref[i]) * s_ref[i] + outer
        so_ref[i] = new
        q16 = jnp.broadcast_to(q_ref[i:i + 1, :], (16, d))
        if full is None:
            high = new.astype(low)
            rest = (new - high.astype(jnp.float32)).astype(low)
            o = (jnp.dot(q16, high, preferred_element_type=jnp.float32)
                 + jnp.dot(q16, rest, preferred_element_type=jnp.float32))
        else:
            o = jnp.dot(q16, new, preferred_element_type=jnp.float32,
                        precision=full)
        o_ref[i:i + 1, :] = o[0:1]


def _lightning_decode_pallas(q, k, v, state, slot_of, slopes, interpret):
    from jax.experimental.pallas import tpu as pltpu

    s, h, d = q.shape
    heads = min(LIGHTNING_DECODE_HEADS, h)
    if h % heads:
        heads = 1
    if q.dtype != jnp.bfloat16:
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    slope_rows = jnp.broadcast_to(slopes[:, None, None], (h, 1, d))
    token = pl.BlockSpec((None, heads, d), lambda i, j, slot: (i, j, 0))
    held = pl.BlockSpec((None, heads, d, d),
                        lambda i, j, slot: (slot[i], j, 0, 0))
    return pl.pallas_call(
        functools.partial(_lightning_decode_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, h // heads),
            in_specs=[pl.BlockSpec((heads, 1, d),
                                   lambda i, j, slot: (j, 0, 0)),
                      token, token, token, held],
            out_specs=[token, held],
        ),
        out_shape=[jax.ShapeDtypeStruct((s, h, d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 5 (the state, after the prefetched slots, the slopes
        # and q, k, v) is result 1: updated where it lies
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(slot_of.astype(jnp.int32), slope_rows, q, k, v, state)


def lightning_decode(q, k, v, state, slot_of, slopes, *, mode: str = "auto"):
    """One position per slot. q (already scaled), k, v: [S, H, D];
    ``state`` [S + 1, H, D, D] float32, donated; ``slot_of`` [S]: the row
    of ``state`` each lane reads and writes — its own for a live slot,
    the parking row S for every other (whose state then stays as it is,
    bit for bit). -> (o [S, H, D] float32, the state). On a TPU the
    state is read and written once, in place; result shapes
    (f32[S,H,D], f32[S+1,H,D,D])."""
    mode = _mode(mode)
    if mode == "jax":
        return _lightning_decode_jax(q, k, v, state, slot_of, slopes)
    return _lightning_decode_pallas(q, k, v, state, slot_of, slopes,
                                    mode == "interpret")


# ---------------------------------------------------------------------------
# Block-sparse attention: the selection
# ---------------------------------------------------------------------------

def compress_rows(k, n_valid, *, kernel: int, stride: int):
    """A chunk's share of the compressed keys. k [P, C, Hkv, D], the
    chunk's keys (C in whole strides, the chunk starting on a stride);
    positions >= ``n_valid`` [P] count as zero. -> (rows [P, Hkv, C /
    stride, D] float32: for each kernel that STARTS in the chunk the sum
    of its keys inside the chunk over ``kernel``; behind [P, kernel /
    stride - 1, Hkv, D]: what the chunk adds to the kernels that start
    1, 2, ... strides before it)."""
    p, c, h_kv, d = k.shape
    real = jnp.arange(c)[None, :] < n_valid[:, None]
    kz = jnp.where(real[:, :, None, None], k.astype(jnp.float32), 0.0)
    sums = kz.reshape(p, c // stride, stride, h_kv, d).sum(2) / kernel
    r = kernel // stride
    pad = jnp.pad(sums, ((0, 0), (0, r - 1), (0, 0), (0, 0)))
    rows = sum(pad[:, i:i + c // stride] for i in range(r))
    behind = jnp.stack(
        [sums[:, :r - i].sum(1) for i in range(1, r)], axis=1
    ) if r > 1 else jnp.zeros((p, 0, h_kv, d), jnp.float32)
    return rows.transpose(0, 2, 1, 3), behind


def block_scores(q, kc, qpos, *, scale: float, kernel: int, stride: int,
                 block: int):
    """Block scores of each query's KV groups. q [N, C, G, Hg, D]; kc
    [N, G, R, D] float32 (rows past the complete kernels are masked by
    position, a parking row with them); qpos [N, C]. -> B [N, G, C, R *
    stride // block] float32: per group the softmax over the kernels
    that lie wholly at or before the query's position, summed over the
    group's heads, then per block the maximum over the kernels that
    overlap it (a block with no complete kernel scores 0)."""
    n, c, g, hg, d = q.shape
    r = kc.shape[2]
    n_blocks = r * stride // block
    per, over = block // stride, kernel // stride

    def one(args):
        q1, kc1, pos1 = args                   # [C,G,Hg,D] [G,R,D] [C]
        s = jnp.einsum("cghd,gjd->gchj", q1.astype(jnp.float32), kc1,
                       preferred_element_type=jnp.float32) * scale
        whole = (jnp.arange(r)[None, :] * stride + kernel
                 <= pos1[:, None] + 1)                        # [C, R]
        s = jnp.where(whole[None, :, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(whole[None, :, None, :], p, 0.0).sum(2)  # [G, C, R]
        p = p[..., :n_blocks * per]
        best = p.reshape(g, c, n_blocks, per).max(-1)
        for i in range(1, over):
            # the kernel that starts i strides before the block
            before = jnp.pad(p, ((0, 0), (0, 0), (i, 0)))[
                ..., :n_blocks * per:per]
            best = jnp.maximum(best, before)
        return best

    if n * c * hg * r <= 2 ** 24:
        return jax.vmap(lambda *a: one(a))(q, kc, qpos)
    return lax.map(one, (q, kc, qpos))


def _window_first(qpos, window: int, block: int):
    return jnp.maximum(qpos - (window - 1), 0) // block


def _best_of_rest(scores, first, *, topk: int, init: int):
    """(rest, ranked, values, blocks): the candidate blocks (past the
    first ``init``, before the window's first block ``first``, which is
    broadcast against the scores' last axis), their scores with every
    other block at -1, and the ``topk`` best — a value of -1 names no
    candidate. ``top_k`` breaks ties by the lower index."""
    nb = scores.shape[-1]
    b = jnp.arange(nb)
    rest = (b >= init) & (b < first)
    ranked = jnp.where(rest, scores, -1.0)
    return (rest, ranked) + tuple(lax.top_k(ranked, min(topk, nb)))


def select_blocks(scores, qpos, *, topk: int, init: int, window: int,
                  block: int, dense_len: int):
    """The per-token block mask of a chunk. scores [N, G, C, NB], qpos
    [N, C] -> bool [N, G, C, NB]: every block for a query before
    ``dense_len`` (the causal mask cuts the rest); else the first
    ``init`` blocks, the blocks from the one that holds position qpos -
    window + 1 on, and the ``topk`` highest-scoring of the rest."""
    b = jnp.arange(scores.shape[-1])
    first = _window_first(qpos, window, block)[:, None, :, None]
    rest, ranked, vals, top = _best_of_rest(scores, first, topk=topk,
                                            init=init)
    # Blocks under one straddling kernel score alike, so ties at the
    # k-th score are the rule: ``top_k`` breaks them by the lower index,
    # and so does this mask (as ``select_block_list`` and the reference).
    kth, kth_at = vals[..., -1:], top[..., -1:]
    chosen = rest & ((ranked > kth) | ((ranked == kth) & (b <= kth_at)))
    dense = (qpos < dense_len)[:, None, :, None]
    return dense | (b < init) | (b >= first) | chosen


def select_block_list(scores, pos, *, topk: int, init: int, window: int,
                      block: int, dense_len: int):
    """The blocks one decode query a slot reads, as a list. scores [S, G,
    NB], pos [S] -> (idx [S, G, NSEL] int32, ok [S, G, NSEL] bool):
    ``init`` first blocks, ``window // block + 1`` window blocks (those
    past the query's own block not ok), the ``topk`` best of the rest
    (those that are no candidate not ok); for a query before
    ``dense_len`` blocks 0 .. pos // block. An entry that is not ok
    names block 0. NSEL = ``block_list_len``."""
    s, g, nb = scores.shape
    n_sel = block_list_len(topk=topk, init=init, window=window, block=block,
                           dense_len=dense_len)
    first = _window_first(pos, window, block)                  # [S]
    last = pos // block
    _, _, vals, top = _best_of_rest(scores, first[:, None, None], topk=topk,
                                    init=init)
    n_win = window // block + 1
    win = first[:, None] + jnp.arange(n_win)[None, :]          # [S, n_win]
    win_ok = (win <= last[:, None]) & (win >= init)
    idx = jnp.concatenate([
        jnp.broadcast_to(jnp.arange(init), (s, g, init)),
        jnp.broadcast_to(win[:, None, :], (s, g, n_win)),
        top], axis=-1)
    ok = jnp.concatenate([
        jnp.ones((s, g, init), bool),
        jnp.broadcast_to(win_ok[:, None, :], (s, g, n_win)),
        vals >= 0.0], axis=-1)
    pad = n_sel - idx.shape[-1]
    idx = jnp.pad(idx, ((0, 0), (0, 0), (0, pad)))
    ok = jnp.pad(ok, ((0, 0), (0, 0), (0, pad)))
    every = jnp.arange(n_sel)
    dense = (pos < dense_len)[:, None, None]
    idx = jnp.where(dense, every, idx)
    ok = jnp.where(dense, every <= last[:, None, None], ok)
    return jnp.where(ok, idx, 0).astype(jnp.int32), ok


def block_list_len(*, topk: int, init: int, window: int, block: int,
                   dense_len: int) -> int:
    return max(init + window // block + 1 + topk, dense_len // block)


# ---------------------------------------------------------------------------
# Block-sparse attention: decode over the selected blocks
# ---------------------------------------------------------------------------

def _sparse_decode_jax(q, kc, vc, idx, ok, pos, scale, block):
    s, h_kv, t, d = kc.shape
    g = q.shape[1] // h_kv
    nb = t // block
    seen = jnp.zeros((s, h_kv, nb), jnp.int32).at[
        jnp.arange(s)[:, None, None], jnp.arange(h_kv)[None, :, None], idx
    ].max(ok.astype(jnp.int32)) > 0
    mask = (jnp.repeat(seen, block, axis=-1)
            & (jnp.arange(t)[None, None, :] <= pos[:, None, None]))
    qg = q.reshape(s, h_kv, g, d)
    sc = jnp.einsum("sghd,sgtd->sght", qg, kc,
                    preferred_element_type=jnp.float32) * scale
    sc = jnp.where(mask[:, :, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("sght,sgtd->sghd", p.astype(vc.dtype), vc,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype).reshape(s, h_kv * g, d)


def _sparse_decode_kernel(idx_ref, ok_ref, pos_ref, q_ref, *refs, per_step,
                          block, scale, n_sel, groups):
    """One program = one (slot, KV group, ``per_step`` selected blocks):
    the group's query heads against the blocks' keys, running softmax
    across the steps."""
    k_refs, v_refs = refs[:per_step], refs[per_step:2 * per_step]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * per_step:]
    slot, grp, step = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    base = (slot * groups + grp) * n_sel + step * per_step

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = ok_ref[base]
    for u in range(1, per_step):
        live = live + ok_ref[base + u]

    @pl.when(live > 0)
    def _blocks():
        k = jnp.concatenate([r[...] for r in k_refs], axis=0)
        v = jnp.concatenate([r[...] for r in v_refs], axis=0)
        s = lax.dot_general(q_ref[...], k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        col = lax.broadcasted_iota(jnp.int32, (1, per_step * block), 1)
        key = jnp.full((1, per_step * block), -1, jnp.int32)
        for u in range(per_step):
            at = jnp.where(ok_ref[base + u] > 0,
                           idx_ref[base + u] * block - u * block, -2 ** 30)
            key = jnp.where(col // block == u, col + at, key)
        visible = (key >= 0) & (key <= pos_ref[slot])
        visible = jnp.broadcast_to(visible, s.shape)
        s = jnp.where(visible, s, NEG_INF)
        _online_softmax_step(s, v, m_ref, l_ref, acc_ref, visible=visible)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _sparse_decode_pallas(q, kc, vc, idx, ok, pos, scale, block, interpret):
    from jax.experimental.pallas import tpu as pltpu

    s, h_kv, t, d = kc.shape
    g = q.shape[1] // h_kv
    n_sel = idx.shape[-1]
    per_step = min(SPARSE_DECODE_BLOCKS, n_sel)
    while n_sel % per_step:
        per_step -= 1
    q = q.reshape(s, h_kv, g, d)

    def q_map(i, j, n, idx, ok, pos):
        return (i, j, 0, 0)

    def kv_spec(u):
        def kv_map(i, j, n, idx, ok, pos):
            return (i, j, idx[(i * h_kv + j) * n_sel + n * per_step + u], 0)
        return pl.BlockSpec((None, None, block, d), kv_map)

    specs = [kv_spec(u) for u in range(per_step)]
    out = pl.pallas_call(
        functools.partial(_sparse_decode_kernel, per_step=per_step,
                          block=block, scale=scale, n_sel=n_sel,
                          groups=h_kv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s, h_kv, n_sel // per_step),
            in_specs=[pl.BlockSpec((None, None, g, d), q_map)] + specs * 2,
            out_specs=pl.BlockSpec((None, None, g, d), q_map),
            scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                            pltpu.VMEM((g, 1), jnp.float32),
                            pltpu.VMEM((g, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((s, h_kv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(idx.reshape(-1).astype(jnp.int32), ok.reshape(-1).astype(jnp.int32),
      pos.astype(jnp.int32), q, *([kc] * per_step), *([vc] * per_step))
    return out.reshape(s, h_kv * g, d)


def sparse_decode_attention(q, kc, vc, idx, ok, pos, *, scale: float,
                            block: int, mode: str = "auto"):
    """One query per slot over the blocks selected for each of its KV
    groups. q [S, H, D]; kc, vc [S, Hkv, Tmax, D]; ``idx``, ``ok`` [S,
    Hkv, NSEL] (``select_block_list``); slot s sees the keys of its ok
    blocks at positions <= pos[s]. -> [S, H, D]. The kernel reads only
    those blocks (an entry that is not ok names block 0 and a run of
    them is fetched once); result shape [S, Hkv, H / Hkv, D]."""
    mode = _mode(mode)
    if mode == "jax":
        return _sparse_decode_jax(q, kc, vc, idx, ok, pos, scale, block)
    return _sparse_decode_pallas(q, kc, vc, idx, ok, pos, scale, block,
                                 mode == "interpret")


# ---------------------------------------------------------------------------
# Block-sparse attention: a prefill chunk under its block mask
# ---------------------------------------------------------------------------

def _sparse_prefill_jax(q, kc, vc, sel, slots, ends, scale, block):
    p, c, h, d = q.shape
    h_kv, t = kc.shape[1], kc.shape[2]
    g = h // h_kv
    k, v = kc[slots], vc[slots]                                # [P,Hkv,T,D]
    qpos = ends[:, None] - c + jnp.arange(c)[None, :]
    mask = (jnp.repeat(sel, block, axis=-1)
            & (jnp.arange(t)[None, None, None, :]
               <= qpos[:, None, :, None]))                     # [P,G,C,T]
    qg = q.reshape(p, c, h_kv, g, d)
    s = jnp.einsum("pcghd,pgtd->pghct", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, :, None], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("pghct,pgtd->pcghd", pr.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype).reshape(p, c, h, d)


def _sparse_prefill_kernel(slots_ref, ends_ref, q_ref, sel_ref, k_ref, v_ref,
                           o_ref, m_ref, l_ref, acc_ref, *, chunk, heads,
                           block, scale, per_mask):
    """One program = one (row, KV group, block of ``heads`` query heads,
    key tile). q_ref [heads * C, D], rows (head, position); sel_ref [C,
    M]: the chunk's block mask over the M blocks that hold this key
    tile; k_ref, v_ref [tile, D]. The mask of a tile's blocks is spread
    over its keys by one small product with a 0/1 matrix."""
    row, kt = pl.program_id(0), pl.program_id(3)
    tile = k_ref.shape[0]
    end = ends_ref[row]

    @pl.when(kt == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kt * tile < end)
    def _tile():
        m_blocks = sel_ref.shape[1]
        at = (kt % per_mask) * (tile // block)
        spread = (lax.broadcasted_iota(jnp.int32, (m_blocks, tile), 0)
                  == lax.broadcasted_iota(jnp.int32, (m_blocks, tile), 1)
                  // block + at).astype(sel_ref.dtype)
        picked = jnp.dot(sel_ref[...], spread,
                         preferred_element_type=jnp.float32) > 0.5
        key = lax.broadcasted_iota(jnp.int32, (chunk, tile), 1) + kt * tile
        qpos = lax.broadcasted_iota(jnp.int32, (chunk, tile), 0) + end - chunk
        visible = picked & (key <= qpos)
        k, v = k_ref[...], v_ref[...]
        for i in range(heads):
            rows = slice(i * chunk, (i + 1) * chunk)
            s = lax.dot_general(q_ref[rows, :], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            s = jnp.where(visible, s, NEG_INF)
            _online_softmax_step(s, v, m_ref, l_ref, acc_ref, rows,
                                 visible=visible)

    @pl.when(kt == pl.num_programs(3) - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _sparse_prefill_pallas(q, kc, vc, sel, slots, ends, scale, block,
                           interpret):
    from jax.experimental.pallas import tpu as pltpu

    p, c, h, d = q.shape
    h_kv, t = kc.shape[1], kc.shape[2]
    g = h // h_kv
    nb = t // block
    tile = min(SPARSE_PREFILL_KEYS, t)
    while t % tile or tile % block:
        tile -= block
    heads = min(SPARSE_PREFILL_HEADS, g)
    while g % heads:
        heads -= 1
    m_blocks = min(SPARSE_PREFILL_MASK_BLOCKS, nb)
    while nb % m_blocks or m_blocks % (tile // block):
        m_blocks -= 1
    per_mask = m_blocks * block // tile
    # [P, C, H, D] -> [P, Hkv, G / heads, heads * C, D]: rows (head, position)
    qg = q.reshape(p, c, h_kv, g // heads, heads, d).transpose(
        0, 2, 3, 4, 1, 5).reshape(p, h_kv, g // heads, heads * c, d)

    def q_map(r, j, i, n, slots, ends):
        return (r, j, i, 0, 0)

    def last_tile(n, ends, r):
        # past the row's last visible tile: stay there, no new copy
        return jnp.minimum(n, (ends[r] - 1) // tile)

    def kv_map(r, j, i, n, slots, ends):
        return (slots[r], j, last_tile(n, ends, r), 0)

    def sel_map(r, j, i, n, slots, ends):
        return (r, j, 0, last_tile(n, ends, r) // per_mask)

    kv = pl.BlockSpec((None, None, tile, d), kv_map)
    rows = heads * c
    out = pl.pallas_call(
        functools.partial(_sparse_prefill_kernel, chunk=c, heads=heads,
                          block=block, scale=scale, per_mask=per_mask),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(p, h_kv, g // heads, t // tile),
            in_specs=[pl.BlockSpec((None, None, None, rows, d), q_map),
                      pl.BlockSpec((None, None, c, m_blocks), sel_map),
                      kv, kv],
            out_specs=pl.BlockSpec((None, None, None, rows, d), q_map),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(slots.astype(jnp.int32), ends.astype(jnp.int32), qg,
      sel.astype(q.dtype), kc, vc)
    return out.reshape(p, h_kv, g // heads, heads, c, d).transpose(
        0, 4, 1, 2, 3, 5).reshape(p, c, h, d)


def sparse_prefill_attention(q, kc, vc, sel, slots, ends, *, scale: float,
                             block: int, mode: str = "auto"):
    """One prefill chunk per row against its slot's rows of a sparse
    layer's cache, the chunk already written. q [P, C, H, D]; kc, vc [S,
    Hkv, Tmax, D]; ``sel`` bool [P, Hkv, C, Tmax / block]
    (``select_blocks``); row r reads slot ``slots[r]`` and its query i,
    at position ends[r] - C + i, sees the keys of its selected blocks up
    to its own position. -> [P, C, H, D]. The kernel reads the slot's
    K/V up to the chunk's end, once per ``heads`` query heads, and keeps
    the scores on the chip; result shape [P, Hkv, G / heads, heads * C,
    D]."""
    mode = _mode(mode)
    if mode == "jax":
        return _sparse_prefill_jax(q, kc, vc, sel, slots, ends, scale, block)
    return _sparse_prefill_pallas(q, kc, vc, sel, slots, ends, scale, block,
                                  mode == "interpret")
