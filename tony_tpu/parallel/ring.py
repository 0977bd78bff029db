"""Ring attention: exact long-context attention with the sequence sharded
over the ``sp`` mesh axis. Each step every device computes blockwise
attention of its local queries against the K/V block it currently holds,
then passes that block to its ring neighbour with ``ppermute`` — compute and
ICI transfer overlap, HBM never holds more than one remote block.

This is a capability the reference never had (SURVEY.md §5.7: long-context
lands in the model/ops layer the 2018 orchestrator lacked). Communication is
XLA collectives over ICI — no NCCL.

Online-softmax accumulation (flash-attention style): carry running max *m*,
normalizer *l*, and unnormalized output *o*; each block update is
numerically exact, so the result matches full attention to fp tolerance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _chunk_attention(q, k, v, *, q_start, k_start, causal, scale, block_k):
    """Flash-style blockwise attention of the local queries against one kv
    shard, returning unnormalized softmax statistics for ring merging.

    Memory is O(Tq · block_k) — the full [Tq, Tk] score matrix is never
    materialized, so each ring step costs the same peak memory as the local
    flash kernel's inner loop (same math as
    ops/attention._blockwise_attention_jax, with traced
    global position offsets instead of the decode convention).

    q: [B, Tq, H, D]  k,v: [B, Tk, H, D]; ``q_start``/``k_start`` are the
    (traced) global positions of the first q/k row. Returns (o, m, l):
    unnormalized out [B, Tq, H, D] f32, rowmax [B, H, Tq], rowsum
    [B, H, Tq]; fully-masked rows come back with m = NEG_INF, l = 0.
    """
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    block_k = min(block_k, t_k)
    n_blocks = -(-t_k // block_k)
    pad = n_blocks * block_k - t_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qf = q.astype(jnp.float32) * scale
    q_pos = q_start + jnp.arange(t_q)

    def step(carry, ki):
        o, m, l = carry
        k_blk = lax.dynamic_slice_in_dim(k, ki * block_k, block_k, 1)
        v_blk = lax.dynamic_slice_in_dim(v, ki * block_k, block_k, 1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32))
        local_k = ki * block_k + jnp.arange(block_k)
        if pad:
            s = jnp.where(local_k[None, None, None, :] < t_k, s, NEG_INF)
        if causal:
            k_pos = k_start + local_k
            s = jnp.where(
                q_pos[None, None, :, None] >= k_pos[None, None, None, :],
                s, NEG_INF,
            )
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_safe))
        l_new = l * alpha + p.sum(-1)
        o_new = o * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32)
        )
        return (o_new, m_new, l_new), None

    o0 = jnp.zeros((b, t_q, h, d), jnp.float32)
    m0 = jnp.full((b, h, t_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t_q), jnp.float32)
    # Remat per kv block: without it, grad-of-scan stacks every block's
    # [B, H, Tq, block_k] p/s residuals — the full score matrix again. With
    # it, backward recomputes each block and only the (o, m, l) carries are
    # stored: O(Tq · D · Tk/block_k), a block_k/D-fold saving.
    (o, m, l), _ = lax.scan(
        jax.checkpoint(step), (o0, m0, l0), jnp.arange(n_blocks)
    )
    return o, m, l


def _merge(o1, m1, l1, o2, m2, l2):
    """Merge two partial softmax accumulations (exact)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    o = (
        o1 * a1.transpose(0, 2, 1)[..., None]
        + o2 * a2.transpose(0, 2, 1)[..., None]
    )
    return o, m, l


def _resolve_kernel(kernel: str, mesh: Mesh | None = None) -> str:
    """``"auto"`` -> the Pallas kernel where the mesh's devices (else the
    default backend) are TPUs, the blockwise-JAX path elsewhere."""
    if kernel != "auto":
        return kernel
    from tony_tpu.ops.attention import _on_tpu

    return "pallas" if _on_tpu(mesh) else "jax"


def ring_attention_local(
    q, k, v, *, axis_name: str, causal: bool, scale: float,
    block_k: int = 512, kernel: str = "auto",
):
    """Per-shard body (runs inside shard_map). q,k,v: [B, Tlocal, H, D].

    ``kernel`` selects the per-step chunk attention:

    * ``"auto"`` — the Pallas flash kernel on TPU (via
      ``ops.flash_attention_lse``), the independent blockwise-JAX
      implementation elsewhere;
    * ``"jax"`` — pin the blockwise-JAX path (the cross-check);
    * ``"pallas"`` / ``"interpret"`` — pin the kernel (interpret = Pallas
      interpreter mode, for CPU tests of the kernel path).

    Either way the forward never materializes a [Tlocal, Tlocal] score
    matrix and the backward is remat-bounded: per-ring-step recompute keeps
    stored residuals to the merge carries plus the rotating K/V blocks."""
    kernel = _resolve_kernel(kernel)
    if kernel in ("pallas", "interpret"):
        return _ring_kernel_local(
            q, k, v, axis_name=axis_name, causal=causal, scale=scale,
            block_k=block_k, mode=kernel,
        )
    axis_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, t_q, h, d = q.shape
    t_k = k.shape[1]

    # Ring: at step s, this device holds the kv block originally owned by
    # (my_idx - s) mod axis_size.
    fwd_perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(carry, s):
        o, m, l, k_blk, v_blk = carry
        kv_owner = (my_idx - s) % axis_size

        def attend(o, m, l):
            o_blk, m_blk, l_blk = _chunk_attention(
                q, k_blk, v_blk,
                q_start=my_idx * t_q, k_start=kv_owner * t_k,
                causal=causal, scale=scale, block_k=block_k,
            )
            return _merge(o, m, l, o_blk, m_blk, l_blk)

        if causal:
            # A ring step whose kv shard sits entirely in this shard's
            # future is fully masked — skip its matmuls (roughly half the
            # ring steps on average; the ppermute still rotates the block
            # so the ring stays in lockstep). Compared in global positions
            # so cross-length attention (t_q != t_k) stays exact: skip iff
            # the block's first key comes after our last query.
            fully_masked = kv_owner * t_k >= (my_idx + 1) * t_q
            o, m, l = lax.cond(
                fully_masked,
                lambda o, m, l: (o, m, l),
                attend,
                o, m, l,
            )
        else:
            o, m, l = attend(o, m, l)
        # Rotate K/V around the ring (skipped work on the last step is
        # dead-code-eliminated only when axis_size is static — it is).
        k_nxt = lax.ppermute(k_blk, axis_name, fwd_perm)
        v_nxt = lax.ppermute(v_blk, axis_name, fwd_perm)
        return (o, m, l, k_nxt, v_nxt), None

    o0 = jnp.zeros((b, t_q, h, d), dtype=jnp.float32)
    m0 = jnp.full((b, h, t_q), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, t_q), dtype=jnp.float32)
    # Remat per ring step: backward replays one step's inner loop at a
    # time instead of stacking residuals for all axis_size steps (an
    # sp-fold saving; the stored carries are the rotating K/V blocks).
    (o, m, l, _, _), _ = lax.scan(
        jax.checkpoint(step), (o0, m0, l0, k, v), jnp.arange(axis_size)
    )
    l = jnp.maximum(l, 1e-30)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _merge_lse(o1, lse1, o2, lse2):
    """Merge two normalized partials (o [B,T,H,D] f32, lse [B,H,T]) —
    the (out, lse) form of ``_merge``, matching the kernel's outputs."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    denom = w1 + w2
    wt1 = (w1 / denom).transpose(0, 2, 1)[..., None]
    wt2 = (w2 / denom).transpose(0, 2, 1)[..., None]
    return o1 * wt1 + o2 * wt2, m + jnp.log(denom)


def _ring_kernel_local(
    q, k, v, *, axis_name: str, causal: bool, scale: float,
    block_k: int, mode: str,
):
    """Ring body with the Pallas flash kernel doing each step's chunk
    attention (ops.flash_attention_lse). The ring structure makes the
    kernel calls mask-cheap: step 0 is plain causal self-attention (the
    kernel's fast diagonal path), and every later live step attends a
    block that is entirely in the past — ``causal=False``, no mask work at
    all; fully-future blocks are skipped by the lax.cond. Merging uses the
    kernel's (out, lse) outputs; gradients flow through the merge weights
    into the kernel's lse (see _flash_attention_pallas_bwd's g_lse)."""
    from tony_tpu.ops.attention import flash_attention_lse

    axis_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    t_q = q.shape[1]
    t_k = k.shape[1]
    fwd_perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def chunk(k_blk, v_blk, *, causal_step):
        o, lse = flash_attention_lse(
            q, k_blk, v_blk, causal=causal_step, scale=scale,
            block_k=block_k, mode=mode,
        )
        return o.astype(jnp.float32), lse

    # Step 0: this shard's own K/V — the only step that needs a causal mask.
    out, lse = chunk(k, v, causal_step=causal)
    if axis_size == 1:
        return out.astype(q.dtype)
    k_blk = lax.ppermute(k, axis_name, fwd_perm)
    v_blk = lax.ppermute(v, axis_name, fwd_perm)

    def step(carry, s):
        out, lse, k_blk, v_blk = carry
        kv_owner = (my_idx - s) % axis_size

        def attend(out, lse):
            o2, lse2 = chunk(k_blk, v_blk, causal_step=False)
            return _merge_lse(out, lse, o2, lse2)

        if causal:
            # Global-position comparison (exact for t_q != t_k): skip iff
            # the block's first key comes after our last query. Blocks that
            # straddle the diagonal cannot occur for s >= 1 — each shard
            # owns a disjoint position range.
            fully_masked = kv_owner * t_k >= (my_idx + 1) * t_q
            out, lse = lax.cond(
                fully_masked, lambda o, l: (o, l), attend, out, lse,
            )
        else:
            out, lse = attend(out, lse)
        k_nxt = lax.ppermute(k_blk, axis_name, fwd_perm)
        v_nxt = lax.ppermute(v_blk, axis_name, fwd_perm)
        return (out, lse, k_nxt, v_nxt), None

    # Remat per ring step (same policy as the JAX path): backward replays
    # one step's kernels at a time; stored residuals are the merge carries
    # plus the rotating K/V blocks.
    (out, lse, _, _), _ = lax.scan(
        jax.checkpoint(step), (out, lse, k_blk, v_blk),
        jnp.arange(1, axis_size),
    )
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    scale: float | None = None,
    batch_axes=("dp", "ep"),
    head_axis: str = "tp",
    block_k: int = 512,
    kernel: str = "auto",
) -> jax.Array:
    """Exact attention over a sequence sharded on ``axis_name``.

    q, k, v: [batch, seq, heads, head_dim] (global shapes). The sequence axis
    is split over ``sp``, heads over ``tp``, batch over ``dp``/``ep``;
    within each shard the kv scan runs ``block_k`` keys at a time (flash
    accumulation), so memory stays O(T/sp · block_k). ``kernel`` selects
    the per-step chunk attention (see ``ring_attention_local``).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    kernel = _resolve_kernel(kernel, mesh)
    spec = P(batch_axes, axis_name, head_axis, None)
    body = functools.partial(
        ring_attention_local, axis_name=axis_name, causal=causal,
        scale=scale, block_k=block_k, kernel=kernel,
    )
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    # jit is required: the remat'd scan bodies inside shard_map cannot be
    # evaluated eagerly (and callers embed this in jitted train steps
    # anyway — the bare-call path only exists in tests).
    return jax.jit(sharded)(q, k, v)  # tony: noqa[TONY-X001] — jit required for the scan bodies; callers embed in jitted steps, bare path is test-only
