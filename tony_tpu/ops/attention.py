"""Flash attention: online-softmax blockwise attention that never
materializes the [T, T] score matrix.

Forward on TPU is a Pallas kernel (grid over (batch x heads, q-blocks); K/V
blocks stream through VMEM; MXU does the two matmuls per block in fp32
accumulation). Backward on TPU is a two-pass Pallas pair
(``_flash_core_bwd``): a dq kernel over q-blocks and a dk/dv kernel over
kv-blocks, each recomputing the masked probabilities from the saved
(out, lse) statistics. Off TPU, a blockwise ``lax.scan`` computes the same
math in both directions, so results match to fp tolerance and memory stays
O(T · block) everywhere.

Public layout is [batch, seq, heads, head_dim], the same as
``tony_tpu.parallel.ring_attention``. Ring attention carries its own
per-block accumulation (it must merge partial (o, m, l) statistics across
ring steps, which this op's public API does not expose) — its bias-based
masking makes the two paths intentionally independent implementations,
cross-checked against each other in tests.

Causal masking follows the decode convention: when t_q != t_k the query
block sits at the END of the key range (query row i has global position
t_k - t_q + i), so KV-cache decode attends to the full prefix.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.sharding import Mesh

from tony_tpu.parallel.sharding import auto_axes, local_spec, per_shard

NEG_INF = -1e30


def _on_tpu(mesh: Mesh | None = None) -> bool:
    """Whether the Pallas kernels are the path to take: the platform of
    the mesh's own devices when a concrete mesh is given, else of the
    default backend. A backend that fails to initialise raises here — it
    never reads as "not a TPU" and selects the blockwise path."""
    if isinstance(mesh, Mesh):
        return mesh.devices.flat[0].platform == "tpu"
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------

_LANES = 128  # TPU vreg lane count; m/l scratch rows broadcast across lanes


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, scale, causal, t_k, t_q,
):
    """One program = one (batch*head, q-block, kv-block). The kv axis is the
    innermost (sequential) grid dimension, so only one [block_k, d] K/V tile
    is resident in VMEM at a time — context length is bounded by HBM, not
    VMEM. Running (o, m, l) statistics persist across kv steps in scratch;
    the output block is written once on the final kv step.

    Refs: q_ref [1, block_q, d], k_ref/v_ref [1, block_k, d],
    o_ref [1, block_q, d]; scratch acc [block_q, d] f32, m/l
    [block_q, LANES] f32 (value broadcast across lanes — vreg-friendly).
    ``t_k``/``t_q`` are real (pre-padding) lengths.
    """
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Decode convention: the query block sits at the END of the key range,
    # so global query position = t_k - t_q + row (self-attention reduces to
    # position == row).
    q_off = t_k - t_q
    k_start = ki * block_k
    # Causal skip: this kv block is fully masked when its first key comes
    # after the q block's last row — skip the matmuls (half the FLOPs for
    # self-attention; the tile copy still streams, hidden by the pipeline).
    live = k_start <= q_off + (qi + 1) * block_q - 1 if causal else True

    def _scores():
        # Operands stay in the input dtype (bf16): the MXU runs bf16
        # matmuls at full rate and fp32 at a fraction of it; accumulation
        # is fp32 via preferred_element_type (the FA2 recipe). The scale
        # folds in AFTER the dot, in fp32, so no precision is spent on a
        # bf16 pre-scale.
        return jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k]

    def _accumulate(s, *, may_be_masked: bool):
        """Online-softmax update. The unmasked variant drops every
        NEG_INF guard: with only real scores m_new is always finite, and
        alpha = exp(m - m_new) underflows cleanly to 0 on the first live
        block (m = NEG_INF)."""
        # Lanes of m/l hold identical values; a lane-max reads them back.
        m = jnp.max(m_ref[...], axis=1)
        l = jnp.max(l_ref[...], axis=1)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        if may_be_masked:
            # Fully-masked rows keep m_new at NEG_INF; shift to 0 for exp.
            m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
            p = jnp.exp(s - m_safe[:, None])
            p = jnp.where(s <= NEG_INF / 2, 0.0, p)
            alpha = jnp.exp(jnp.where(m <= NEG_INF / 2, NEG_INF, m) - m_safe)
            alpha = jnp.where(m <= NEG_INF / 2, 0.0, alpha)
        else:
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        # p downcast to the V dtype for the MXU (bf16 full rate, fp32
        # accumulation) — p ∈ [0, 1] so the cast costs ~3 decimal digits
        # on already-exponentiated values, the standard FA2 trade.
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    # Mask work only happens where the block straddles the causal diagonal
    # or holds padded tail keys; interior blocks take a branch with no iota
    # and no where — pure matmul + online softmax.
    tail_pad = bool(t_k % block_k)
    if causal or tail_pad:
        needs_mask = False
        if tail_pad:
            needs_mask = needs_mask | (ki == num_k - 1)
        if causal:
            needs_mask = needs_mask | (
                k_start + block_k - 1 > q_off + qi * block_q
            )

        @pl.when(live & jnp.logical_not(needs_mask))
        def _compute_fast():
            _accumulate(_scores(), may_be_masked=False)

        @pl.when(live & needs_mask)
        def _compute_masked():
            s = _scores()
            k_pos = k_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            if tail_pad:
                # Final block is padding past t_k; mask the tail keys.
                s = jnp.where(k_pos < t_k, s, NEG_INF)
            if causal:
                q_pos = (
                    q_off + qi * block_q
                    + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                )
                s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            _accumulate(s, may_be_masked=True)
    else:

        @pl.when(live)
        def _compute():
            _accumulate(_scores(), may_be_masked=False)

    @pl.when(ki == num_k - 1)
    def _finalize():
        m = jnp.max(m_ref[...], axis=1)
        l = jnp.maximum(jnp.max(l_ref[...], axis=1), 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        # Log-sum-exp per row, saved for the backward kernels. Fully-masked
        # rows get a finite value (log l_min) so exp(NEG_INF - lse)
        # underflows to 0 instead of NaN-ing.
        lse = jnp.where(m <= NEG_INF / 2, 0.0, m) + jnp.log(l)
        lse_ref[...] = lse[None, None, :]


def _flash_attention_pallas(
    q, k, v, *, causal, scale, block_q, block_k, interpret=False,
    return_lse=False,
):
    """q,k,v: [BH, T, D] (batch and heads pre-flattened). With
    ``return_lse`` also returns the per-row log-sum-exp [BH, T] the
    backward kernels consume."""
    from jax.experimental.pallas import tpu as pltpu

    bh, t_q, d = q.shape
    t_k = k.shape[1]
    block_q = min(block_q, t_q)
    block_k = min(block_k, t_k)
    # Pad BOTH sequence axes to block multiples: a final partial tile would
    # otherwise alias real rows when the BlockSpec clamps its window — on
    # the q side that rewrites earlier rows with wrong positions (silently
    # non-causal output), on the k side it double-counts keys. Padded q rows
    # compute garbage that is sliced off below; the kernel's position math
    # uses the real t_q/t_k.
    pad_k = (-t_k) % block_k
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    pad_q = (-t_q) % block_q
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    grid = (bh, (t_q + pad_q) // block_q, (t_k + pad_k) // block_k)
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, t_k=t_k, t_q=t_q,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            # [bh, 1, T] layout: a (1, 1, block_q) block satisfies the TPU
            # (8, 128) tiling rule (second-to-last dim equals the array's).
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_q + pad_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, t_q + pad_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    lse = lse[:, 0]
    if pad_q:
        out, lse = out[:, :t_q], lse[:, :t_q]
    return (out, lse) if return_lse else out


# ---------------------------------------------------------------------------
# Pallas backward kernels (Dao-style two-pass flash backward)
# ---------------------------------------------------------------------------
# p = exp(s - lse) is reconstructed from the saved per-row log-sum-exp, so
# the backward never materializes [T, T]; dq accumulates over kv blocks and
# (dk, dv) over q blocks, each as its own kernel with the reduction axis as
# the innermost sequential grid dimension. Masking mirrors the forward's
# two-branch trick: only blocks that straddle the causal diagonal or hold
# padded tail rows/keys pay the iota + where VPU work — interior blocks
# run pure matmul + exp. This is NOT free hygiene: an earlier device-trace
# sweep had the always-masked variant at roughly 2.6x the forward (dq+dkv,
# 8k, BH=32) — the per-block wheres cost as much as a matmul, and
# branching recovered most of it. Not re-measured on the current chip.


def _bwd_masked_p(s, lse_row, *, qi, ki, block_q, block_k, q_off, t_q, t_k,
                  causal):
    k_pos = ki * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    q_row = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    valid = (k_pos < t_k) & (q_row < t_q)
    if causal:
        valid &= (q_off + q_row) >= k_pos
    p = jnp.exp(s - lse_row[:, None])
    return jnp.where(valid, p, 0.0)


def _bwd_needs_mask(*, qi, ki, block_q, block_k, q_off, t_q, t_k, causal):
    """Traced predicate: does this (q-block, kv-block) need the iota +
    where masking pass? Interior blocks — fully below the causal diagonal
    and free of padded tail rows/keys — skip it (see the module note:
    measured at ~matmul cost per block)."""
    needs = False
    if causal:
        # Straddles the diagonal: some (row, key) pairs are masked.
        needs = ki * block_k + block_k - 1 > q_off + qi * block_q
    if t_k % block_k:
        needs = needs | (ki * block_k + block_k > t_k)
    if t_q % block_q:
        needs = needs | (qi * block_q + block_q > t_q)
    return needs


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
    *, scale, causal, t_k, t_q,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    q_off = t_k - t_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = k_start <= q_off + (qi + 1) * block_q - 1 if causal else True

    def _accumulate(p):
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta_ref[0, 0][:, None])).astype(k_ref.dtype)
        acc_ref[...] += jax.lax.dot_general(
            ds, k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    def _scores():
        # bf16 MXU operands, fp32 accumulation (FA2): upcasting to fp32
        # before the dots runs the MXU at a fraction of its bf16 rate.
        return jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    needs_mask = _bwd_needs_mask(
        qi=qi, ki=ki, block_q=block_q, block_k=block_k, q_off=q_off,
        t_q=t_q, t_k=t_k, causal=causal,
    )

    @pl.when(live & jnp.logical_not(needs_mask))
    def _compute_fast():
        _accumulate(jnp.exp(_scores() - lse_ref[0, 0][:, None]))

    @pl.when(live & needs_mask)
    def _compute_masked():
        _accumulate(_bwd_masked_p(
            _scores(), lse_ref[0, 0], qi=qi, ki=ki, block_q=block_q,
            block_k=block_k, q_off=q_off, t_q=t_q, t_k=t_k, causal=causal,
        ))

    @pl.when(ki == num_k - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, scale, causal, t_k, t_q,
):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    num_q = pl.num_programs(2)
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    q_off = t_k - t_q
    k_start = ki * block_k

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # Causal skip mirrored from the dq kernel: a q block entirely above the
    # diagonal contributes nothing to this kv block.
    live = q_off + (qi + 1) * block_q - 1 >= k_start if causal else True

    def _accumulate(p):
        # bf16 MXU operands, fp32 accumulation (FA2) — see dq kernel.
        p16 = p.astype(do_ref.dtype)
        dv_acc[...] += jax.lax.dot_general(
            p16, do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta_ref[0, 0][:, None])).astype(q_ref.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    def _scores():
        return jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    needs_mask = _bwd_needs_mask(
        qi=qi, ki=ki, block_q=block_q, block_k=block_k, q_off=q_off,
        t_q=t_q, t_k=t_k, causal=causal,
    )

    @pl.when(live & jnp.logical_not(needs_mask))
    def _compute_fast():
        _accumulate(jnp.exp(_scores() - lse_ref[0, 0][:, None]))

    @pl.when(live & needs_mask)
    def _compute_masked():
        _accumulate(_bwd_masked_p(
            _scores(), lse_ref[0, 0], qi=qi, ki=ki, block_q=block_q,
            block_k=block_k, q_off=q_off, t_q=t_q, t_k=t_k, causal=causal,
        ))

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_attention_pallas_bwd(
    q, k, v, out, lse, do, *, causal, scale, block_q, block_k,
    interpret=False, g_lse=None,
):
    """Backward for the Pallas forward. All inputs [BH, T, D] (lse/delta
    [BH, T]); returns (dq, dk, dv).

    ``g_lse`` is the optional cotangent of the forward's lse output (ring
    attention differentiates through its merge weights): d lse/d s = p, so
    it folds into the existing kernels as ds = p·(dp - (delta - g_lse)) —
    delta is simply shifted, no kernel change."""
    from jax.experimental.pallas import tpu as pltpu

    bh, t_q, d = q.shape
    t_k = k.shape[1]
    block_q = min(block_q, t_q)
    block_k = min(block_k, t_k)
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    pad_q = (-t_q) % block_q
    pad_k = (-t_k) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
        do = jnp.pad(do, ((0, 0), (0, pad_q), (0, 0)))
        lse = jnp.pad(lse, ((0, 0), (0, pad_q)))
        delta = jnp.pad(delta, ((0, 0), (0, pad_q)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    n_q = (t_q + pad_q) // block_q
    n_k = (t_k + pad_k) // block_k

    lse3 = lse[:, None, :]
    delta3 = delta[:, None, :]
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))
    rowspec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, scale=scale, causal=causal,
            t_k=t_k, t_q=t_q,
        ),
        grid=(bh, n_q, n_k),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, t_q + pad_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, do, lse3, delta3)

    # dkv grid: kv blocks parallel, q blocks sequential (innermost).
    qspec2 = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    kspec2 = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    rowspec2 = pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i))
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, scale=scale, causal=causal,
            t_k=t_k, t_q=t_q,
        ),
        grid=(bh, n_k, n_q),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2],
        out_specs=[kspec2, kspec2],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_k + pad_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t_k + pad_k, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, do, lse3, delta3)
    if pad_q:
        dq = dq[:, :t_q]
    if pad_k:
        dk, dv = dk[:, :t_k], dv[:, :t_k]
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Blockwise JAX path (fallback forward + recompute backward)
# ---------------------------------------------------------------------------

def _blockwise_attention_jax(q, k, v, *, causal, scale, block_k,
                             return_lse=False):
    """Same online-softmax math as the kernel, as a lax.scan over kv blocks.
    q,k,v: [BH, T, D]. With ``return_lse`` also returns the per-row
    log-sum-exp [BH, T] (same masked-row convention as the kernel)."""
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    block_k = min(block_k, t_k)
    n_blocks = -(-t_k // block_k)
    pad = n_blocks * block_k - t_k
    if pad:
        # dynamic_slice clamps out-of-range starts (double-counting rows),
        # so pad to a block multiple and mask the tail keys instead.
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    qf = q.astype(jnp.float32) * scale
    # Decode convention (see kernel): query block sits at the end of keys.
    q_pos = (t_k - t_q) + jnp.arange(t_q)

    def step(carry, ki):
        o, m, l = carry
        k_blk = lax.dynamic_slice_in_dim(k, ki * block_k, block_k, axis=1)
        v_blk = lax.dynamic_slice_in_dim(v, ki * block_k, block_k, axis=1)
        s = jnp.einsum("btd,bsd->bts", qf, k_blk.astype(jnp.float32))
        k_pos = ki * block_k + jnp.arange(block_k)
        if pad:
            s = jnp.where(k_pos[None, None, :] < t_k, s, NEG_INF)
        if causal:
            s = jnp.where(q_pos[None, :, None] >= k_pos[None, None, :], s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_safe))
        l_new = l * alpha + p.sum(-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bts,bsd->btd", p, v_blk.astype(jnp.float32)
        )
        return (o_new, m_new, l_new), None

    o0 = jnp.zeros((bh, t_q, d), jnp.float32)
    m0 = jnp.full((bh, t_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bh, t_q), jnp.float32)
    (o, m, l), _ = lax.scan(step, (o0, m0, l0), jnp.arange(n_blocks))
    l = jnp.maximum(l, 1e-30)
    out = (o / l[..., None]).astype(q.dtype)
    if not return_lse:
        return out
    lse = jnp.where(m <= NEG_INF / 2, 0.0, m) + jnp.log(l)
    return out, lse


# ---------------------------------------------------------------------------
# Public op with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, causal, scale, block_q, block_k, kernel):
    if kernel:
        return _flash_attention_pallas(
            q, k, v, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k,
        )
    return _blockwise_attention_jax(
        q, k, v, causal=causal, scale=scale, block_k=block_k
    )


# What the kernel's backward needs of its forward, under the names a
# ``jax.checkpoint`` policy can keep them by: a Pallas call is not a dot,
# so a policy that saves dots alone throws both away and runs the forward
# kernel a second time in the backward to have them again.
FLASH_RESIDUALS = ("flash_out", "flash_lse")


def _flash_core_fwd(q, k, v, causal, scale, block_q, block_k, kernel):
    if kernel:
        out, lse = _flash_attention_pallas(
            q, k, v, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, return_lse=True,
        )
        out = checkpoint_name(out, FLASH_RESIDUALS[0])
        lse = checkpoint_name(lse, FLASH_RESIDUALS[1])
        return out, (q, k, v, out, lse)
    out = _blockwise_attention_jax(
        q, k, v, causal=causal, scale=scale, block_k=block_k
    )
    return out, (q, k, v)


def _flash_core_bwd(causal, scale, block_q, block_k, kernel, res, g):
    if kernel:
        # Pallas two-pass backward from the saved lse — never rebuilds the
        # [T, T] score matrix and never re-runs the forward.
        q, k, v, out, lse = res
        return _flash_attention_pallas_bwd(
            q, k, v, out, lse, g, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k,
        )
    q, k, v = res
    # Recompute-based backward through the blockwise scan: O(T·block)
    # memory, identical math to the forward kernel.
    _, vjp = jax.vjp(
        lambda q, k, v: _blockwise_attention_jax(
            q, k, v, causal=causal, scale=scale, block_k=block_k
        ),
        q, k, v,
    )
    return vjp(g)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# Measured-autotuner override (parallel/autotune.py): a persisted tune
# record's winning (block_q, block_k) is applied process-wide through
# this pair, consulted by _default_blocks only where the caller left an
# argument None — an explicit block at a call site always wins. None
# means "no tuned pin"; values still clamp to the sequence.
_TUNED_BLOCKS: "tuple[int | None, int | None]" = (None, None)


def set_tuned_blocks(block_q: int | None = None,
                     block_k: int | None = None) -> None:
    global _TUNED_BLOCKS
    _TUNED_BLOCKS = (block_q, block_k)


def clear_tuned_blocks() -> None:
    set_tuned_blocks(None, None)


def tuned_blocks() -> "tuple[int | None, int | None]":
    return _TUNED_BLOCKS


def _default_blocks(t_q: int, t_k: int,
                    block_q: int | None, block_k: int | None):
    """Length-bucketed defaults:

    * seq > 2048: 1024×1024 — fewer grid steps amortize the per-block
      scalar+VPU work; an earlier kernel-trace sweep (fwd/dq/dkv, d=64 and
      d=128) had it winning or tying every 8k shape.
    * seq <= 2048: 512×512 — at 2k a 1024 tile leaves a 2-step kv grid,
      too few blocks to hide the pipeline ramp, while 512 keeps 4; pinning
      1024 everywhere once cost the 2k shape about half its speed.

    Neither bucket has been re-measured on the current chip (PERF.md).
    Both clamp to the sequence (2048-wide tiles fail to compile against
    the 16M scoped-VMEM budget). Lengths that are a multiple of 512 but
    not 1024 (2560, 3072, ...) land in the 1024 bucket and pay a
    partially-padded tail tile; callers can still pin either block.
    Re-derive with ``tools/sweep_flash_blocks.py`` (device-trace kernel
    timing + wall check; needs a real TPU — Pallas on CPU is
    interpret-only)."""
    tuned_q, tuned_k = _TUNED_BLOCKS
    default = 512 if max(t_q, t_k) <= 2048 else 1024
    if block_q is None:
        block_q = min(tuned_q if tuned_q else default, t_q)
    if block_k is None:
        block_k = min(tuned_k if tuned_k else default, t_k)
    return block_q, block_k


# Dim roles of a [B, T, H, D] attention operand (parallel/sharding.py).
_QKV_ROLES = ("batch", None, "heads", None)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    force_jax: bool = False,
    mesh: Mesh | None = None,
) -> jax.Array:
    """Memory-efficient exact attention. q,k,v: [B, T, H, D] -> [B, T, H, D].

    K/V may have a different sequence length than Q (cross-attention /
    decode) and fewer heads than Q (GQA/MQA: H % H_kv == 0; each group of
    H/H_kv query heads shares one K/V head — the repeat happens here, and
    autodiff folds the grouped K/V gradients back automatically).
    ``force_jax=True`` pins the blockwise-JAX path (the reference tests
    compare the kernel with).

    Under a multi-device ``mesh`` (explicit, or the ambient ``set_mesh``
    one) the op runs per shard — batch over dp/ep, heads over tp — through
    ``per_shard``; the sequence is never split here (sp > 1 is ring
    attention's job).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    t_q, h = q.shape[1:3]
    t_k, h_kv = k.shape[1:3]
    if h % h_kv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
    block_q, block_k = _default_blocks(t_q, t_k, block_q, block_k)
    kernel = _on_tpu(mesh) and not force_jax

    def expand_kv(k, v, h):
        group = h // k.shape[2]
        if group == 1:
            return k, v
        return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)

    def local(q, k, v):
        b, _, h, d = q.shape
        k, v = expand_kv(k, v, h)
        qf = q.transpose(0, 2, 1, 3).reshape(b * h, t_q, d)
        kf = k.transpose(0, 2, 1, 3).reshape(b * h, t_k, d)
        vf = v.transpose(0, 2, 1, 3).reshape(b * h, t_k, d)
        out = _flash_core(qf, kf, vf, causal, scale, block_q, block_k, kernel)
        return out.reshape(b, h, t_q, d).transpose(0, 2, 1, 3)

    axes = auto_axes(mesh)
    if local_spec(k.shape, _QKV_ROLES, axes) != local_spec(
            q.shape, _QKV_ROLES, axes):
        # The K/V heads do not split over the axes the q heads do: expand
        # them first, so every shard's q heads meet their own K/V group.
        k, v = expand_kv(k, v, h)
    return per_shard(local, mesh, (_QKV_ROLES,) * 3, q, k, v)


# ---------------------------------------------------------------------------
# (out, lse) entry for ring attention
# ---------------------------------------------------------------------------
# Ring attention merges per-step partials with softmax statistics, so it
# needs the per-row log-sum-exp alongside the normalized output — and it
# differentiates through the merge weights, so lse carries a cotangent.
# d lse / d s = p folds into the flash backward as a shift of delta (see
# _flash_attention_pallas_bwd); the kernels are reused unchanged.


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse_core(q, k, v, causal, scale, block_q, block_k, mode):
    out, lse = _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k,
                              mode)[0]
    return out, lse


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k, mode):
    if mode == "jax":
        out, lse = _blockwise_attention_jax(
            q, k, v, causal=causal, scale=scale, block_k=block_k,
            return_lse=True,
        )
        return (out, lse), (q, k, v)
    out, lse = _flash_attention_pallas(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=(mode == "interpret"), return_lse=True,
    )
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(causal, scale, block_q, block_k, mode, res, g):
    g_out, g_lse = g
    if mode == "jax":
        q, k, v = res
        _, vjp = jax.vjp(
            lambda q, k, v: _blockwise_attention_jax(
                q, k, v, causal=causal, scale=scale, block_k=block_k,
                return_lse=True,
            ),
            q, k, v,
        )
        return vjp((g_out, g_lse))
    q, k, v, out, lse = res
    return _flash_attention_pallas_bwd(
        q, k, v, out, lse, g_out, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=(mode == "interpret"),
        g_lse=g_lse,
    )


_flash_lse_core.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    mode: str = "auto",
) -> tuple[jax.Array, jax.Array]:
    """Flash attention returning ``(out, lse)`` for partial-softmax merging.

    q,k,v: [B, T, H, D] -> out [B, T, H, D] (q dtype), lse [B, H, T] f32
    (log-sum-exp of the scaled scores per query row; the masked-row
    convention matches the Pallas kernel). ``mode``: "auto" picks the
    Pallas kernel on TPU and the blockwise-JAX path elsewhere; "jax" pins
    the fallback; "interpret" runs the kernel in interpreter mode (CPU
    tests of the kernel path).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if mode == "auto":
        mode = "pallas" if _on_tpu() else "jax"
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    block_q, block_k = _default_blocks(t_q, t_k, block_q, block_k)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, t_q, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, t_k, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, t_k, d)
    out, lse = _flash_lse_core(qf, kf, vf, causal, scale, block_q, block_k,
                               mode)
    return (
        out.reshape(b, h, t_q, d).transpose(0, 2, 1, 3),
        lse.reshape(b, h, t_q),
    )


# ---------------------------------------------------------------------------
# Attention of the serving engine against its stacked KV cache
# ---------------------------------------------------------------------------
# The cache lies [L, S, Tmax, Hkv, Dh] in HBM and is donated and rewritten
# in place on every dispatch. XLA cannot fuse the pick of one layer into
# the batched contraction that reads it (even a static slice of the
# operand is first copied out: a [S, Tmax, Hkv, Dh] slab per buffer per
# layer), so decode reads the buffer through a kernel whose index_map
# picks the layer from a scalar-prefetched index: K/V blocks are DMA'd
# straight out of the stacked buffer and nothing slab-shaped exists.


def ring_positions(cur, n_rows: int, ring: int):
    """Position held by each row of a ring cache whose newest entry is
    position ``cur`` (any shape; a trailing axis of ``n_rows`` is added):
    row r holds the largest position q <= cur with q % ring == r. Rows
    at or past ``ring`` (the parking row) and rows never written since
    position 0 read negative, which every mask refuses — so a ring is
    masked by POSITION and a reused slot cannot show its last tenant."""
    r = jnp.arange(n_rows)
    held = cur[..., None] - (cur[..., None] - r) % ring
    return jnp.where(r < ring, held, -1)


def cache_rows_merge(h_kv: int, d_k: int, d_v: int) -> bool:
    """Whether [Tmax, Hkv, D] is the same bytes as [Tmax * Hkv, D] under
    the TPU's tiling of the two minor dims, so a kernel may read the
    stacked cache as rows with no copy: D in whole 128-lane rows, Hkv a
    sublane tile (compiled for a v5e: 1, 2, 4, 8, 16, 24 merge; 12, and
    Dh 64, get another layout and the reshape would copy the whole
    cache per call, which is why heads of 64 lie two to a row,
    ``cache_heads_per_row``; so does Hkv 4 at Dh 256, which is why a wide
    K comes in tiles). ``h_kv`` and the widths are the buffer's own: its
    rows a position and their lanes."""
    return (d_k % 128 == 0 and d_v % 128 == 0
            and (h_kv % 8 == 0 or (h_kv in (1, 2, 4) and d_k == 128)))


def cache_heads_per_row(h_kv: int, d_k: int, d_v: int) -> int:
    """KV heads that one row of the stacked cache holds: 2 where K and V
    heads are 64 wide and pair up, else 1. A [.., Hkv, 64] bfloat16
    buffer may take 128 lanes a row on the device, twice its bytes, and
    its rows do not merge (``cache_rows_merge``); kept [.., Hkv / 2, 128]
    — the same bytes in the same order, KV head 2r in lanes 0..63 of row
    r and head 2r + 1 in lanes 64..127 — it costs 64 lanes a head and
    merges as any 128-wide cache does. The attention functions below
    know such a cache by its rows being twice the query's width."""
    return 2 if d_k == d_v == 64 and h_kv % 2 == 0 else 1


def _pairs_heads(q, k) -> bool:
    return jax.tree.leaves(k)[0].shape[-1] == 2 * q.shape[-1]


def _own_half_of(n_h: int, rows: int):
    """[Hq] 0 or 1: the half of a paired row that a query head's KV head
    lies in (``rows`` rows a position, two KV heads each)."""
    return (jnp.arange(n_h) // (n_h // (2 * rows))) % 2


def _pair_queries(q, rows: int):
    """q [.., Hq, D] against a cache of paired heads: [.., Hq, 2D] with
    each head's dims in its own KV head's half of the row and zeros in
    the other, so that its product with a row is its score against its
    own head alone, and the row's query heads (both KV heads' groups)
    are one group of the row."""
    n_h, d = q.shape[-2:]
    mine = _own_half_of(n_h, rows)[:, None] == jnp.arange(2 * d) // d
    return jnp.where(mine, jnp.concatenate([q, q], axis=-1), 0)


def _own_half(o, rows: int):
    """The result [.., Hq, 2D] over paired rows -> [.., Hq, D]: each
    head's own KV head's half of the values."""
    n_h, d = o.shape[-2], o.shape[-1] // 2
    second = _own_half_of(n_h, rows)[:, None] == 1
    return jnp.where(second, o[..., d:], o[..., :d])


def cache_rows_view(buf):
    """``buf`` [L, S, T, Hkv, D] as rows [L, S, T * Hkv, D] where that is
    the same bytes and the TPU would otherwise re-lay the whole buffer
    around a chunk's write or a slot's read: D whole 128-lane tiles and
    fewer KV heads than a sublane tile (compiled for a v5e: at 4 heads
    prefill copied every full-layer buffer to a head-major layout and
    back, 1 GB each way, per dispatch). Else ``buf`` as it is."""
    n_l, n_s, t, h_kv, d = buf.shape
    if d % 128 == 0 and h_kv in (1, 2, 4):
        return buf.reshape(n_l, n_s, t * h_kv, d)
    return buf


def cache_take(buf, layer, slot, start, n: int):
    """[n, Hkv, D] of one buffer from (layer, slot, start)."""
    rows = cache_rows_view(buf)
    if rows.ndim == buf.ndim:
        return lax.dynamic_slice(
            buf, (layer, slot, start, 0, 0), (1, 1, n) + buf.shape[3:])[0, 0]
    h_kv = buf.shape[3]
    return lax.dynamic_slice(
        rows, (layer, slot, start * h_kv, 0), (1, 1, n * h_kv, buf.shape[4])
    ).reshape((n,) + buf.shape[3:])


def cache_slot_rows(buf, layer, slots):
    """The rows [P, Tmax, Hkv, D] of ``slots`` [P] in one layer of one
    buffer: P small dynamic slices (on the TPU a gather over the stacked
    buffer lowers to slices of the WHOLE buffer)."""
    return jnp.stack([cache_take(buf, layer, slots[i], 0, buf.shape[2])
                      for i in range(slots.shape[0])])


def grouped_cache_attention(q, k, v, mask, *, scale=None, sink=None):
    """Grouped attention against cache rows in plain JAX — q
    [B, Sq, Hq, Dk] regrouped [B, Sq, Hkv, G, Dk] so GQA never
    head-repeats the cache k [B, T, Hkv, Dk] / v [B, T, Hkv, Dv]
    (Dv may differ from Dk); stored-dtype reads with fp32 MXU
    accumulation and fp32 softmax (the decode.py recipe).
    mask: [B, Sq, T] True where the key is visible. ``sink`` [Hq]: one
    more softmax column per query head that takes mass and adds no
    value. A cache of paired heads (k and v [B, T, Hkv / 2, 2D]
    against q [.., D]) is read as it lies. -> [B, Sq, Hq, Dv]."""
    b, s, n_h, d = q.shape
    h_kv = k.shape[2]
    if scale is None:
        scale = d ** -0.5
    if _pairs_heads(q, k):
        # rows of two KV heads (``cache_heads_per_row``)
        return _own_half(grouped_cache_attention(
            _pair_queries(q, h_kv), k, v, mask, scale=scale, sink=sink),
            h_kv)
    qg = q.reshape(b, s, h_kv, n_h // h_kv, d)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32,
    ) * scale
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    if sink is None:
        probs = jax.nn.softmax(scores, axis=-1)
    else:
        b_g = sink.astype(jnp.float32).reshape(1, h_kv, -1, 1, 1)
        m = jnp.maximum(scores.max(-1, keepdims=True), b_g)
        e = jnp.exp(scores - m)
        probs = e / (e.sum(-1, keepdims=True) + jnp.exp(b_g - m))
    return jnp.einsum(
        "bhgqk,bkhd->bqhgd", probs.astype(q.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype).reshape(b, s, n_h, v.shape[-1])


def _online_softmax_step(s, v, m_ref, l_ref, acc_ref, rows=...,
                         visible=None):
    """One key block of the running softmax over ``rows`` of the
    statistics: ``s`` the masked float32 scores [rows, block], ``v``
    [block, Dv], a ref or a value. ``visible``: zero p under it (a block
    that may hold no visible key, where m is not yet a real score)."""
    m = m_ref[rows]
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    if visible is not None:
        p = jnp.where(visible, p, 0.0)
    l_ref[rows] = alpha * l_ref[rows] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[rows] = alpha * acc_ref[rows] + jnp.dot(
        p.astype(v.dtype), v[...], preferred_element_type=jnp.float32,
    )
    m_ref[rows] = m_new


def _softmax_result(m_ref, l_ref, acc_ref, sink_ref, dtype):
    """acc / l when the last block has been seen. ``sink_ref`` [rows,
    1] or None: the sink as one more column, exp(b - m) joins the sum.
    m is a real score here (the newest position is always visible); a
    sink above it is rescaled like any late max."""
    l = l_ref[...]
    if sink_ref is None:
        return (acc_ref[...] / l).astype(dtype)
    b = sink_ref[...]
    m_all = jnp.maximum(m_ref[...], b)
    beta = jnp.exp(m_ref[...] - m_all)
    return (acc_ref[...] * beta / (l * beta + jnp.exp(b - m_all))
            ).astype(dtype)


def _cache_decode_kernel(
    layer_ref, pos_ref, src_ref, last_ref, *refs, scale, h_kv, window, ring,
    has_sink, k_parts,
):
    """One program = one (slot, key block). Refs: q_ref [Hq, Dk], o_ref
    [Hq, Dv]; k_ref [block, Dk] / v_ref [block, Dv], the rows (t,
    kv-head) of ``block / Hkv`` positions exactly as they lie in the
    cache — so both matmuls run on the stored layout, every query head
    against every kv-head's rows, and the mask keeps a head's own group
    (all but 1/Hkv of the MXU work is discarded; the MXU is otherwise
    idle in decode and the relayout it saves is not free).

    A block past the slot's last live one (``last_ref``, from
    ``decode_last_block``) is not computed, and not fetched: the index
    map stays on the last live block. A slot that reads nothing (pos <
    0) computes no block and writes a row of zeros.

    Full cache (``window`` 0): key block 0 holds position 0, which every
    slot that reads sees, so m is finite from the first block on and
    masked scores underflow to p = 0 with no guard. Ring cache: row r
    holds position pos - (pos - r) % ring, visible inside the last
    ``window`` positions and not before position 0; a block may hold no
    visible row, so p is zeroed under the mask. ``sink_ref`` [Hq, 1]:
    folded into the denominator when the last block has been seen."""
    q_ref, *k_refs = refs[:1 + k_parts]
    rest = refs[1 + k_parts:]
    if has_sink:
        v_ref, sink_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        (v_ref, o_ref, m_ref, l_ref, acc_ref), sink_ref = rest, None
    slot, kb = pl.program_id(0), pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cur = pos_ref[slot]

    @pl.when((kb <= last_ref[slot]) & (cur >= 0))
    def _block():
        # K in lane tiles (one, or several for a head wider than a
        # tile): the score is the sum of the tiles' products.
        lanes = k_refs[0].shape[-1]
        s = sum(
            lax.dot_general(
                q_ref[:, i * lanes:(i + 1) * lanes], k_ref[...],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            ) for i, k_ref in enumerate(k_refs)
        ) * scale                                          # [Hq, block]
        group = s.shape[0] // h_kv
        head = lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        row = lax.broadcasted_iota(jnp.int32, s.shape, 1) + kb * s.shape[1]
        if window:
            back = (cur - row // h_kv) % ring    # how far behind ``cur``
            visible = ((row % h_kv == head) & (back < window)
                       & (back <= cur))
        else:
            visible = (row % h_kv == head) & (row // h_kv <= cur)
        s = jnp.where(visible, s, NEG_INF)
        _online_softmax_step(s, v_ref, m_ref, l_ref, acc_ref,
                             visible=visible if window else None)

    seen_all = kb == pl.num_programs(1) - 1

    @pl.when(seen_all & (cur >= 0))
    def _finalize():
        o_ref[...] = _softmax_result(m_ref, l_ref, acc_ref, sink_ref,
                                     o_ref.dtype)

    @pl.when(seen_all & (cur < 0))
    def _nothing_read():
        o_ref[...] = jnp.zeros_like(o_ref)


# Rows (position, kv-head) of K and of V that one grid step of the decode
# kernel streams through VMEM (1 MB of bf16 each at Dh 128). Swept on a
# v5e inside the serving programs with the bound by live blocks on
# (``tools/sweep_decode_blocks.py``; PERF.md section 6, PR 39): a smaller
# block follows the live lengths closer, and every grid step, dead ones
# too, costs its fixed third of a microsecond. 2,048 and 4,096 read within
# 1.2% of each other at Mistral-7B's and MiMo's shapes (2,048 ahead with
# every lane decoding, 4,096 with three quarters parked), 1,024 2-5% and
# 512 10-19% slower, 8,192 2-10% slower: one constant serves both, and it
# stays, so the running softmax sums in the order it did.
DECODE_BLOCK_ROWS = 4096


def decode_key_block(t_read: int, h_kv: int,
                     block_rows: int | None = None) -> int:
    """Positions in one key block of ``cache_decode_attention``'s kernel:
    about ``block_rows`` rows (position, kv-head), a divisor of the
    ``t_read`` positions a slot's reservation (or its ring) holds."""
    rows = DECODE_BLOCK_ROWS if block_rows is None else block_rows
    return math.gcd(t_read, max(1, rows // h_kv))


def decode_last_block(pos, t_read: int, block: int):
    """The last key block of ``block`` positions that holds a live key of
    a slot at ``pos`` (a numpy or a jax array alike): the kernel reads a
    slot's blocks 0 .. this, whole. In a ring no row past ``pos`` holds
    a position before the first wrap, and every row does after it."""
    return pos.clip(0, t_read - 1) // block


def _cache_decode_pallas(q, *operands, n_k, has_sink, scale, block_rows,
                         window=0, ring=0, interpret=False):
    """``operands``: the ``n_k`` lane tiles of K, V, layer, pos and, with
    ``has_sink``, the sinks."""
    from jax.experimental.pallas import tpu as pltpu

    k_parts, (v_all, layer, pos, *sink) = operands[:n_k], operands[n_k:]
    n_l, n_s, t, h_kv, lanes = k_parts[0].shape
    d_v = v_all.shape[-1]
    n_h = q.shape[1]
    q = jnp.pad(q, ((0, 0), (0, 0), (0, n_k * lanes - q.shape[-1])))
    # [.., Tmax, Hkv, D] -> [.., Tmax * Hkv, D]: the same bytes under the
    # TPU's tiling of the two minor dims (a bitcast, no copy).
    k_parts = [k.reshape(n_l, n_s, t * h_kv, lanes) for k in k_parts]
    v_all = v_all.reshape(n_l, n_s, t * h_kv, d_v)
    # A ring is read over its ``ring`` positions only: the parking row
    # past them never enters a block.
    t_read = ring if window else t
    positions = decode_key_block(t_read, h_kv, block_rows)
    block = positions * h_kv
    pos = pos.astype(jnp.int32)
    # A slot that reads nothing names, at every grid step, the block the
    # step before it left in VMEM: the last live block of the nearest
    # slot before it that reads (slot 0's first block where none does).
    src = lax.cummax(jnp.where(pos >= 0, jnp.arange(n_s, dtype=jnp.int32), 0))
    last = decode_last_block(pos, t_read, positions)[src]
    q_spec = pl.BlockSpec((None, n_h, n_k * lanes),
                          lambda s, j, layer, pos, src, last: (s, 0, 0))
    o_spec = pl.BlockSpec((None, n_h, d_v),
                          lambda s, j, layer, pos, src, last: (s, 0, 0))

    def kv_map(s, j, layer, pos, src, last):
        # past the slot's last live block: stay there, so the pipeline
        # issues no new copy
        at = jnp.where(pos[s] < 0, last[s], jnp.minimum(j, last[s]))
        return (layer[0], src[s], at, 0)

    def kv_spec(d):
        return pl.BlockSpec((None, None, block, d), kv_map)

    in_specs = [q_spec] + [kv_spec(lanes)] * n_k + [kv_spec(d_v)]
    operands = [q, *k_parts, v_all]
    if has_sink:
        in_specs.append(pl.BlockSpec(
            (n_h, 1), lambda s, j, layer, pos, src, last: (0, 0)))
        operands.append(sink[0].astype(jnp.float32).reshape(n_h, 1))
    return pl.pallas_call(
        functools.partial(_cache_decode_kernel, scale=scale, h_kv=h_kv,
                          window=window, ring=ring, has_sink=has_sink,
                          k_parts=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_s, t_read // positions),
            in_specs=in_specs,
            out_specs=o_spec,
            scratch_shapes=[
                pltpu.VMEM((n_h, 1), jnp.float32),
                pltpu.VMEM((n_h, 1), jnp.float32),
                pltpu.VMEM((n_h, d_v), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_s, n_h, d_v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(layer.reshape(1).astype(jnp.int32), pos, src, last, *operands)


# Dim roles of the decode query [S, Hq, Dh] and the stacked cache.
_DECODE_Q_ROLES = ("batch", "heads", None)
_CACHE_ROLES = (None, "batch", None, "heads", None)


def cache_decode_attention(
    q: jax.Array,
    k_all: jax.Array,
    v_all: jax.Array,
    layer: jax.Array,
    pos: jax.Array,
    *,
    scale: float | None = None,
    block_rows: int | None = None,
    mode: str = "auto",
    mesh: Mesh | None = None,
    window: int = 0,
    sink: jax.Array | None = None,
) -> jax.Array:
    """One decode query per slot against ONE layer of a stacked cache.

    q: [S, Hq, Dk]; k_all: [L, S, Tmax, Hkv, Dk], v_all: [L, S, Tmax,
    Hkv, Dv] (Hq % Hkv == 0; Dv may differ from Dk); ``layer`` a traced
    scalar; slot s sees keys 0..pos[s] inclusive. A slot with pos < 0
    decodes nothing: it reads no key, and its row of the result is
    finite and means nothing (zeros from the kernel).
    -> [S, Hq, Dv]. The bytes read are a slot's LIVE key blocks of the
    layer's own K/V, once: the blocks of ``decode_key_block`` positions
    up to the one that holds position pos[s] (``decode_last_block``), in
    a ring up to row min(pos[s], ring - 1); a grid step past that block
    fetches and computes nothing, and costs its fixed third of a
    microsecond. About ``block_rows`` rows (position, kv-head) of each
    stream through VMEM per grid step (``DECODE_BLOCK_ROWS``).

    ``k_all`` may also be a TUPLE of 128-lane tiles [L, S, Tmax, Hkv,
    128] of a K wider than one tile and no multiple of it, the last
    zero-filled past Dk (Dk 192: two tiles): under the TPU's tiling a
    [Tmax, 4, 256] buffer does not merge to rows without a copy of the
    whole cache and a [Tmax, 4, 128] one does (compiled for a v5e), so
    such a cache is kept in tiles and the score is the sum of the tiles'
    products. Heads 64 wide lie two to a row ([L, S, Tmax, Hkv / 2, 128],
    ``cache_heads_per_row``); the kernel then scores a query head
    against its own half of a row.

    ``window`` > 0 reads the cache as a RING: its third axis holds
    ``ring`` = Tmax - 1 positions and one parking row; position p lies
    at row p % ring, and slot s sees the last ``window`` positions up to
    pos[s] (``ring_positions``). ``sink`` [Hq]: a per-head bias that
    joins the softmax denominator and adds no value.

    ``mode`` as in ``flash_attention_lse``; "auto" takes the kernel on a
    TPU where the cache's rows merge without a copy, the plain path
    elsewhere. Under a multi-device mesh the kernel runs per shard —
    slots over dp/ep, heads over tp — and refuses a tp that splits the
    query heads but not the KV heads.
    """
    k_parts = k_all if isinstance(k_all, tuple) else (k_all,)
    h_kv, d = k_parts[0].shape[3:]
    ring = k_parts[0].shape[2] - 1 if window else 0
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if _pairs_heads(q, k_all):
        # rows of two KV heads (``cache_heads_per_row``): each query head
        # against its own half, through the same kernel
        return _own_half(cache_decode_attention(
            _pair_queries(q, h_kv), k_all, v_all, layer, pos, scale=scale,
            block_rows=block_rows, mode=mode, mesh=mesh, window=window,
            sink=sink), h_kv)
    if mode == "auto":
        merges = cache_rows_merge(h_kv, d, v_all.shape[-1])
        mode = "pallas" if merges and _on_tpu(mesh) else "jax"
    if mode == "jax":
        k = jnp.concatenate(
            [lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
             for c in k_parts], axis=-1)[..., :q.shape[-1]]
        v = lax.dynamic_index_in_dim(v_all, layer, 0, keepdims=False)
        if window:
            held = ring_positions(pos, k.shape[1], ring)
            mask = (held >= 0) & (held > pos[:, None] - window)
        else:
            mask = jnp.arange(k.shape[1])[None, :] <= pos[:, None]
        return grouped_cache_attention(
            q[:, None], k, v, mask[:, None], scale=scale, sink=sink
        )[:, 0]
    axes = auto_axes(mesh)
    if (local_spec(q.shape, _DECODE_Q_ROLES, axes)[1]
            != local_spec(k_parts[0].shape, _CACHE_ROLES, axes)[3]):
        raise ValueError(
            f"{h_kv} KV heads do not split over the mesh as the "
            f"{q.shape[1]} query heads do"
        )
    local = functools.partial(
        _cache_decode_pallas, n_k=len(k_parts), has_sink=sink is not None,
        scale=scale, block_rows=block_rows, window=window, ring=ring,
        interpret=(mode == "interpret"),
    )
    roles = ([_DECODE_Q_ROLES] + [_CACHE_ROLES] * (len(k_parts) + 1)
             + [(), ("batch",)])
    operands = [q, *k_parts, v_all, layer, pos]
    if sink is not None:
        roles.append(("heads",))
        operands.append(sink)
    return per_shard(local, mesh, tuple(roles), *operands)


# ---------------------------------------------------------------------------
# A prefill chunk against its slots' rows of the stacked cache
# ---------------------------------------------------------------------------


def rowwise_cache_attention(q, k, v, mask, *, scale=None, sink=None):
    """``grouped_cache_attention`` one batch row at a time (``lax.map``):
    for a batch whose float32 scores [B, Hq, Sq, T] would not fit at
    once."""
    return lax.map(
        lambda row: grouped_cache_attention(
            row[0][None], row[1][None], row[2][None], row[3][None],
            scale=scale, sink=sink)[0],
        (q, k, v, mask))


def prefill_key_block(t_max: int, h_kv: int, block_rows: int = 2048) -> int:
    """Positions in one key block of ``cache_prefill_attention``'s kernel:
    about ``block_rows`` rows (position, kv-head), a divisor of Tmax. A
    row whose chunk ends at ``end`` reads ``ceil(end / block)`` blocks."""
    return math.gcd(t_max, max(1, block_rows // h_kv))


def _cache_prefill_kernel(
    layer_ref, slots_ref, ends_ref, *refs, scale, h_kv, group, chunk,
    block_q, has_sink, k_parts,
):
    """One program = one (row, kv-head, key block). Refs: q_ref [C * G,
    Dk], the head's G query heads of every position of the chunk, rows
    (position, head in group); k_ref [block * Hkv, 128] per lane tile /
    v_ref [block * Hkv, Dv], the rows (t, kv-head) of ``block`` positions
    as they lie in the cache; o_ref [C * G, Dv]. The head's own rows are
    taken out of a block by a strided read of its float32 copy (a
    bfloat16 row shares its sublane with the next head's, and Mosaic
    reads strided only in 32 bits), so each head's queries meet their
    own keys only: no product is computed to be masked away.

    Query row i of the chunk sees keys 0 .. ends - C + i. Key block 0
    holds position 0, which every query sees, so m is finite from the
    first block on and masked scores underflow to p = 0 with no guard;
    a block past the row's last visible position is not computed (and
    not fetched: the index map stays on the last live block)."""
    q_ref, *k_refs = refs[:1 + k_parts]
    rest = refs[1 + k_parts:]
    if has_sink:
        v_ref, sink_ref, o_ref, m_ref, l_ref, acc_ref, *x_refs = rest
    else:
        (v_ref, o_ref, m_ref, l_ref, acc_ref, *x_refs), sink_ref = rest, None
    x_refs = x_refs or [None] * (k_parts + 1)
    row, head, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    block = v_ref.shape[0] // h_kv
    end = ends_ref[row]

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def own_rows(ref, x_ref):
        """[block, D] of this kv-head out of the block's rows (t, h)."""
        if h_kv == 1:
            return ref[...]
        if ref.dtype.itemsize == 4:
            return ref[pl.ds(head, block, stride=h_kv), :]
        x_ref[...] = ref[...].astype(jnp.float32)
        return x_ref[pl.ds(head, block, stride=h_kv), :].astype(ref.dtype)

    @pl.when(kb * block < end)
    def _block():
        ks = [own_rows(k_ref, x_ref) for k_ref, x_ref in zip(k_refs, x_refs)]
        v = own_rows(v_ref, x_refs[-1])
        lanes = ks[0].shape[-1]
        n_q = q_ref.shape[0]
        for lo in range(0, n_q, block_q):
            rows = slice(lo, min(lo + block_q, n_q))
            s = sum(
                lax.dot_general(
                    q_ref[rows, i * lanes:(i + 1) * lanes], k,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) for i, k in enumerate(ks)
            ) * scale                                      # [rows, block]
            at = lax.broadcasted_iota(jnp.int32, s.shape, 0) + lo
            key = lax.broadcasted_iota(jnp.int32, s.shape, 1) + kb * block
            s = jnp.where(key <= end - chunk + at // group, s, NEG_INF)
            _online_softmax_step(s, v, m_ref, l_ref, acc_ref, rows)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = _softmax_result(m_ref, l_ref, acc_ref, sink_ref,
                                     o_ref.dtype)


def _cache_prefill_pallas(q, *operands, n_k, has_sink, scale, block_rows,
                          block_q=512, interpret=False):
    """``operands``: the ``n_k`` lane tiles of K, V, layer, slots, ends
    and, with ``has_sink``, the sinks. A head's queries meet a key block
    ``block_q`` rows at a time (256 and 512 measured alike on a v5e at
    4 x 128 x 64 queries, all 2,048 at once a fifth slower)."""
    from jax.experimental.pallas import tpu as pltpu

    k_parts, (v_all, layer, slots, ends, *sink) = operands[:n_k], operands[n_k:]
    n_l, n_s, t, h_kv, lanes = k_parts[0].shape
    d_v = v_all.shape[-1]
    p, c, n_h, _ = q.shape
    group = n_h // h_kv
    n_q, d_k = c * group, n_k * lanes
    # [P, C, Hq, Dk] -> [P, Hkv, C * G, Dk]: a kv-head's queries together
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, d_k - q.shape[-1]),))
    q = q.reshape(p, c, h_kv, group, d_k).transpose(0, 2, 1, 3, 4)
    q = q.reshape(p, h_kv, n_q, d_k)
    # [.., Tmax, Hkv, D] -> [.., Tmax * Hkv, D]: the same bytes under the
    # TPU's tiling of the two minor dims (a bitcast, no copy).
    k_parts = [k.reshape(n_l, n_s, t * h_kv, lanes) for k in k_parts]
    v_all = v_all.reshape(n_l, n_s, t * h_kv, d_v)
    block = prefill_key_block(t, h_kv, block_rows)

    def q_map(r, h, j, layer, slots, ends):
        return (r, h, 0, 0)

    def kv_map(r, h, j, layer, slots, ends):
        # past the row's last visible block: stay there, so the pipeline
        # issues no new copy
        return (layer[0], slots[r], jnp.minimum(j, (ends[r] - 1) // block), 0)

    def kv_spec(d):
        return pl.BlockSpec((None, None, block * h_kv, d), kv_map)

    in_specs = ([pl.BlockSpec((None, None, n_q, d_k), q_map)]
                + [kv_spec(lanes)] * n_k + [kv_spec(d_v)])
    operands = [q, *k_parts, v_all]
    if has_sink:
        in_specs.append(pl.BlockSpec(
            (None, n_q, 1), lambda r, h, j, layer, slots, ends: (h, 0, 0)))
        operands.append(jnp.broadcast_to(
            sink[0].astype(jnp.float32).reshape(h_kv, 1, group),
            (h_kv, c, group)).reshape(h_kv, n_q, 1))
    scratch = [
        pltpu.VMEM((n_q, 1), jnp.float32),
        pltpu.VMEM((n_q, 1), jnp.float32),
        pltpu.VMEM((n_q, d_v), jnp.float32),
    ]
    if h_kv > 1 and v_all.dtype.itemsize < 4:
        # float32 copies of a block, one per operand (own_rows)
        scratch += [pltpu.VMEM((block * h_kv, d), jnp.float32)
                    for d in [lanes] * n_k + [d_v]]
    out = pl.pallas_call(
        functools.partial(_cache_prefill_kernel, scale=scale, h_kv=h_kv,
                          group=group, chunk=c, block_q=block_q,
                          has_sink=has_sink, k_parts=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(p, h_kv, t // block),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, None, n_q, d_v), q_map),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((p, h_kv, n_q, d_v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(layer.reshape(1).astype(jnp.int32), slots.astype(jnp.int32),
      ends.astype(jnp.int32), *operands)
    return out.reshape(p, h_kv, c, group, d_v).transpose(
        0, 2, 1, 3, 4).reshape(p, c, n_h, d_v)


def cache_prefill_attention(
    q: jax.Array,
    k_all,
    v_all: jax.Array,
    layer: jax.Array,
    slots: jax.Array,
    ends: jax.Array,
    *,
    scale: float | None = None,
    sink: jax.Array | None = None,
    mode: str = "auto",
    block_rows: int = 2048,
) -> jax.Array:
    """One prefill chunk per row against ONE layer of a stacked cache,
    the chunk's own K/V already written: the twin of
    ``cache_decode_attention`` for a chunk whose float32 scores against
    the whole reservation are too large to hold.

    q: [P, C, Hq, Dk]; k_all: [L, S, Tmax, Hkv, Dk] (or the tuple of
    128-lane tiles a wide K is kept in, or the paired rows of heads 64
    wide), v_all: [L, S, Tmax, Hkv, Dv];
    ``layer`` a traced scalar; row r reads slot ``slots[r]`` and its
    query i sees keys 0 .. ends[r] - C + i (``ends`` = the chunks' starts
    + C, in [C, Tmax]). ``sink`` [Hq] as in the decode kernel.
    -> [P, C, Hq, Dv].

    The kernel reads each slot's K/V blocks straight out of the stacked
    buffer, only up to the block that holds position ``ends[r] - 1``
    (``prefill_key_block`` positions a block; 2,048 rows a block
    measured 1.6 times as fast as 1,024 on a v5e, and 4,096 pass the
    16 MB of VMEM a kernel is given), once per KV head, and keeps scores, running maximum, denominator and accumulator on the
    chip: bfloat16 operands, float32 scores and softmax, probabilities
    in the compute dtype into the second product with float32
    accumulation (the plain path's recipe).

    ``mode`` "jax" is the plain path: the slots' rows read out whole and
    ``grouped_cache_attention`` row by row. "auto" takes the kernel on a
    TPU where the cache's rows merge without a copy
    (``cache_rows_merge``) and no mesh axis is left to partition (the
    slots index the cache's batch axis, which a mesh shards)."""
    k_parts = k_all if isinstance(k_all, tuple) else (k_all,)
    t, h_kv, d = k_parts[0].shape[2:]
    c = q.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if _pairs_heads(q, k_all):
        # rows of two KV heads, as in ``cache_decode_attention``
        return _own_half(cache_prefill_attention(
            _pair_queries(q, h_kv), k_all, v_all, layer, slots, ends,
            scale=scale, sink=sink, mode=mode, block_rows=block_rows), h_kv)
    if mode == "auto":
        takes = (cache_rows_merge(h_kv, d, v_all.shape[-1]) and _on_tpu()
                 and math.prod(auto_axes().values()) == 1)
        mode = "pallas" if takes else "jax"
    if mode == "jax":
        k = jnp.concatenate([cache_slot_rows(tile, layer, slots)
                             for tile in k_parts], axis=-1)[..., :q.shape[-1]]
        v = cache_slot_rows(v_all, layer, slots)
        sees = ends[:, None] - c + jnp.arange(c)[None, :]        # [P, C]
        mask = sees[:, :, None] >= jnp.arange(t)[None, None, :]
        return rowwise_cache_attention(q, k, v, mask, scale=scale, sink=sink)
    operands = [q, *k_parts, v_all, layer, slots, ends]
    if sink is not None:
        operands.append(sink)
    return _cache_prefill_pallas(
        *operands, n_k=len(k_parts), has_sink=sink is not None, scale=scale,
        block_rows=block_rows, interpret=(mode == "interpret"))
