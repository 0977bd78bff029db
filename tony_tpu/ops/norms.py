"""Fused RMSNorm: one VMEM pass per row-block on TPU (Pallas), einsum-free
JAX fallback elsewhere. Backward is XLA autodiff of the fallback (the op is
cheap enough that a hand bwd kernel buys nothing — HBM traffic dominates and
recompute fuses into the surrounding matmul)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.sharding import Mesh

from tony_tpu.ops.attention import _on_tpu
from tony_tpu.parallel.sharding import per_shard


def _rms_norm_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[:] = (x * jax.lax.rsqrt(var + eps) * w_ref[:].astype(jnp.float32)).astype(
        o_ref.dtype
    )


def _rms_norm_jax(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def _rms_norm_pallas(x, w, eps, block_rows, interpret=False):
    rows, d = x.shape
    block = min(block_rows, rows)
    return pl.pallas_call(
        functools.partial(_rms_norm_kernel, eps=eps),
        grid=(pl.cdiv(rows, block),),
        in_specs=[
            pl.BlockSpec((block, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _rms_core(x, w, eps, block_rows, kernel, mesh):
    """Only the forward kernel runs per shard; the custom_vjp sits
    OUTSIDE ``per_shard`` so the (plain-JAX) backward stays under XLA's
    own partitioning — x is replicated over tp/pp, and transposing the
    shard_map would all-reduce every dx over those axes."""
    if not kernel:
        return _rms_norm_jax(x, w, eps)

    def local(x, w):
        rows = x.reshape(-1, x.shape[-1])
        return _rms_norm_pallas(rows, w, eps, block_rows).reshape(x.shape)

    # Rows are normed where they live: leading dim over dp/ep, the second
    # of a [b, t, d] input over sp; the feature dim is never split.
    return per_shard(local, mesh, (("batch", "seq")[: x.ndim - 1], ()), x, w)


def _rms_fwd(x, w, eps, block_rows, kernel, mesh):
    return _rms_core(x, w, eps, block_rows, kernel, mesh), (x, w)


def _rms_bwd(eps, block_rows, kernel, mesh, res, g):
    x, w = res
    _, vjp = jax.vjp(lambda x, w: _rms_norm_jax(x, w, eps), x, w)
    return vjp(g)


_rms_core.defvjp(_rms_fwd, _rms_bwd)


def rms_norm(
    x: jax.Array,
    w: jax.Array,
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    force_jax: bool = False,
    mesh: Mesh | None = None,
) -> jax.Array:
    """RMSNorm over the last axis. x: [..., d], w: [d]. Under a
    multi-device ``mesh`` (explicit, or the ambient ``set_mesh`` one) the
    kernel runs per shard through ``per_shard``."""
    kernel = _on_tpu(mesh) and not force_jax
    return _rms_core(x, w, eps, block_rows, kernel, mesh)
