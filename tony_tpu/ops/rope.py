"""Rotary position embeddings, in pure JAX, twice over.

``apply_rope`` splits the feature axis into its even and odd lanes and
stacks them back. As written that does NOT fuse into the surrounding
projections on the chip: compiled for a v5e at Mistral widths each call
is a transposing copy that makes the 128 lanes the major axis, two
gathers, a concatenate and two more relayouts, and its transpose under
autodiff is two scatter-adds into zero-filled buffers with float32 copies
of q-shaped arrays on the way (ISSUE 33; PERF.md section 6). The inference
layer (``models/decode.py``) still calls it: its programs stay as they
are until the serving cells are re-rated (ROADMAP Queue 3, D-two-ropes).

``rotate_rope`` is the training side's: the same rotation without ever
splitting the lane axis, ``y = x*C + swap(x)*S``, with its own backward
(the inverse rotation of the cotangent: no residual but the tables). The
same products and the same one addition per lane as ``apply_rope``, so
the two agree bit for bit forward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def rope_frequencies(
    head_dim: int, max_seq: int, *, theta: float = 10000.0
) -> tuple[jax.Array, jax.Array]:
    """Precompute (cos, sin) tables: [max_seq, head_dim // 2], fp32."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    *,
    positions: jax.Array | None = None,
) -> jax.Array:
    """Rotate pairs of features. x: [B, T, H, D]; cos/sin: [max_seq, D/2].

    ``positions`` ([B, T] or [T]) selects rows of the tables — required under
    sequence parallelism where a shard's local index 0 is global index
    shard*T_local (the ring layer passes the offset positions).
    """
    b, t, h, d = x.shape
    if positions is None:
        positions = jnp.arange(t)
    c = cos[positions]  # [T, D/2] or [B, T, D/2]
    s = sin[positions]
    if c.ndim == 2:
        c = c[None]
        s = s[None]
    c = c[:, :, None, :].astype(jnp.float32)
    s = s[:, :, None, :].astype(jnp.float32)
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    r1 = x1 * c - x2 * s
    r2 = x1 * s + x2 * c
    out = jnp.stack([r1, r2], axis=-1).reshape(b, t, h, d)
    return out.astype(x.dtype)


def _swap_pairs(x: jax.Array) -> jax.Array:
    """Neighbouring features exchanged (0<->1, 2<->3, ...), in float32, as
    a product with a constant matrix of zeros and ones: each result is a
    sum of ONE term, so it is exact, and on the chip the rotation fuses
    around the product where a lane roll and a select materialise their
    slices in float32 (ISSUE 33's two scratch compiles). A float32 ``x``
    asks for the product's highest precision: the default rounds a
    float32 operand to bfloat16."""
    d = x.shape[-1]
    lane = jnp.arange(d)
    exchange = (lane[:, None] == (lane ^ 1)[None, :]).astype(x.dtype)
    return jnp.einsum(
        "...d,de->...e", x, exchange,
        preferred_element_type=jnp.float32,
        precision=None if x.dtype == jnp.bfloat16 else lax.Precision.HIGHEST,
    )


@jax.custom_vjp
def _rotate(x, c, s):
    out = x.astype(jnp.float32) * c + _swap_pairs(x) * s
    return out.astype(x.dtype)


def _rotate_fwd(x, c, s):
    # Through ``_rotate`` itself, not its body: the call stays one opaque
    # equation to a ``jax.checkpoint`` policy, which would otherwise see
    # the exchange's dot, count it among the matmul outputs worth keeping
    # ("dots") and save a float32 array of x's size per call.
    return _rotate(x, c, s), (c, s)


def _rotate_bwd(tables, g):
    # The cotangent of a rotation is the inverse rotation: the same form
    # with the sines' signs exchanged within each pair, which is -s. The
    # exchange is applied to the cotangent as it arrives (bfloat16 in
    # training), not to a float32 product the matmul would round.
    c, s = tables
    dx = g.astype(jnp.float32) * c - _swap_pairs(g) * s
    return dx.astype(g.dtype), None, None


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def rotate_rope(
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    *,
    positions: jax.Array | None = None,
) -> jax.Array:
    """``apply_rope``'s function of the same arguments for the TRAINING
    layer: x [B, T, H, D] rotated in place as ``x*C + swap(x)*S``, with C
    the cosines repeated pairwise, S the sines carrying the pair's sign
    (-s on even lanes, +s on odd) and ``swap`` the exchange of
    neighbouring lanes. On even lanes that is ``x1*c + x2*(-s)``, on odd
    ``x2*c + x1*s``: ``apply_rope``'s products and addition, in float32.
    Its VJP needs no residual but the tables, so nothing q-shaped is kept
    or scattered for it."""
    t = x.shape[1]
    if positions is None:
        positions = jnp.arange(t)
    c = cos[positions].astype(jnp.float32)  # [T, D/2] or [B, T, D/2]
    s = sin[positions].astype(jnp.float32)
    c = jnp.repeat(c, 2, axis=-1)
    s = jnp.stack([-s, s], axis=-1).reshape(c.shape)
    if c.ndim == 2:
        c, s = c[None], s[None]
    return _rotate(x, c[:, :, None, :], s[:, :, None, :])
