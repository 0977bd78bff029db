"""The precision a control computes in: the nearest below the one the
configuration states. The configurations here state bfloat16 matmuls, so
the control rounds both operands of every matmul to fp8 (e4m3: four
significant bits) under a per-tensor scale, and accumulates in float32 —
the step that would tempt a later PR."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def fp8_e4m3(x):
    """Round to four significant bits (e4m3's mantissa) after scaling the
    tensor's largest magnitude to e4m3's largest normal (448), so no value
    leaves the format's exponent range at the top; the few-bit mantissa is
    what the control is about."""
    x = x.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = amax / 448.0
    m, e = jnp.frexp(x / scale)          # m in [0.5, 1)
    q = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    # e4m3's smallest subnormal step is 2**-9: flush below half of it.
    q = jnp.where(jnp.abs(x / scale) < 2.0 ** -10, 0.0, q)
    # Straight-through: rounding has no slope, so a gradient passes as if
    # the operand were exact (the backward's matmuls then see the rounded
    # operands the forward saved).
    return x + jax.lax.stop_gradient(q * scale - x)


OPERAND = {
    "float32": lambda x: x.astype(jnp.float32),
    "fp8": fp8_e4m3,
}
