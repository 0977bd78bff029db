"""Grouped matrix product: rows sorted by group against one weight matrix
per group — the expert products of a dropless expert layer.

On a TPU this is the megablox Pallas kernel that ships with jax
(``jax.experimental.pallas.ops.tpu.megablox``) with tiles sized for
WEIGHT STREAMING: an expert layer at serving batch sizes has a handful of
rows a group, so the product's time is the time to read each visited
group's [k, n] weights once, and the tile of them a grid step moves has to
be large enough to keep the memory pipe full. ``lax.ragged_dot``'s own TPU
lowering read 21% of the HBM peak at 2 rows a group (measured on a v5e, PR
28: 16 groups of [4096, 4096] under 512 rows). Elsewhere, and for shapes
the kernel's tiles do not divide, ``lax.ragged_dot``.

The tile rule (``_tiling``). The kernel's grid is (n tiles, visited
(row tile, group) pairs, k tiles), k innermost, and a grid step fetches
the [tm, tk] block of ROWS at (row tile, k tile) beside the [tk, tn] block
of weights. With one k tile the rows' block index stays put over the
consecutive groups of a row tile, and the pipeline fetches it once per n
tile; with two it alternates on every step, and 512 KB of rows are read
again beside every 2 MB of weights, though a handful of them belong to the
group being streamed (gate|up of a [4096 -> 2 x 2048] expert so moved 1.25 x
its weights: PR 36). So ``tk`` is all of ``k`` wherever a [k, 128] tile
fits the streamed tile's bytes, and ``tn`` gives way instead."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.ops import attention

# Rows a tile holds (a pass of the expert layer is a whole number of
# them), and the bytes of weights one grid step streams: the [tk, tn]
# tile is double-buffered in VMEM beside the [tm, tk] rows and a float32
# [tm, tn] accumulator.
ROW_TILE = 128
_WEIGHT_TILE_BYTES = 2 * 1024 * 1024


def _tiling(m: int, k: int, n: int, itemsize: int):
    """(tm, tk, tn) dividing (m, k, n), or None where none does: the
    widest ``tn`` of 512, 256, 128 at which a whole-``k`` weight tile
    keeps within the streamed tile's bytes (the rows then stay resident
    over a row tile's groups: module docstring); where not even a
    [k, 128] tile does, ``tk`` halves under it."""
    if m % ROW_TILE or n % 128 or k % 128:
        return None
    widths = [t for t in (512, 256, 128) if n % t == 0]
    tn = next((t for t in widths
               if k * t * itemsize <= _WEIGHT_TILE_BYTES), widths[-1])
    tk = k
    while tk * tn * itemsize > _WEIGHT_TILE_BYTES and tk % 256 == 0:
        tk //= 2
    return ROW_TILE, tk, tn


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, mode: str = "auto") -> jax.Array:
    """lhs [m, k] whose first ``group_sizes[0]`` rows belong to group 0,
    the next ``group_sizes[1]`` to group 1, ...; rhs [groups, k, n].
    -> [m, n] in lhs's dtype (float32 accumulation). Rows past the
    groups' sum belong to no group and come back UNDEFINED: the caller
    selects them out. ``mode``: "auto" (the kernel on a TPU where its
    tiles divide the shapes), "pallas", "interpret", "jax"."""
    m, k = lhs.shape
    tiling = _tiling(m, k, rhs.shape[2], lhs.dtype.itemsize)
    if mode == "auto":
        mode = "pallas" if tiling and attention._on_tpu() else "jax"
    if mode == "jax":
        return lax.ragged_dot(lhs, rhs, group_sizes)
    # (the package rebinds the name ``gmm`` to its differentiable
    # wrapper, so the kernel's module is taken by its full name)
    kernel = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    return kernel.gmm(lhs, rhs, group_sizes.astype(jnp.int32),
                      preferred_element_type=lhs.dtype, tiling=tiling,
                      interpret=(mode == "interpret"))
