"""Logical-axis sharding rules: model code names array dimensions by role
("batch", "seq", "embed", ...); this module maps roles onto mesh axes. The
mapping is the whole parallelism policy — change the table, change the
strategy, model code untouched (the TPU-native analogue of the reference's
framework-runtime switch seam, TaskExecutor.java:128-151: policy lives in one
place, mechanism elsewhere).
"""

from __future__ import annotations

import math
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# role -> mesh axis (or tuple of axes). None = replicated.
LOGICAL_RULES: dict[str, Any] = {
    "batch": ("dp", "ep"),   # ep folds into the batch split outside MoE blocks
    "seq": "sp",             # sequence/context parallel (ring attention)
    "embed": None,           # activations replicated over tp; weights split below
    "heads": "tp",           # attention heads tensor-parallel
    "kv": None,
    "mlp": "tp",             # MLP hidden dim tensor-parallel (megatron split)
    "vocab": "tp",
    "expert": "ep",          # MoE expert axis
    "layers": "pp",          # stacked layer params pipeline-staged
    "embed_fsdp": "dp",      # weight-sharding (fsdp/zero-3) along embed dim
    "stage": "pp",
}


def logical_spec(*axes: str | None, rules: dict[str, Any] | None = None) -> P:
    """('batch','seq','embed') -> PartitionSpec(('dp','ep'),'sp',None)."""
    rules = LOGICAL_RULES if rules is None else rules
    out = []
    for ax in axes:
        if ax is None:
            out.append(None)
        else:
            if ax not in rules:
                # .get() would silently replicate a typo'd role ("head" for
                # "heads") — an OOM or lost parallelism with no error.
                raise KeyError(f"unknown logical axis {ax!r}; known: {sorted(rules)}")
            out.append(rules[ax])
    return P(*out)


def logical_sharding(
    mesh: Mesh, *axes: str | None, rules: dict[str, Any] | None = None
) -> NamedSharding:
    return NamedSharding(mesh, logical_spec(*axes, rules=rules))


def with_logical_constraint(
    x: jax.Array, *axes: str | None, mesh: Mesh | None = None
) -> jax.Array:
    """In-graph sharding hint (lax.with_sharding_constraint under jit)."""
    spec = logical_spec(*axes)
    if mesh is not None:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def auto_axes(mesh: Mesh | None = None) -> dict[str, int]:
    """Sizes of the mesh axes XLA still partitions automatically at this
    point of the trace: the axes of ``mesh`` (or, with none given, of the
    ambient ``jax.sharding.set_mesh`` mesh) minus those an enclosing
    ``shard_map`` already made manual. Empty outside any mesh."""
    ambient = jax.sharding.get_abstract_mesh()
    shape = ambient.shape if mesh is None else mesh.shape
    return {n: s for n, s in shape.items() if n not in ambient.manual_axes}


def local_spec(shape, roles, axes: dict[str, int]) -> P:
    """PartitionSpec of one ``per_shard`` operand: each dim takes the
    ``axes`` its role maps to, and replicates where they do not divide
    it (sharding is a placement choice, never a correctness one). Dims
    past the end of ``roles`` replicate."""
    out = []
    for dim, role in zip(shape, roles):
        names = LOGICAL_RULES[role] if role else ()
        names = (names,) if isinstance(names, str) else tuple(names or ())
        names = tuple(n for n in names if axes.get(n, 1) > 1)
        size = math.prod(axes[n] for n in names)
        out.append(names if names and dim % size == 0 else None)
    return P(*out)


def per_shard(fn, mesh: Mesh | None, roles, *args):
    """``fn(*args)`` run once per device on that device's block of each
    operand. This is how the Pallas kernels meet a mesh: Mosaic calls
    cannot be partitioned by XLA, so every op that may lower to one goes
    through here — training, decode and serving alike. ``roles`` names
    each operand's dims (``LOGICAL_RULES`` keys; None = replicated); the
    result is laid out like ``args[0]``. With no axis left to partition —
    no mesh, one device, or a body already inside a ``shard_map`` over
    every axis (pipeline stages, the ring) — ``fn`` is called directly,
    so wrappers never nest over axes that are already manual."""
    axes = auto_axes(mesh)
    if math.prod(axes.values()) == 1:
        return fn(*args)
    specs = tuple(local_spec(a.shape, r, axes) for a, r in zip(args, roles))
    return jax.shard_map(  # tony: noqa[TONY-X001] — built while the caller's jitted step traces, not per dispatch
        fn, mesh=mesh, in_specs=specs, out_specs=specs[0],
        axis_names=frozenset(axes), check_vma=False,
    )(*args)


def shard_pytree(tree: Any, spec_tree: Any, mesh: Mesh) -> Any:
    """Device-put every leaf with the NamedSharding from a parallel tree of
    logical-axis tuples (None leaf = replicate)."""

    def place(x, axes):
        if axes is None:
            sh = NamedSharding(mesh, P())
        else:
            sh = logical_sharding(mesh, *axes)
        return jax.device_put(x, sh)

    return jax.tree.map(place, tree, spec_tree, is_leaf=lambda t: t is None)
