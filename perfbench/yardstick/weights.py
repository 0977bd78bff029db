"""Seeded weights: the benchmark's own generator, used by the model modules
(which hand the arrays to the program in its own tree) and by the plain
references (which make the same arrays again from the seed and take
nothing the program made). One key per (leaf, layer), so a reference can
make one layer at a time. Values are drawn in float32 and cast to the
dtype the configuration states; a reference upcasts that to float32 again.

What the leaves are is no business of this file: a model module
(``models/<model>.py``) states its architecture as a table, leaf name ->
``Leaf``, and every function here takes that table. A leaf's key follows
from its NAME alone (``leaf_id``), never from its place in a table, so a
leaf added to one model moves no bit of another."""

from __future__ import annotations

import functools
import hashlib
import json
from dataclasses import dataclass
from pathlib import Path

import jax
import jax.numpy as jnp

# The names that had an id before ids were hashed keep it, so that their
# bits stay what every earlier run drew. Closed: a new name is hashed.
PINNED_IDS = Path(__file__).resolve().parents[1] / "models" / "pinned_leaf_ids.json"


@dataclass(frozen=True)
class Leaf:
    """One row of a model's leaf table."""

    shape: tuple
    scale: float = 1.0             # N(0, 1) times this; not read for a norm
    norm: bool = False             # 1 + 0.1·N(0, 1), so that ignoring it shows
    layers: range | None = None    # the layers that carry it; None: a top leaf


@functools.cache
def _pinned() -> dict:
    with open(PINNED_IDS) as f:
        return json.load(f)


def leaf_id(name: str) -> int:
    """A fixed function of the name: the pinned id, else 31 bits of the
    name's SHA-256 (above every pinned id)."""
    if name in _pinned():
        return _pinned()[name]
    digest = hashlib.sha256(name.encode()).digest()
    return 2 ** 16 + int.from_bytes(digest[:4], "big") % (2 ** 31 - 2 ** 16)


def check_table(table: dict) -> dict:
    """A model module builds its table through this: two names that draw
    from one key are an error here, not a pair of equal matrices later."""
    seen: dict[int, str] = {}
    for name in table:
        other = seen.setdefault(leaf_id(name), name)
        if other != name:
            raise ValueError(f"leaves {other!r} and {name!r} share the id "
                             f"{leaf_id(name)}: rename one")
    return table


def seed_key(seed: int):
    # --seed may be a little over 2**31: split it so that neither half
    # overflows a 32-bit key word.
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def leaf(key, table: dict, name: str, layer, dtype) -> jax.Array:
    """One leaf of one layer (``layer`` may be traced; top leaves pass 0)."""
    row = table[name]
    k = jax.random.fold_in(jax.random.fold_in(key, leaf_id(name)), layer)
    x = jax.random.normal(k, row.shape, jnp.float32)
    x = 1.0 + 0.1 * x if row.norm else x * row.scale
    return x.astype(dtype)


def layer_tree(key, table: dict, layer, dtype, like: int | None = None) -> dict:
    """The leaves of one layer. ``layer`` may be traced, but which leaves a
    layer carries is static: ``like`` names a layer that carries the same
    ones (default: ``layer`` itself, then a plain int)."""
    static = layer if like is None else like
    return {n: leaf(key, table, n, layer, dtype) for n, row in table.items()
            if row.layers is not None and static in row.layers}


def top_tree(key, table: dict, dtype) -> dict:
    return {n: leaf(key, table, n, 0, dtype) for n, row in table.items()
            if row.layers is None}


def stacked_layers(key, table: dict, dtype) -> dict:
    """Every layer leaf stacked on a leading axis over the layers that
    carry it: [len(layers), ...]."""
    return {n: jax.vmap(lambda l, n=n: leaf(key, table, n, l, dtype))(
                jnp.arange(row.layers.start, row.layers.stop))
            for n, row in table.items() if row.layers is not None}
