"""Seeded weights: the benchmark's own generator, used by the job scripts
(which hand the arrays to the program) and by the plain references (which
make the same arrays again from the seed and take nothing the program
made). One key per (leaf, layer), so a reference can make one layer at a
time. Values are drawn in float32 and cast to the dtype the configuration
states; a reference upcasts that to float32 again.

The benchmark's layout of a decoder layer (Mistral's names):
input_norm [d], q_proj [d, H, Dh], k_proj / v_proj [d, Hkv, Dh],
o_proj [H, Dh, d], post_norm [d], gate_proj / up_proj [d, F],
down_proj [F, d]; and embed [V, d], final_norm [d], lm_head [d, V]."""

from __future__ import annotations

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("input_norm", "q_proj", "k_proj", "v_proj", "o_proj",
                "post_norm", "gate_proj", "up_proj", "down_proj")
TOP_LEAVES = ("embed", "final_norm", "lm_head")
_LEAF_ID = {n: i for i, n in enumerate(LAYER_LEAVES + TOP_LEAVES)}


def leaf_shape(cfg: dict, name: str) -> tuple:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, v = cfg["head_dim"], cfg["vocab_size"]
    return {
        "input_norm": (d,), "post_norm": (d,), "final_norm": (d,),
        "q_proj": (d, h, dh), "k_proj": (d, hkv, dh), "v_proj": (d, hkv, dh),
        "o_proj": (h, dh, d), "gate_proj": (d, f), "up_proj": (d, f),
        "down_proj": (f, d), "embed": (v, d), "lm_head": (d, v),
    }[name]


def _scale(cfg: dict, name: str) -> float:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    if name == "embed":
        return 1.0
    if name == "down_proj":
        return f ** -0.5
    if name == "o_proj":
        return (cfg["num_attention_heads"] * cfg["head_dim"]) ** -0.5
    return d ** -0.5


def seed_key(seed: int):
    # --seed may be a little over 2**31: split it so that neither half
    # overflows a 32-bit key word.
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def leaf(key, cfg: dict, name: str, layer, dtype) -> jax.Array:
    """One leaf of one layer (``layer`` may be traced; top leaves pass 0).
    Norm weights are 1 + 0.1·N(0,1) so that ignoring them shows."""
    k = jax.random.fold_in(jax.random.fold_in(key, _LEAF_ID[name]), layer)
    x = jax.random.normal(k, leaf_shape(cfg, name), jnp.float32)
    if name.endswith("norm"):
        x = 1.0 + 0.1 * x
    else:
        x = x * _scale(cfg, name)
    return x.astype(dtype)


def layer_tree(key, cfg: dict, layer, dtype) -> dict:
    return {n: leaf(key, cfg, n, layer, dtype) for n in LAYER_LEAVES}


def top_tree(key, cfg: dict, dtype) -> dict:
    return {n: leaf(key, cfg, n, 0, dtype) for n in TOP_LEAVES}


def stacked_layers(key, cfg: dict, dtype) -> dict:
    """Every layer's leaves stacked on a leading axis [L, ...]."""
    layers = jnp.arange(cfg["num_hidden_layers"])
    return {n: jax.vmap(lambda l, n=n: leaf(key, cfg, n, l, dtype))(layers)
            for n in LAYER_LEAVES}
