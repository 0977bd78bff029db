"""What both job scripts share: the benchmark's seeded weights laid out in
the program's parameter tree, and the loader of a configuration's plain
reference. Not a job kind (a job kind is ``<kind>.py`` without the
underscore)."""

from __future__ import annotations

import importlib.util
from pathlib import Path

PROGRAM_LAYER_NAMES = {
    "ln1": "input_norm", "wq": "q_proj", "wk": "k_proj", "wv": "v_proj",
    "wo": "o_proj", "ln2": "post_norm", "w_gate": "gate_proj",
    "w_up": "up_proj", "w_down": "down_proj",
}
PROGRAM_TOP_NAMES = {"embed": "embed", "final_norm": "final_norm",
                     "unembed": "lm_head"}


def program_params(key, cfg, dtype):
    """The benchmark's seeded weights in the program's parameter tree."""
    from yardstick import weights

    layers = weights.stacked_layers(key, cfg, dtype)
    top = weights.top_tree(key, cfg, dtype)
    tree = {prog: top[ours] for prog, ours in PROGRAM_TOP_NAMES.items()}
    tree["layers"] = {prog: layers[ours]
                      for prog, ours in PROGRAM_LAYER_NAMES.items()}
    return tree


def leaf_norms(tree) -> dict:
    """||leaf|| under the benchmark's leaf names, from a program tree."""
    import jax.numpy as jnp

    out = {ours: tree[prog] for prog, ours in PROGRAM_TOP_NAMES.items()}
    out.update({ours: tree["layers"][prog]
                for prog, ours in PROGRAM_LAYER_NAMES.items()})
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in out.items()}


def load_reference(config_path: str):
    path = Path(config_path).with_suffix("").as_posix() + ".reference.py"
    spec = importlib.util.spec_from_file_location("perfbench_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
