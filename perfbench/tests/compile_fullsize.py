"""Rehearsal 3 of the on-chip-measurement guide: compile each cell's
programs at FULL size for a described (not attached) v5e chip and print the
TPU compiler's own memory count. Nothing runs; no time comes from this.

    JAX_PLATFORMS=cpu python3 perfbench/tests/compile_fullsize.py train|serve|train-ref|serve-ref [layers]
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT.parent))
sys.path.insert(0, str(ROOT / "jobs"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from yardstick import spec  # noqa: E402

GB = 1e9


def report(name, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: args {m.argument_size_in_bytes / GB:.2f} GB, out "
          f"{m.output_size_in_bytes / GB:.2f}, temp "
          f"{m.temp_size_in_bytes / GB:.2f}, alias "
          f"{m.alias_size_in_bytes / GB:.2f} -> {total / GB:.2f} GB",
          flush=True)


def main():
    what = sys.argv[1]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = topo.devices[0]
    one = SingleDeviceSharding(dev)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    key_sds = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    if what in ("train", "train-ref"):
        cfg = json.loads((ROOT / "configs/mistral7b-train-1chip.json").read_text())
        if len(sys.argv) > 2:
            cfg["num_hidden_layers"] = int(sys.argv[2])
        tr = json.loads((ROOT / "traffic/b4x2048.json").read_text())
        batch, seq = tr["batch"], tr["seq"]
    else:
        cfg = json.loads((ROOT / "configs/mistral7b-serve-1chip.json").read_text())
        if len(sys.argv) > 2:
            cfg["num_hidden_layers"] = int(sys.argv[2])
    run = cfg["run"]
    model = spec.load_model(cfg["model"])

    if what == "train":
        from jax.sharding import Mesh
        import numpy as np
        from tony_tpu.models import make_train_step
        from tony_tpu.parallel.mesh import AXES

        mesh = Mesh(np.array([dev]).reshape((1,) * len(AXES)), AXES)
        tcfg = model.program_config(cfg, run, max_seq=seq,
                                    dtype=run["compute_dtype"], remat=True,
                                    remat_policy=run["remat"])
        hp = run["optimizer"]
        init_fn, step_fn = make_train_step(
            tcfg, mesh, learning_rate=hp["learning_rate"],
            weight_decay=hp["weight_decay"], grad_clip=hp["grad_clip"])
        state = jax.eval_shape(init_fn.__wrapped__, jax.random.key(0))
        state = on_chip(state)
        tokens = sds((batch, seq + 1), jnp.int32)
        report("train step", step_fn.lower(state, tokens).compile())
        gen = jax.jit(lambda k: model.program_params(k, cfg, jnp.float32))
        report("seeded weights", gen.lower(key_sds).compile())
        change = jax.jit(lambda p, k: model.leaf_norms(jax.tree.map(
            lambda a, b: a - b, p,
            model.program_params(k, cfg, jnp.float32))))
        report("change norms", change.lower(state.params, key_sds).compile())
    elif what == "train-ref":
        ref = spec.load_module(
            ROOT / "configs/mistral7b-train-1chip.reference.py", "ref")
        ck = ref.model_key(cfg)
        params = on_chip(jax.eval_shape(
            lambda k: ref.init_params.__wrapped__(k, ck), jax.random.key(0)))
        hpk = tuple(sorted(run["optimizer"].items()))
        for lowp in ("float32", "fp8"):
            with jax.default_matmul_precision("highest"):
                c = ref.adamw_step.lower(
                    params, params, params, sds((), jnp.float32),
                    sds((batch, seq + 1), jnp.int32), ck, lowp, hpk).compile()
            report(f"reference adamw step ({lowp})", c)
        report("reference change norms",
               ref.change_norms.lower(params, key_sds, ck).compile())
    elif what == "serve":
        from tony_tpu.models import decode_weights
        from tony_tpu.serving import engine as eng

        tcfg = model.program_config(cfg, run, max_seq=run["max_seq"],
                                    dtype=run["weights_dtype"])
        gen = jax.jit(lambda k: decode_weights(
            model.program_params(k, cfg, jnp.bfloat16), tcfg))
        report("seeded fused weights", gen.lower(key_sds).compile())
        fused = on_chip(jax.eval_shape(gen, jax.random.key(0)))
        S, T = run["slots"], run["max_seq"]
        kv = sds((tcfg.n_layers, S, T, tcfg.kv_heads, tcfg.head_dim),
                 jnp.bfloat16)
        i32 = lambda *s: sds(s, jnp.int32)  # noqa: E731
        report("decode_window", eng.decode_window.lower(
            fused, kv, kv, i32(S), i32(S), i32(S), sds((S,), jnp.float32),
            key_sds, i32(), cfg=tcfg, steps=1).compile())
        report("prefill_chunks", eng.prefill_chunks.lower(
            fused, kv, kv, i32(4, 32), i32(4), i32(4), i32(4),
            sds((4,), jnp.float32), key_sds, i32(), cfg=tcfg).compile())
    elif what == "serve-ref":
        ref = spec.load_module(
            ROOT / "configs/mistral7b-serve-1chip.reference.py", "ref")
        ck = ref.model_key(cfg)
        x = sds((4, 1408, cfg["hidden_size"]), jnp.float32)
        for lowp in ("float32", "fp8"):
            with jax.default_matmul_precision("highest"):
                report(f"reference layer ({lowp})", ref._layer_step.lower(
                    x, key_sds, sds((), jnp.int32), ck, "bfloat16",
                    lowp).compile())
                report(f"reference head ({lowp})", ref._head.lower(
                    x, key_sds, ck, "bfloat16", lowp).compile())


if __name__ == "__main__":
    main()
