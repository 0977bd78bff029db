"""The serving programs of the benchmark's configurations as text, for a
described (not attached) v5e, debug locations stripped: what two checkouts
are compared by to show that a change left a program as it was (PR 32's
check; ``.claude/skills/verify/SKILL.md``).

    JAX_PLATFORMS=cpu python3 tools/serving_hlo.py <checkout> <out dir> [configuration ...]
    diff -r <out dir of the parent> <out dir of the change>

Per configuration and program (``decode_window``, ``prefill_chunks``; a
training configuration named on the command line: its ``train_step``) it
writes the StableHLO of ``lower()`` and the optimized HLO of ``compile()``,
and prints the TPU compiler's own memory count of the program.
Each Mosaic kernel is serialised from a copy without its debug info (a
kernel's bytecode holds its callers' paths and lines, so unstripped
bodies differ between any two directories). One process at a time: the TPU
library's lock. Nothing runs; no time comes from this."""

from __future__ import annotations

import inspect
import json
import os
import re
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
DEFAULT = ("mistral7b-serve-1chip", "mimo-v2-flash-serve-1chip")


def clean(text: str) -> str:
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"loc\([^)]*\)", "", text)
    return re.sub(r"(?s)(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r".*?\n\n", "", text)


def train_step(repo: Path, name: str, cfg: dict, model, device, on_chip,
               i32):
    """A training configuration's step, lowered at its cell's batch (the
    first workload of ``BENCHMARK.json`` that names the configuration)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from tony_tpu.models import make_train_step
    from tony_tpu.parallel.mesh import AXES

    bench = json.loads((repo / "BENCHMARK.json").read_text())
    traffic = next(w["traffic"] for w in bench["workloads"]
                   if w["config"] == name)
    tr = json.loads((repo / "perfbench" / "traffic"
                     / f"{traffic}.json").read_text())
    run = cfg["run"]
    mesh = Mesh(np.array([device]).reshape((1,) * len(AXES)), AXES)
    tcfg = model.program_config(cfg, run, max_seq=tr["seq"],
                                dtype=run["compute_dtype"], remat=True,
                                remat_policy=run["remat"])
    hp = run["optimizer"]
    init_fn, step_fn = make_train_step(
        tcfg, mesh, learning_rate=hp["learning_rate"],
        weight_decay=hp["weight_decay"], grad_clip=hp["grad_clip"])
    state = on_chip(jax.eval_shape(init_fn.__wrapped__, jax.random.key(0)))
    return step_fn.lower(state, i32(tr["batch"], tr["seq"] + 1))


def main() -> int:
    repo, out = Path(sys.argv[1]).resolve(), Path(sys.argv[2])
    names = sys.argv[3:] or DEFAULT
    sys.path.insert(0, str(repo / "perfbench"))
    sys.path.insert(0, str(repo))
    import jax
    import jax.numpy as jnp
    from jax._src import tpu_custom_call
    from jax._src.lib.mlir import ir
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    serialise = tpu_custom_call._lower_mosaic_module_to_asm

    def without_locations(module, **kw):
        with module.context, module.operation.location:
            bare = ir.Module.parse(
                module.operation.get_asm(enable_debug_info=False))
        return serialise(bare, **kw)

    tpu_custom_call._lower_mosaic_module_to_asm = without_locations
    import tony_tpu.ops as ops
    from tony_tpu.models import decode_weights
    from tony_tpu.serving import engine
    from yardstick import spec

    for module in ("attention", "norms", "hybrid"):
        if hasattr(ops, module):
            getattr(ops, module)._on_tpu = lambda mesh=None: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    def i32(*shape):
        return sds(shape, jnp.int32)

    def write(name, programs):
        for program, lowered in programs.items():
            (out / f"{name}.{program}.stablehlo.txt").write_text(
                clean(lowered.as_text()))
            compiled = lowered.compile()
            (out / f"{name}.{program}.hlo.txt").write_text(
                clean(compiled.as_text()))
            m = compiled.memory_analysis()
            print(name, program, "arguments %.3f GB, temporaries %.3f GB, "
                  "results %.3f GB of which aliased %.3f GB" % tuple(
                      n / 1e9 for n in (
                          m.argument_size_in_bytes, m.temp_size_in_bytes,
                          m.output_size_in_bytes, m.alias_size_in_bytes)),
                  flush=True)

    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    out.mkdir(parents=True, exist_ok=True)
    for name in names:
        cfg = json.loads((repo / "perfbench" / "configs"
                          / f"{name}.json").read_text())
        run = cfg["run"]
        model = spec.load_module(
            repo / "perfbench" / "models" / f"{cfg['model']}.py",
            "model_" + cfg["model"])
        if "optimizer" in run:
            write(name, {"train_step": train_step(repo, name, cfg, model,
                                                  topo.devices[0], on_chip,
                                                  i32)})
            continue
        tcfg = model.program_config(cfg, run, max_seq=run["max_seq"],
                                    dtype=run["weights_dtype"])
        chunk = int(cfg.get("conf", {}).get("tony.serving.prefill-chunk", 32))
        fused = on_chip(jax.eval_shape(lambda k: decode_weights(
            model.program_params(k, cfg, jnp.bfloat16), tcfg),
            jax.random.key(0)))
        slots, t_max = run["slots"], run["max_seq"]
        k, v = on_chip(jax.eval_shape(lambda: engine.init_slot_cache(
            tcfg, slots, t_max, prefill_chunk=chunk)))
        # as the scheduler calls it: since PR 44 with the window before
        # (``prev``), whose last tokens a lane is fed where the host has none
        prev = ((i32(slots, 1),) if "prev" in inspect.signature(
            engine.decode_window).parameters else ())
        programs = {
            "decode_window": engine.decode_window.lower(
                fused, k, v, i32(slots), i32(slots), i32(slots),
                sds((slots,), jnp.float32), key, i32(), *prev, cfg=tcfg,
                steps=1),
            "prefill_chunks": engine.prefill_chunks.lower(
                fused, k, v, i32(4, chunk), i32(4), i32(4), i32(4),
                sds((4,), jnp.float32), key, i32(), cfg=tcfg),
        }
        write(name, programs)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
