"""The builder's reading of a serving configuration's LONGEST requests on
the chip, for a cell whose own check cannot reach them: in
``minicpm-sala.serve.longdoc-saturated`` the requests that end inside the
window are 13-16 k positions long (PERF.md section 7 (t)), so the sparse
layers' selection past 20 k positions is compared with nothing there.

The configuration's engine, built as ``perfbench/jobs/serve.py`` builds it
(its ``run``, its ``conf``'s prefill chunk, the weights from ``--seed``),
serves prompts of the given lengths, ids drawn from the seed; the engine
goes, and the configuration's plain reference judges the served tokens as
the harness does (``served_token_gaps``) against the configuration's own
limits. ``--controls`` then puts each of the reference's controls in the
program's place on the same rows. One line of JSON a reading. On no CPU:
the engine and the reference are full size.

    python3 tools/sala_long_check.py --seed 2147489101 \\
        --prompts 21000,27500 --new 64 --controls dense,fp8,no_decay
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "perfbench"))


def serve(cfg: dict, model, seed: int, prompts: list, new: int) -> list:
    """The prompts through the configuration's engine; their answers."""
    import jax
    import jax.numpy as jnp

    from tony_tpu.models import decode_weights
    from tony_tpu.serving import ServingEngine
    from yardstick import weights

    run = cfg["run"]
    tcfg = model.program_config(cfg, run, max_seq=int(run["max_seq"]),
                                dtype=run["weights_dtype"])
    dtype = jnp.dtype(run["weights_dtype"])
    fused = jax.jit(lambda k: decode_weights(  # tony: noqa[TONY-X001] — one-shot weights from the seed, not a step path
        model.program_params(k, cfg, dtype), tcfg))(weights.seed_key(seed))
    engine = ServingEngine(
        fused, tcfg, slots=int(run["slots"]), max_len=int(run["max_seq"]),
        prefill_chunk=int(cfg["conf"]["tony.serving.prefill-chunk"]),
        max_queue=int(run["max_queue"]), seed=seed & 0x7FFFFFFF,
        kv_quant="none")
    del fused
    requests = [engine.submit(p, new) for p in prompts]
    while not all(r.done() for r in requests):
        engine.step()
    answers = [list(r.result()["tokens"]) for r in requests]
    stats = engine.stats()
    print(json.dumps({"served": [len(a) for a in answers],
                      "sparse": stats.get("sparse"),
                      "state": stats.get("state")}), flush=True)
    # the reference needs the memory: every array still alive goes
    del engine, requests
    gc.collect()
    for array in jax.live_arrays():
        array.delete()
    return answers


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="minicpm-sala-serve-1chip",
                    help="a configuration of perfbench/configs, or a path")
    ap.add_argument("--reference", help="default: beside the configuration")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompts", default="21000,27500")
    ap.add_argument("--new", type=int, default=64)
    ap.add_argument("--controls", default="")
    args = ap.parse_args()

    import numpy as np

    from yardstick import spec

    path = Path(args.config)
    if path.suffix != ".json":
        path = REPO / "perfbench" / "configs" / f"{args.config}.json"
    cfg = json.loads(path.read_text())
    model = spec.load_model(cfg["model"])
    reference = spec.load_module(
        args.reference or path.with_suffix("").as_posix() + ".reference.py",
        "reference")
    lengths = [int(n) for n in args.prompts.split(",")]
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg["vocab_size"], size=n).astype(np.int32)
               for n in lengths]
    answers = serve(cfg, model, args.seed, prompts, args.new)

    tokens = np.zeros((len(prompts), max(lengths) + args.new), np.int32)
    for i, (p, a) in enumerate(zip(prompts, answers)):
        tokens[i, :p.size + len(a)] = np.concatenate([p, a])
    totals = [p.size + len(a) for p, a in zip(prompts, answers)]
    limits = {k: v for k, v in cfg["correct"]["limits"].items()
              if k.endswith("_gap")}
    for control in [None] + [c for c in args.controls.split(",") if c]:
        got = reference.served_token_gaps(
            cfg, args.seed, tokens, lengths, totals,
            dtype=cfg["run"]["weights_dtype"], lowp_control=control)
        print(json.dumps({
            "config": args.config, "seed": args.seed, "prompts": lengths,
            "control": control, **got, "limits": limits,
            "correct": all(got[k] <= v for k, v in limits.items())}),
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
