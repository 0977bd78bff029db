"""The decode kernel's key block, swept INSIDE the serving program.

A builder's tool for the chip: a serving configuration's full-size
``decode_window`` (seeded weights, the whole cache) is compiled once per
``DECODE_BLOCK_ROWS`` and timed over lanes whose positions are drawn from a
traffic mix as a steady state holds them (a request in flight is met with
a chance proportional to its answer's length), with the mix's share of
lanes parked. ``all`` puts every lane at the last position: what a kernel
that reads the whole reservation costs. A kernel timed alone misleads
(PERF.md section 7 (n)), so nothing here runs outside the program.

    python3 tools/sweep_decode_blocks.py --config mistral7b-serve-1chip \\
        --traffic chat-saturated --active 30,8 --rows 512,1024,2048,4096 \\
        [--out chiprun_out/sweep_decode/mistral.json]

Run on no CPU: a time from one is no device time."""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "perfbench"))


def live_positions(rng, traffic: dict, n: int):
    """``n`` positions of requests in flight under ``traffic``."""
    import numpy as np

    def draw(d, size):
        x = np.exp(rng.normal(np.log(d["median"]), d["sigma"], size))
        return np.clip(x, d["min"], d["max"]).astype(np.int64)

    prompts = draw(traffic["prompt_len"], 4096)
    outputs = draw(traffic["output_len"], 4096)
    met = rng.choice(4096, size=n, p=outputs / outputs.sum())
    return prompts[met] + (rng.random(n) * outputs[met]).astype(np.int64)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--active", required=True,
                    help="lanes decoding, one scenario each: 30,8")
    ap.add_argument("--rows", required=True)
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--out")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        raise SystemExit("sweep_decode_blocks: no TPU; a CPU gives no time")

    from yardstick import spec, weights

    from tony_tpu.models import decode_weights
    from tony_tpu.ops import attention
    from tony_tpu.serving import engine as eng

    cfg = json.loads((ROOT / f"perfbench/configs/{args.config}.json").read_text())
    traffic = json.loads(
        (ROOT / f"perfbench/traffic/{args.traffic}.json").read_text())
    run = cfg["run"]
    model = spec.load_model(cfg["model"])
    tcfg = model.program_config(cfg, run, max_seq=int(run["max_seq"]),
                                dtype=run["weights_dtype"])
    dtype = jnp.dtype(run["weights_dtype"])
    fused = jax.jit(lambda k: decode_weights(  # tony: noqa[TONY-X001] — one-shot weights from the seed, not a step path
        model.program_params(k, cfg, dtype), tcfg))(weights.seed_key(args.seed))
    n_s, t_max = int(run["slots"]), int(run["max_seq"])
    chunk = int(cfg.get("conf", {}).get("tony.serving.prefill-chunk", 32))
    k_all, v_all = eng.init_slot_cache(tcfg, n_s, t_max, prefill_chunk=chunk)
    jax.block_until_ready((fused, k_all, v_all))

    rng = np.random.default_rng(args.seed)
    scenarios = {"all": (np.full(n_s, t_max - 2, np.int32),
                         np.ones(n_s, bool))}
    for n in (int(a) for a in args.active.split(",")):
        pos = np.zeros(n_s, np.int32)
        active = np.zeros(n_s, bool)
        active[rng.choice(n_s, size=n, replace=False)] = True
        pos[active] = np.minimum(live_positions(rng, traffic, n), t_max - 2)
        # a free lane keeps its last tenant's position
        pos[~active] = np.minimum(
            live_positions(rng, traffic, n_s - n), t_max - 2)
        scenarios[f"active{n}"] = (pos, active)

    tokens = np.zeros(n_s, np.int32)
    temp = np.zeros(n_s, np.float32)
    key = jax.random.key(0)
    lines = []
    for rows in (int(r) for r in args.rows.split(",")):
        attention.DECODE_BLOCK_ROWS = rows
        jax.clear_caches()
        line = {"config": args.config, "block_rows": rows,
                "block_positions": eng.decode_read_block(k_all)}
        for name, (pos, active) in scenarios.items():
            wpos = np.where(active, pos, t_max - 1).astype(np.int32)
            line[f"{name}.read_share"] = eng.decode_read_positions(
                pos, ~active, t_max, line["block_positions"]) / (n_s * t_max)

            def call():
                nonlocal k_all, v_all
                k_all, v_all, toks, _ = eng.decode_window(
                    fused, k_all, v_all, pos, wpos, tokens, temp, key,
                    np.int32(0), cfg=tcfg, steps=1)
                return toks

            jax.block_until_ready(call())
            jax.block_until_ready(call())
            t0 = time.perf_counter()
            for _ in range(args.calls):
                toks = call()
            jax.block_until_ready(toks)
            line[f"{name}.ms"] = 1000 * (time.perf_counter() - t0) / args.calls
        print(json.dumps(line), flush=True)
        lines.append(line)
    if args.out:
        out = Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(lines, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
