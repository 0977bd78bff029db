"""Flagship transformer LM serving/generation, submitted through
tony_tpu — the inference half of the lm_train showcase. Restores a
checkpoint written by ``lm_train.py`` (local dir or ``gs://`` prefix),
builds a persistent ``DecodeSession`` (weights fuse once; every
``generate`` call reuses the compiled loop), and decodes continuations
for a batch of prompts with greedy or temperature sampling.

Submit locally (mini-cluster, CPU)::

    python -m tony_tpu.client.cli local \
        --executes examples/lm_generate.py --framework jax \
        --conf tony.worker.instances=1 \
        --task_params "--max-new 16 --d-model 64 --n-layers 2"

Point ``--ckpt`` at a training job's checkpoint dir to serve trained
weights (the model flags must match the training config); without it the
example smoke-runs on fresh weights. On TPU pass ``--dtype bfloat16``.
"""
from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np

import tony_tpu.runtime as rt
from tony_tpu.checkpoint import CheckpointManager
from tony_tpu.models import DecodeSession, init_params
from tony_tpu.parallel.plan import compile_cache_summary


def parse_args(argv):
    p = argparse.ArgumentParser(description="tony_tpu LM generation example")
    p.add_argument("--ckpt", default="",
                   help="checkpoint dir/gs:// prefix from lm_train.py "
                        "(empty: fresh weights smoke run)")
    p.add_argument("--prompt", default="1,5,9,2",
                   help="comma-separated token ids; ':' separates batch "
                        "rows (shell-safe — task params pass through "
                        "bash -c)")
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=1.0)
    p.add_argument("--eos", type=int, default=-1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--max-seq", type=int, default=256)
    # Model flags shared with lm_train.py (same names, same defaults) —
    # they must match the checkpoint's training config.
    from lm_train import add_model_args

    add_model_args(p)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    ctx = rt.initialize()
    # Shared derivation: a checkpoint written by lm_train.py restores
    # here only if the arg→config mapping is byte-identical.
    from lm_train import learning_rate_for, model_config_from_args

    cfg = model_config_from_args(args, max_seq=args.max_seq)
    mesh = rt.build_job_mesh()
    print(f"[{ctx.job_name}:{ctx.task_index}] generating on mesh "
          f"{dict(mesh.shape)} {rt.describe_devices()}", flush=True)
    if not args.ckpt:
        params = init_params(jax.random.key(args.seed), cfg)
    else:
        # lm_train checkpoints the full TrainState (params + optimizer
        # state), so the restore template must have that structure — the
        # serving job keeps only .params. NOT wrapped in Path(): gs://
        # URIs must survive verbatim. The restore is topology-portable:
        # a checkpoint written on MORE (or fewer) processes than this
        # serving job reassembles from all shard files and re-shards.
        from tony_tpu.models import make_train_step

        init_fn, _ = make_train_step(
            cfg, mesh, learning_rate=learning_rate_for(cfg)
        )
        mgr = CheckpointManager(
            args.ckpt, process_id=ctx.process_id,
            num_processes=ctx.num_processes,
        )
        with jax.sharding.set_mesh(mesh):
            template = init_fn(jax.random.key(0))
            restored = mgr.restore(template)
        if restored is None:
            print(f"no complete checkpoint under {args.ckpt}",
                  file=sys.stderr)
            return 2
        params = restored.params
        print(f"restored step {int(restored.step)} from {args.ckpt}",
              flush=True)

    rows = [
        [int(t) for t in row.split(",") if t.strip()]
        for row in args.prompt.split(":")
    ]
    # Serve sharded in place when the job mesh is bigger than one device
    # (fused weights megatron-split over tp, KV cache sharded); a 1-device
    # mesh serves exactly like the plain session.
    session = DecodeSession(
        params, cfg, mesh=mesh if mesh.devices.size > 1 else None
    )
    # Ragged prompts decode in groups of equal length (one compiled loop
    # per length) — never padded, so every row comes out exactly as it
    # would alone, which is what a serving engine is compared against.
    out_rows: dict[int, np.ndarray] = {}
    for width in sorted({len(r) for r in rows}):
        idx = [i for i, r in enumerate(rows) if len(r) == width]
        out = session.generate(
            jnp.asarray([rows[i] for i in idx], jnp.int32),
            max_new_tokens=args.max_new,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p,
            eos_token=None if args.eos < 0 else args.eos,
            key=(jax.random.key(args.seed)
                 if args.temperature > 0 else None),
        )
        if ctx.num_processes > 1:
            # Multi-process job: `out` is a global array whose shards
            # live on other hosts too — fetching it directly raises.
            # Gather the full value onto every host first.
            from jax.experimental import multihost_utils

            out = multihost_utils.process_allgather(out, tiled=True)
        out_rows.update(zip(idx, np.asarray(out)))
    for i in range(len(rows)):
        print(f"generated[{i}]: "
              f"{','.join(str(int(t)) for t in out_rows[i])}", flush=True)
    print(compile_cache_summary(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
