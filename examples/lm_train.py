"""Flagship transformer LM training, submitted through tony_tpu — the
"switching from the reference" showcase: everything the orchestrator
injects (distributed identity, slice topology, data sharding, scratch
dirs) plus everything the compute plane provides (5-axis mesh, flash
attention, GQA, optional MoE, checkpoint/resume) in one user script.

The whole framework surface a training job needs:

    ctx  = rt.initialize()        # jax.distributed from the injected env
    mesh = rt.build_job_mesh()    # 5-axis mesh; dp spans slices on DCN
    reader = rt.sharded_reader([...], fmt="tokens")   # exactly-once shards
    init_fn, step_fn = make_train_step(cfg, mesh)     # jitted sharded step
    mgr = CheckpointManager(...)  # async, per-process-sharded, resumable

Submit locally (mini-cluster, CPU)::

    python -m tony_tpu.client.cli local \
        --executes examples/lm_train.py --framework jax \
        --conf tony.worker.instances=1 \
        --task_params "--steps 10 --d-model 64 --n-layers 2"

On a TPU fleet, add ``tony.gcp.project`` / ``gs://`` staging (see
docs/DEPLOY.md §3) and size the model/axes for the slice.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import tony_tpu.runtime as rt
from tony_tpu import observability
from tony_tpu.checkpoint import CheckpointManager
from tony_tpu.models import TransformerConfig, make_train_step
from tony_tpu.parallel.mesh import MeshSpec
from tony_tpu.parallel.plan import compile_cache_summary


def add_model_args(p: argparse.ArgumentParser) -> None:
    """Model flags shared verbatim with lm_generate.py — one definition
    so a checkpoint trained with defaults always restores with defaults
    (flag-default drift surfaces as opaque pytree mismatches)."""
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--n-kv-heads", type=int, default=2)
    p.add_argument("--n-experts", type=int, default=0)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--dtype", default="float32",
                   help="float32 on CPU, bfloat16 on TPU")


def parse_args(argv):
    p = argparse.ArgumentParser(description="tony_tpu flagship LM example")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--data", default="",
                   help="corpus path(s, comma-sep): *.jblk = block-"
                        "compressed jsonl containers with a 'tokens' "
                        "field per record; anything else = fixed-width "
                        "uint16 token records of length seq+1. Empty: "
                        "the synthetic motif corpus.")
    add_model_args(p)
    p.add_argument("--checkpoint-every", type=int, default=10)
    p.add_argument("--ckpt-dir", default="",
                   help="checkpoint dir or gs:// prefix (default: the "
                        "job's TONY_LOG_DIR scratch)")
    return p.parse_args(argv)


def model_config_from_args(args, *, max_seq: int) -> TransformerConfig:
    """The single source of the arg→config derivation: lm_generate.py
    imports this so a checkpoint written here always restores there —
    drift in head_dim/d_ff derivation would surface as opaque pytree
    mismatches at restore time."""
    return TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model,
        n_layers=args.n_layers, n_heads=args.n_heads,
        head_dim=max(8, args.d_model // args.n_heads),
        d_ff=args.d_model * 4, max_seq=max_seq,
        n_kv_heads=args.n_kv_heads, n_experts=args.n_experts,
        # Save matmul outputs, recompute the elementwise work between
        # them: with remat off the rolled layer scan stacks three fp32
        # [batch, seq, d_ff] SwiGLU intermediates per layer, and the
        # flagship widths at batch 8 x 2048 then need 16.2 GB of a v5e's
        # 15.75 (the TPU compiler's own count); this way they need 11.
        dtype=args.dtype, remat=True, remat_policy="dots",
    )


def learning_rate_for(cfg: TransformerConfig) -> float:
    """1e-2 suits the CPU-sized defaults; past d_model 64 the step scales
    with 1/width, as adamw needs: at the flagship's d_model 1024 a flat
    1e-2 drove the loss from 10.9 up to 15.6 on the chip before it fell,
    and left a model that emits one token."""
    return min(1e-2, 0.64 / cfg.d_model)


def synthetic_tokens(seed: int, n_docs: int, seq: int, vocab: int):
    """Deterministic corpus: repeated n-gram motifs per doc, so the LM has
    real structure to learn without any network egress."""
    rng = np.random.default_rng(seed)
    docs = []
    for _ in range(n_docs):
        motif = rng.integers(1, vocab, size=(8,))
        reps = -(-(seq + 1) // len(motif))
        noise = rng.integers(1, vocab, size=(seq + 1,))
        doc = np.tile(motif, reps)[: seq + 1]
        mask = rng.random(seq + 1) < 0.15
        doc = np.where(mask, noise, doc)
        docs.append(doc)
    return np.stack(docs).astype(np.int32)


def corpus_batches(args, ctx):
    """Endless [batch, seq+1] HOST token batches. With ``--data``,
    records stream through the framework data plane —
    ``rt.sharded_reader`` shards byte ranges exactly once across
    processes (its fetcher thread read-ahead overlaps decode with the
    running step), and the reader re-opens per epoch. Batches stay on
    the host deliberately: the train step's ``_to_global_batch`` owns
    device placement, and it is the only placement that is correct on
    BOTH single- and multi-process meshes (a pre-committed global array
    here would hit the documented multihost device_put trap). Without
    ``--data``, the synthetic motif corpus is sampled (the offline
    default)."""
    if not args.data:
        # The synthetic path honors `throttle_io` fault-plan entries the
        # same way the framework reader does (io/reader.py): the sleep
        # lands inside next(), where the step anatomy's wrap_batches
        # measures it as data_wait.
        from tony_tpu.resilience.faults import io_faults_from_env

        faults = io_faults_from_env()
        corpus = synthetic_tokens(0, n_docs=64, seq=args.seq,
                                  vocab=args.vocab)
        shard = corpus[ctx.process_id::max(ctx.num_processes, 1)]
        rng = np.random.default_rng(ctx.process_id)
        while True:
            idx = rng.integers(0, len(shard), size=(args.batch,))
            if faults is not None:
                faults.maybe_throttle()
            yield shard[idx]
        return
    paths = [p for p in args.data.split(",") if p]
    if not paths:
        raise ValueError("--data given but no paths parsed from it")
    jblk = [p.endswith(".jblk") for p in paths]
    if any(jblk) and not all(jblk):
        # A .jblk container fed to the fixed-width reader decodes
        # compressed bytes as token ids — garbage that trains without
        # erroring. Refuse the ambiguity.
        raise ValueError(
            f"--data mixes .jblk containers with raw token files: {paths}"
        )
    checked = False
    while True:  # one reader per epoch; splits re-shard identically
        yielded = 0
        if all(jblk):
            with rt.sharded_reader(
                paths, fmt="jsonl-blocks", batch_size=args.batch
            ) as r:
                for recs in r:
                    if not checked and recs:
                        # Validate the first record once, up front: a
                        # missing 'tokens' field or a ragged/wrong-width
                        # list would otherwise surface as an opaque numpy
                        # object-array or XLA shape error mid-training.
                        first = recs[0]
                        tokens = first.get("tokens") if isinstance(
                            first, dict) else None
                        if tokens is None or not hasattr(tokens, "__len__"):
                            raise ValueError(
                                f"--data {args.data}: records must carry a "
                                f"'tokens' list; first record has fields "
                                f"{sorted(first) if isinstance(first, dict) else type(first).__name__}"
                            )
                        if len(tokens) != args.seq + 1:
                            raise ValueError(
                                f"--data {args.data}: 'tokens' must be "
                                f"length seq+1 = {args.seq + 1} "
                                f"(targets are inputs shifted by one); "
                                f"first record has {len(tokens)}"
                            )
                        checked = True
                    if len(recs) == args.batch:
                        yielded += 1
                        yield np.asarray(
                            [rec["tokens"] for rec in recs], np.int32
                        )
        else:
            with rt.sharded_reader(
                paths, fmt="tokens", dtype=np.uint16,
                record_len=args.seq + 1, batch_size=args.batch,
            ) as r:
                for b in r:
                    if b.shape[0] == args.batch:
                        yielded += 1
                        yield b
        if not yielded:
            # This process's byte-range shard holds less than one full
            # batch: re-opening forever would hang training silently.
            raise RuntimeError(
                f"--data {args.data}: process {ctx.process_id}'s shard "
                f"yielded no full batch of {args.batch} (corpus too "
                f"small for this process count / batch size)"
            )


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    ctx = rt.initialize()
    mesh = rt.build_job_mesh()
    print(f"[{ctx.job_name}:{ctx.task_index}] process {ctx.process_id}/"
          f"{ctx.num_processes} slice {ctx.slice_index}/{ctx.num_slices} "
          f"mesh {dict(mesh.shape)} {rt.describe_devices()}", flush=True)

    cfg = model_config_from_args(args, max_seq=args.seq + 1)
    init_fn, step_fn = make_train_step(
        cfg, mesh, learning_rate=learning_rate_for(cfg)
    )

    # Per-process corpus shard via the framework's exactly-once sharding
    # identity (the py4j-reader analogue) — file-backed with --data,
    # synthetic otherwise. The step's anatomy recorder wraps the
    # iterator so host time blocked on input reads as the data_wait
    # phase (tony_step_phase_ms{phase="data_wait"}) even on the
    # synthetic path that never touches the tony_io_* telemetry.
    batches = corpus_batches(args, ctx)
    stats = getattr(step_fn, "stepstats", None)
    if stats is not None:
        batches = stats.wrap_batches(batches)

    scratch = os.environ.get("TONY_LOG_DIR", ".")
    # NOT wrapped in Path(): --ckpt-dir / TONY_CHECKPOINT_DIR may be a
    # gs:// prefix. TONY_CHECKPOINT_DIR is the coordinator-probed location
    # (tony.checkpoint.location) — using it keeps resume-step export and
    # progress-aware retry budgets working without per-script flags.
    ckpt_dir = (
        args.ckpt_dir
        or os.environ.get("TONY_CHECKPOINT_DIR")
        or os.path.join(scratch, "lm-checkpoints")
    )
    mgr = CheckpointManager(
        ckpt_dir,
        process_id=ctx.process_id, num_processes=ctx.num_processes,
    )
    with jax.sharding.set_mesh(mesh):
        state = init_fn(jax.random.key(0))
        # Checkpoint-aware restart: a retried session is told the newest
        # step the coordinator saw complete (TONY_RESUME_STEP);
        # restore_resumable pins every process to that SAME step, falling
        # back to newest-complete outside a retry.
        restored = mgr.restore_resumable(state)
        if restored is not None:
            state = restored
            print(f"resumed from step {int(state.step)}", flush=True)
        first = last = None
        if int(state.step) >= args.steps:
            # A retried session can resume a checkpoint already at the
            # target: that is success, not a crash.
            print(f"already at step {int(state.step)} >= {args.steps}; "
                  f"nothing to do", flush=True)
            return 0
        # `degrade_task` fault-plan entries make THIS process a
        # deterministic mid-training straggler (incarnation 0 only — a
        # replacement after a healing eviction runs clean).
        from tony_tpu.resilience.faults import step_faults_from_env

        step_faults = step_faults_from_env()
        # Host-side step mirror: the in-jit counter advances by exactly
        # one per dispatch, so tracking it here keeps the loop condition
        # and every consumer below off the device — the loss fence is
        # the step's ONE intended readback (TONY-X002 polices the rest).
        step = int(state.step)
        while step < args.steps:
            tokens = next(batches)
            t0 = time.perf_counter()
            state, metrics = step_fn(state, tokens)
            loss = float(jax.device_get(metrics["loss"]))  # tony: noqa[TONY-X002] — the step's intended readback fence
            step += 1
            if step_faults is not None:
                step_faults.maybe_degrade(step)
            # The float() above is the readback fence, so this wall time
            # covers the whole step. report() publishes the snapshot to
            # TONY_METRICS_FILE (when tony launched us), where the
            # executor piggybacks it on its heartbeat — live loss and
            # throughput on the coordinator's /metrics, no extra RPCs.
            dt = time.perf_counter() - t0
            first = loss if first is None else first
            last = loss
            report = {
                "step": step, "loss": loss,
                "tokens_per_sec": args.batch * args.seq / dt if dt else 0.0,
            }
            if stats is None or not stats.enabled \
                    or not stats.steps_observed:
                # With step anatomy active, stepstats owns step_time_ms
                # (the dispatch-to-dispatch wall its phases sum to —
                # two writers with two wall definitions would fight
                # over one gauge). Until it has actually published one
                # (it drops the compile interval, so nothing before the
                # 3rd dispatch), this fenced wall keeps the gauge fed —
                # a 2-step smoke job must still report step times.
                report["step_time_ms"] = dt * 1000.0
            observability.report(**report)
            if step % 5 == 0 or step == args.steps:
                print(f"step {step}: loss {loss:.4f}", flush=True)
            # Interval saves, plus the coordinator's live-migration /
            # evict-time flush order (TONY_CKPT_FLUSH_FILE, relayed by
            # the executor off its heartbeat reply): the coordinator is
            # waiting on this save's commit marker before tearing the
            # job down, so the relaunch resumes from THIS step instead
            # of one checkpoint interval back. flush_requested is
            # checked FIRST (not behind a short-circuit `or`): an
            # interval save at/past the target must also CONSUME the
            # order, or the next step would save a second time for
            # nothing.
            flushed = mgr.flush_requested(step)
            if flushed or step % args.checkpoint_every == 0:
                mgr.save(step, state)
        mgr.save(step, state, blocking=True)

    if not np.isfinite(last) or not last < first:
        print(f"loss did not descend: {first} -> {last}", file=sys.stderr)
        return 1
    print(f"done: loss {first:.4f} -> {last:.4f}", flush=True)
    print(compile_cache_summary(), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
