"""Long-lived LM serving task — the ``serving`` task type's user script.

Restores a checkpoint written by ``lm_train.py`` (local dir or ``gs://``
prefix), fuses it once through ``DecodeSession`` (so the persistent
compile cache recognizes the program on restart), and serves generate
requests over HTTP through the continuous-batching engine
(``tony_tpu.serving``): iteration-level scheduling over a fixed slot
batch, chunked prefill, EOS retirement, slot reuse. Engine knobs default
from the ``TONY_SERVING_*`` env the executor exports from
``tony.serving.*`` conf.

Submit locally (mini-cluster, CPU)::

    python -m tony_tpu.client.cli local \
        --executes examples/lm_serve.py --framework jax \
        --conf tony.serving.instances=1 --conf tony.worker.instances=0 \
        --conf tony.chief.name=serving \
        --task_params "--d-model 64 --n-layers 2 --max-requests 100"

With ``tony.chief.name=serving`` the executor reserves a port, exports
it as ``TB_PORT``, and registers ``http://host:port`` with the
coordinator — so the engine's endpoint is discoverable exactly like a
notebook's, and ``ProxyServer`` (or ``tony notebook``'s tunnel) fronts
it. Clients then::

    POST /generate  {"prompt": [1,5,9], "max_new_tokens": 32,
                     "temperature": 0.0, "eos_id": 2}
    GET  /healthz   -> engine stats
    POST /shutdown  -> drain and exit 0 (job SUCCEEDs)

Serving telemetry (tony_serving_*) publishes through the observability
registry onto $TONY_METRICS_FILE, rides executor heartbeats, and shows
up on the coordinator's /metrics for the health detectors.
"""
from __future__ import annotations

import argparse
import os
import sys

import jax

import tony_tpu.runtime as rt
from tony_tpu import constants
from tony_tpu.checkpoint import CheckpointManager
from tony_tpu.models import DecodeSession, init_params
from tony_tpu.parallel.plan import compile_cache_summary
from tony_tpu.serving import ServingEngine
from tony_tpu.serving.http import ServingServer


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def parse_args(argv):
    p = argparse.ArgumentParser(description="tony_tpu LM serving example")
    p.add_argument("--ckpt", default="",
                   help="checkpoint dir/gs:// prefix from lm_train.py "
                        "(empty: fresh weights smoke run)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--max-seq", type=int, default=512)
    p.add_argument("--slots", type=int,
                   default=_env_int(constants.TONY_SERVING_SLOTS, 8))
    p.add_argument("--prefill-chunk", type=int,
                   default=_env_int(constants.TONY_SERVING_PREFILL_CHUNK, 32))
    p.add_argument("--decode-window", type=int,
                   default=_env_int(constants.TONY_SERVING_DECODE_WINDOW, 1))
    p.add_argument("--max-queue", type=int,
                   default=_env_int(constants.TONY_SERVING_MAX_QUEUE, 1024))
    p.add_argument("--port", type=int, default=-1,
                   help="HTTP port; -1 = $TB_PORT (chief-registered URL) "
                        "else $TONY_SERVING_PORT else ephemeral")
    p.add_argument("--addr-file", default="",
                   help="write host:port here once listening (empty: "
                        "$TONY_LOG_DIR/serving-<job>-<idx>.addr when "
                        "tony-launched)")
    p.add_argument("--max-requests", type=int, default=0,
                   help="exit 0 after this many retired requests "
                        "(0 = serve until /shutdown)")
    p.add_argument("--models", action="append", default=[],
                   help="extra resident checkpoint as name=ckpt_dir "
                        "(repeatable); requests route by their 'model' "
                        "field, swapped compile-free at idle batch "
                        "boundaries (DecodeSession identity layout)")
    p.add_argument("--max-resident-models", type=int, default=4,
                   help="LRU bound on host-resident model packs")
    p.add_argument("--role", choices=("both", "prefill", "decode"),
                   default="both",
                   help="disaggregated fleet role advertised on "
                        "/healthz (the router enforces it; the engine "
                        "itself can always do both)")
    # Model flags shared with lm_train.py (same names, same defaults) —
    # they must match the checkpoint's training config.
    from lm_train import add_model_args

    add_model_args(p)
    return p.parse_args(argv)


def _resolve_port(args) -> int:
    if args.port >= 0:
        return args.port
    tb = os.environ.get(constants.TB_PORT)
    if tb:
        return int(tb)
    return _env_int(constants.TONY_SERVING_PORT, 0)


def _addr_file(args) -> str:
    if args.addr_file:
        return args.addr_file
    log_dir = os.environ.get(constants.TONY_LOG_DIR)
    if not log_dir:
        return ""
    job = os.environ.get(constants.JOB_NAME, "serving")
    idx = os.environ.get(constants.TASK_INDEX, "0")
    return os.path.join(log_dir, f"serving-{job}-{idx}.addr")


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    ctx = rt.initialize()
    from lm_train import learning_rate_for, model_config_from_args

    cfg = model_config_from_args(args, max_seq=args.max_seq)
    mesh = rt.build_job_mesh()
    print(f"[{ctx.job_name}:{ctx.task_index}] serving on mesh "
          f"{dict(mesh.shape)} {rt.describe_devices()}", flush=True)
    if not args.ckpt:
        params = init_params(jax.random.key(args.seed), cfg)
    else:
        # Same restore contract as lm_generate.py: the training job
        # checkpoints the full TrainState; serving keeps only .params.
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

    # Fuse once through DecodeSession (compile-cache-keyed like every
    # other Plan-instrumented program), then hand the fused pack to the
    # engine — a serving restart on a warm persistent cache skips the
    # fusion AND both engine executables' XLA compiles.
    session = DecodeSession(params, cfg)
    engine = ServingEngine(
        session.params, cfg, slots=args.slots,
        prefill_chunk=args.prefill_chunk,
        decode_window=args.decode_window, max_queue=args.max_queue,
        seed=args.seed, max_resident_models=args.max_resident_models,
    )
    # Multiplexed checkpoints: every --models name=ckpt registers a lazy
    # loader — restore happens off the engine loop on first routed
    # request, and the swap itself is compile-free because every pack
    # shares the DecodeSession identity layout.
    for entry in args.models:
        mname, _, mdir = entry.partition("=")
        if not mname or not mdir:
            print(f"bad --models entry {entry!r} (want name=ckpt_dir)",
                  file=sys.stderr)
            return 2

        def _load(ckpt_dir=mdir):
            from tony_tpu.models import make_train_step

            m_init, _ = make_train_step(
                cfg, mesh, learning_rate=learning_rate_for(cfg)
            )
            m_mgr = CheckpointManager(
                ckpt_dir, process_id=ctx.process_id,
                num_processes=ctx.num_processes,
            )
            with jax.sharding.set_mesh(mesh):
                m_restored = m_mgr.restore(m_init(jax.random.key(0)))
            if m_restored is None:
                raise FileNotFoundError(
                    f"no complete checkpoint under {ckpt_dir}")
            return m_restored.params

        engine.add_model(mname, loader=_load)
    engine.start()
    server = ServingServer(engine, port=_resolve_port(args),
                           extra_health={"role": args.role})
    port = server.start()
    addr_file = _addr_file(args)
    if addr_file:
        # Atomic publish: a poller must never read a torn half-line.
        tmp = f"{addr_file}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(f"127.0.0.1:{port}\n")
        os.replace(tmp, addr_file)
    print(f"serving on :{port} (slots={args.slots}, "
          f"chunk={args.prefill_chunk})", flush=True)
    try:
        while not server.wait_shutdown(timeout=0.2):
            if (args.max_requests
                    and engine.stats()["retired"] >= args.max_requests):
                break
    finally:
        # Graceful: stop admitting, let in-flight streams retire (a
        # client mid-long-poll gets its completed generation, not an
        # error), THEN tear down.
        engine.drain(timeout=60.0)
        server.stop()
        engine.close()
    print(f"serving done: {engine.stats()}", flush=True)
    print(compile_cache_summary(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
