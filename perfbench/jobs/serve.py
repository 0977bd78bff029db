"""Job kind ``serve``: the user script of a serving job, submitted through
the normal path (client -> coordinator -> executor -> this file). It makes
the weights on the device from the seed in the served dtype, builds the
program's ``ServingEngine`` behind its ``ServingServer``, publishes its
address atomically and serves until ``POST /shutdown``. The harness drives
it over HTTP. After shutdown it frees the engine and runs the plain
reference over the sample of served requests the harness left for it.

What the model is, the configuration's model module says
(``models/<model>.py``): the program's model configuration and the seeded
weights in the program's tree come from it, and this file reads no size
of the model.

Names of the program this file depends on: ``rt.initialize``,
``rt.build_job_mesh``, ``decode_weights`` (the fused serving layout),
``ServingEngine`` (constructor, ``start``, ``submit``, ``stats``,
``tokens_generated``, ``close``), ``ServingServer`` (``start``, ``wait_shutdown``,
``stop``), and the executor's ``TB_PORT`` and
``TONY_SERVING_PREFILL_CHUNK`` / ``TONY_SERVING_DECODE_WINDOW``."""

from __future__ import annotations

import gc
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from yardstick import spec  # noqa: E402
from yardstick.jobside import (CompileLog, Tracer, Work, device_report,  # noqa: E402
                           memory_peak_bytes, program_compile_s)
from _shared import load_reference  # noqa: E402


def main() -> int:
    work = Work(sys.argv[sys.argv.index("--params") + 1])
    work.stage("script_main")
    p = work.params
    cfg, run = p["config"], p["config"]["run"]
    seed = int(p["seed"])
    model = spec.load_model(cfg["model"])

    import jax
    import jax.numpy as jnp
    import numpy as np

    import tony_tpu.runtime as rt
    from tony_tpu.models import decode_weights
    from tony_tpu.serving import ServingEngine
    from tony_tpu.serving.http import ServingServer

    from yardstick import weights

    rt.initialize()
    compiles = CompileLog()
    mesh = rt.build_job_mesh()
    device = device_report()
    work.publish("device.json", device)
    work.stage("devices")
    print(f"bench serve job: mesh {dict(mesh.shape)} {rt.describe_devices()}",
          flush=True)

    tcfg = model.program_config(cfg, run, max_seq=int(run["max_seq"]),
                                dtype=run["weights_dtype"])
    # One jitted call from the seed, in the served dtype, straight into
    # the engine's fused layout: no float32 copy of the model ever exists.
    dtype = jnp.dtype(run["weights_dtype"])
    fused = jax.jit(lambda k: decode_weights(
        model.program_params(k, cfg, dtype), tcfg))(weights.seed_key(seed))
    jax.block_until_ready(fused)
    work.stage("weights_on_device")

    # The two knobs a deployment sets through its job conf come from the
    # executor's environment, as examples/lm_serve.py reads them: the
    # values the program ships are what is measured.
    chunk = int(os.environ.get("TONY_SERVING_PREFILL_CHUNK") or 32)
    window = int(os.environ.get("TONY_SERVING_DECODE_WINDOW") or 1)
    engine = ServingEngine(
        fused, tcfg, slots=int(run["slots"]), max_len=int(run["max_seq"]),
        prefill_chunk=chunk, decode_window=window,
        max_queue=int(run["max_queue"]), seed=seed & 0x7FFFFFFF,
        kv_quant="none",     # the configuration states a bfloat16 cache
    )
    del fused
    if p.get("fault"):   # test only: an answer altered where it is made
        inner_submit = engine.submit

        def faulty_submit(*a, **kw):
            req = inner_submit(*a, **kw)
            inner_result = req.result

            def result(timeout=None):
                out = inner_result(timeout)
                if p["fault"] == "alter_token" and len(out["tokens"]) > 1:
                    out["tokens"][1] = (out["tokens"][1] + 1) % cfg["vocab_size"]
                # ends a request early (the short warm-up ones it spares)
                if p["fault"] == "truncate_answer" and len(out["tokens"]) > \
                        p["traffic"]["warmup"]["max_new_tokens"]:
                    out["tokens"] = out["tokens"][:-1]
                    out["length"] = len(out["tokens"])
                return out

            req.result = result
            return req

        engine.submit = faulty_submit

    def generated() -> int:
        """The engine's own count of the tokens it has made so far."""
        n = engine.tokens_generated
        if p.get("fault") == "inflate_counter":
            n += n // 16 + 1     # test only: a counter adrift of the answers
        return n

    engine.start()
    server = ServingServer(engine, port=int(os.environ.get("TB_PORT") or 0))
    port = server.start()
    work.publish("addr.json", {"host": "127.0.0.1", "port": port})
    work.stage("server_listening")

    # Serve until /shutdown, sampling slot occupancy and the tokens
    # generated so far (read next to its time, before stats() waits for
    # the engine's condition), and minding the harness's request for a
    # trace.
    samples = []
    tracer, traced = None, False

    def sample() -> float:
        now, made = time.time(), generated()
        s = engine.stats()
        samples.append([now, s["active_slots"], s["queue_depth"],
                        s["prefilling"], made])
        return now

    try:
        while not server.wait_shutdown(timeout=0.05):
            now = sample()
            if p.get("trace") and not traced:
                want = work.read("trace_request.json")
                if want and tracer is None and now >= want["start"]:
                    tracer = Tracer(work.dir / "trace")
                    tracer.start()
                elif tracer is not None and now >= want["start"] + want["len_s"]:
                    tracer.stop()
                    tracer, traced = None, True
        sample()    # the shutdown comes after the window: its far edge
                    # lies between two samples whatever the drain took
    finally:
        if tracer is not None:
            tracer.stop()
        work.stage("shutdown_received")
        # No drain: the harness has already waited for what it wanted;
        # whatever is still queued is answered 503 and counted as cut.
        engine.close()
        server.stop()
    work.stage("engine_closed")
    result = {
        "device": device, "memory_peak_bytes": memory_peak_bytes(),
        "compile_s": program_compile_s(),
        "compile_times": compiles.times, "occupancy": samples,
        "slots": int(run["slots"]), "prefill_chunk": chunk,
        "decode_window": window, "control": p.get("control"),
        "engine_stats": engine.stats(), "tokens_generated": generated(),
    }
    work.publish("window.json", result)

    # Free what the job holds on the device, then the reference over the
    # sample. The engine is closed and nothing of it is used again, so
    # every array still alive goes, whatever name the engine keeps it under.
    del engine, server
    gc.collect()
    for array in jax.live_arrays():
        array.delete()
    check = work.read("check.json")
    if check:
        reference = load_reference(p["config_path"])
        t_ref = time.monotonic()
        n, width = len(check["requests"]), int(check["pad_to"])
        tokens = np.zeros((n, width), np.int32)
        lens_prompt = np.zeros(n, np.int32)
        lens_total = np.zeros(n, np.int32)
        for i, r in enumerate(check["requests"]):
            row = r["prompt"] + r["tokens"]
            tokens[i, :len(row)] = row
            lens_prompt[i], lens_total[i] = len(r["prompt"]), len(row)
        # With a control, the tokens judged are the ones the lower
        # precision puts first, in the served tokens' place: the run's own
        # comparison then holds them to the cell's own limit.
        result["numbers"] = reference.served_token_gaps(
            cfg, seed, tokens, lens_prompt, lens_total, dtype=str(dtype),
            lowp_control=p.get("control"))
        result["reference_s"] = time.monotonic() - t_ref
        work.stage("reference_done")
    work.publish("result.json", result)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
