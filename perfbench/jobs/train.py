"""Job kind ``train``: the user script of a training job, submitted
through the normal path (client -> coordinator -> executor -> this file).
It builds ONE object — ``make_train_step``'s compiled step with its state
— drives it from the seed through its first steps on the reader's own
batches, and hands that same object to the timed window. Everything it
reads comes from the parameters file the harness wrote; everything it
reports goes to the run's work directory.

What the model is, the configuration's model module says
(``models/<model>.py``): the program's model configuration, the seeded
weights in the program's tree and the leaves' norms under the benchmark's
names come from it, and this file reads no size of the model.

Names of the program this file depends on: ``rt.initialize``,
``rt.build_job_mesh``, ``rt.sharded_reader(fmt="tokens")``,
``make_train_step`` (its ``TrainState`` with ``params`` / ``opt_state``,
the adam state's ``mu``), ``MeshSpec``."""

from __future__ import annotations

import gc
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from _shared import load_reference  # noqa: E402
from yardstick import spec  # noqa: E402
from yardstick.jobside import (CompileLog, Tracer, Work, device_report,  # noqa: E402
                               memory_peak_bytes, program_compile_s)

def batches_from(rt, corpus: str, batch: int, record_len: int):
    """Endless [batch, record_len] host batches through the framework's
    sharded reader, re-opened per epoch (as examples/lm_train.py does)."""
    import numpy as np

    while True:
        with rt.sharded_reader([corpus], fmt="tokens", dtype=np.uint16,
                               record_len=record_len,
                               batch_size=batch) as reader:
            for b in reader:
                if b.shape[0] == batch:
                    yield b


def main() -> int:
    work = Work(sys.argv[sys.argv.index("--params") + 1])
    work.stage("script_main")
    p = work.params
    cfg, run, traffic = p["config"], p["config"]["run"], p["traffic"]
    seed, seconds = int(p["seed"]), float(p["seconds"])
    model = spec.load_model(cfg["model"])
    program_params, leaf_norms = model.program_params, model.leaf_norms

    import jax
    import jax.numpy as jnp
    import numpy as np

    import tony_tpu.runtime as rt
    from tony_tpu.models import make_train_step
    from tony_tpu.parallel.mesh import MeshSpec

    from yardstick import compare, traffic as traffic_gen, weights

    rt.initialize()
    compiles = CompileLog()
    mesh = rt.build_job_mesh(MeshSpec(**run.get("mesh", {})))
    device = device_report()
    work.publish("device.json", device)
    work.stage("devices")
    print(f"bench train job: mesh {dict(mesh.shape)} {rt.describe_devices()}",
          flush=True)

    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    records = traffic_gen.training_records(traffic, seed, cfg["vocab_size"])
    corpus = str(work.dir / "corpus.tokens")
    records.tofile(corpus)
    work.stage("corpus_written")

    tcfg = model.program_config(cfg, run, max_seq=seq,
                                dtype=run["compute_dtype"], remat=True,
                                remat_policy=run["remat"])
    hp = run["optimizer"]
    init_fn, step_fn = make_train_step(
        tcfg, mesh, learning_rate=hp["learning_rate"],
        weight_decay=hp["weight_decay"], grad_clip=hp["grad_clip"])
    key = weights.seed_key(seed)
    param_dtype = jnp.dtype(run["param_dtype"])
    batches = batches_from(rt, corpus, batch, seq + 1)
    fault = p.get("fault")

    with jax.sharding.set_mesh(mesh):
        state = init_fn(jax.random.key(0))
        shardings = jax.tree.map(lambda x: x.sharding, state.params)
        state = state._replace(params=jax.jit(
            lambda k: program_params(k, cfg, param_dtype),
            out_shardings=shardings)(key))
        jax.block_until_ready(state.params)
        work.stage("weights_on_device")

        norms_of = jax.jit(leaf_norms)
        change_of = jax.jit(lambda params, k: leaf_norms(jax.tree.map(
            lambda a, b: a - b, params, program_params(k, cfg, param_dtype))))

        def one_step(state, tokens):
            fed = tokens
            if fault == "half_batch":   # test only: part of the batch left out
                fed = np.concatenate([tokens[: batch // 2]] * 2)[:batch]
            state, metrics = step_fn(state, fed)
            loss = float(jax.device_get(metrics["loss"]))   # the fence
            return state, loss

        # The first steps: through the window's own call and feed, on the
        # reader's rows. They warm every program and are what the
        # reference follows once the window has closed.
        first_batches, program = [], {"losses": []}
        for i in range(int(traffic["first_steps"])):
            tokens = next(batches)
            first_batches.append(np.array(tokens))
            state, loss = one_step(state, tokens)
            program["losses"].append(loss)
            if i == 0:
                mu = next(x for x in jax.tree.leaves(
                    state.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
                    if hasattr(x, "mu")).mu
                # adam's first moment after one step is (1 - b1) times the
                # gradient the optimizer got (after the clip).
                program["grad_norms"] = {
                    k: float(v) / (1.0 - 0.9)
                    for k, v in norms_of(mu).items()}
                work.stage("first_step_compiled")
        program["change_norms"] = {
            k: float(v) for k, v in change_of(state.params, key).items()}
        compile_s = program_compile_s()
        work.stage("warmup_done")

        # The window.
        tracer = Tracer(work.dir / "trace") if p.get("trace") else None
        # The last seconds of the window are traced and the profiler is
        # stopped once the window has closed: stopping it blocks for
        # seconds, which must not fall inside what the rate is taken over.
        trace_from = max(seconds - 3.0, 0.5 * seconds)
        step_ends, step_ms, losses = [], [], []
        data_wait_s = 0.0
        started = 0
        t_wall0 = time.time()
        work.stage("window_start")
        m0 = time.monotonic()
        while True:
            now = time.monotonic() - m0
            if now >= seconds:
                break
            if tracer is not None and tracer.span is None \
                    and now >= trace_from:
                tracer.start()
            t_a = time.monotonic()
            with jax.profiler.TraceAnnotation("bench:next-batch"):
                tokens = next(batches)
            t_b = time.monotonic()
            started += 1
            with jax.profiler.TraceAnnotation("bench:train-step"):
                state, loss = one_step(state, tokens)
            t_c = time.monotonic()
            data_wait_s += t_b - t_a
            step_ends.append(t_c - m0)
            step_ms.append((t_c - t_b) * 1000.0)
            losses.append(loss)
        # The window closes with the step that was in flight when the
        # asked-for seconds ran out: every step started is finished and
        # counted, over all of the time they took, so the rate does not
        # jump by one step's tokens from run to run.
        window_s = step_ends[-1]
        t_wall1 = t_wall0 + window_s
        work.stage("window_end")
        if tracer is not None and tracer.span is not None:
            tracer.stop()
        peak = memory_peak_bytes()
        batches.close()
        del state, mu
        gc.collect()

    result = {
        "device": device, "memory_peak_bytes": peak,
        "window": [t_wall0, t_wall1], "seconds": seconds,
        "window_s": window_s,
        "step_ends": step_ends, "step_ms": step_ms,
        "steps_started": started,
        "steps_failed": sum(1 for x in losses if not math.isfinite(x)),
        "tokens_per_step": batch * seq, "data_wait_s": data_wait_s,
        "compile_s": compile_s,
        "compiles_in_window": compiles.inside(t_wall0, t_wall1),
        "batch": batch, "seq": seq, "program": program,
        "control": p.get("control"),
    }
    # Rows fed must be rows of the seeded corpus.
    known = {r.tobytes() for r in records}
    result["foreign_rows"] = sum(
        1 for b in first_batches for row in b if row.tobytes() not in known)
    work.publish("window.json", result)

    # The reference, once the window has closed and its state is freed.
    reference = load_reference(p["config_path"])
    t_ref = time.monotonic()
    ref = reference.first_steps(cfg, seed, first_batches, hp)
    result["reference"] = ref
    if p.get("control"):
        # The control stands in the program's place: the same steps by the
        # reference in the precision below the stated one, judged by the
        # run's own comparison against the cell's own limits.
        program = reference.first_steps(cfg, seed, first_batches, hp,
                                        lowp=p["control"])
    result["numbers"] = compare.training_numbers(program, ref)
    result["reference_s"] = time.monotonic() - t_ref
    work.stage("reference_done")
    work.publish("result.json", result)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
