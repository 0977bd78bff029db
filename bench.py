"""Benchmark harness: prints ONE JSON line.

Primary metric (BASELINE.json north star): mnist_distributed steps/sec/chip
against an assumed 100 steps/sec 4xV100 proxy. The same line carries the
flagship-transformer numbers in ``extras``: train-step tokens/sec/chip with
computed MFU, and a flash-attention (Pallas) vs blockwise-XLA microbench at
seq 2k/8k. The line names the platform, device kind and device count it
ran on at top level: the CPU branch is a smoke run of the host-side
extras, and none of its numbers is a device metric.

Steady-state measurement everywhere: donated state, on-device loop, host
sync only at the timer edges. Every timer edge is fenced — by reading a
scalar back (float()), which waits for the device exactly as
``block_until_ready`` does.

To be replaced by the benchmark ROADMAP.md Queue 1 item 1(a) plans; until
then ``chip_smoke.py`` is the proof that the main path runs on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

BASELINE_STEPS_PER_SEC_PER_CHIP = 100.0  # assumed 4xV100 proxy, not measured
BATCH = 512
MEASURE = 200

# Jit sanitizer ON for every bench run (opt-out with =0): each workload
# records the retraces its dispatches incurred (`retraces_total` in its
# extras), and those feed the BASELINE.json gate — a steady-state step
# that starts recompiling fails `bench --check` even when its wall time
# hides it. setdefault BEFORE any tony_tpu import, matching the tier-1
# conftest arming.
os.environ.setdefault("TONY_JIT_SANITIZER", "1")

# Peak dense bf16 throughput per chip, for MFU — the SAME table the
# live step anatomy uses (observability/stepstats.py), so a bench MFU
# and a production job's tony_mfu gauge are one definition, one table.
from tony_tpu.observability.stepstats import peak_flops_per_chip  # noqa: E402


def _peak_flops() -> float:
    return peak_flops_per_chip(jax.devices()[0])


def best_of_windows(fn, windows: int = 3) -> float:
    """One shared measurement protocol: run ``fn`` once to warm
    (compile), then best-of-``windows`` wall seconds. ``fn`` must END
    fenced (a scalar readback or ``block_until_ready``) — the contract
    from the module docstring."""
    fn()
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_mnist() -> float:
    """Steps/sec/chip with the training loop ON DEVICE: steps_per_call
    batches one lax.scan of optimizer steps per dispatch, so the number
    measures chip throughput, not per-call host dispatch. Distinct
    per-step batches — this is a real training loop, not one batch
    replayed inside the scan."""
    from tony_tpu.models import MnistConfig
    from tony_tpu.models.train import make_classifier_step
    from tony_tpu.parallel.mesh import MeshSpec, build_mesh

    n_chips = len(jax.devices())
    mesh = build_mesh(MeshSpec.auto(n_chips), devices=jax.devices())
    cfg = MnistConfig(arch="cnn", dtype="bfloat16")
    per_call = 50
    init_fn, step_fn = make_classifier_step(
        cfg, mesh, learning_rate=1e-3, steps_per_call=per_call
    )

    rng = np.random.default_rng(0)
    images = jnp.asarray(
        rng.normal(size=(per_call, BATCH, 28, 28, 1)), jnp.float32
    )
    labels = jnp.asarray(
        rng.integers(0, 10, (per_call, BATCH)), jnp.int32
    )

    with jax.sharding.set_mesh(mesh):
        state = init_fn(jax.random.key(0))
        state, metrics = step_fn(state, images, labels)  # compile + warm
        float(metrics["loss"])  # fence

        calls = max(1, MEASURE // per_call)
        best_dt = float("inf")
        # Best-of-5 (not 3): this is the headline vs_baseline number;
        # extra windows cost ~a second each and tighten the recorded best.
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(calls):
                state, metrics = step_fn(state, images, labels)
            float(metrics["loss"])  # tony: noqa[TONY-X002] — intended per-window timing fence
            best_dt = min(best_dt, time.perf_counter() - t0)
    return calls * per_call / best_dt / n_chips


def _bench_lm_train(cfg, batch: int, seq: int, measure: int,
                    optimizer=None, warmup: int = 3):
    """Shared LM train-step measurement: warmup + fence, best-of-2
    windows, analytic model flops (6·N·T PaLM counting + the
    causal attention term; remat recompute NOT counted — MFU is model
    flops, not hardware flops)."""
    from tony_tpu.models import make_train_step
    from tony_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    init_fn, step_fn = make_train_step(cfg, mesh, optimizer=optimizer)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq)),
        jnp.int32,
    )
    with jax.sharding.set_mesh(mesh):
        state = init_fn(jax.random.key(0))
        metrics = None
        for _ in range(warmup):
            state, metrics = step_fn(state, tokens)
        float(metrics["loss"])  # fence

        dt = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(measure):
                state, metrics = step_fn(state, tokens)
            float(metrics["loss"])  # tony: noqa[TONY-X002] — intended per-window timing fence
            dt = min(dt, time.perf_counter() - t0)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    flops_per_step = (
        6.0 * n_params * batch * seq
        + 6.0 * cfg.n_layers * batch * seq * seq * cfg.n_heads * cfg.head_dim
    )
    out = {
        "tokens_per_sec_per_chip": round(batch * seq * measure / dt),
        "params_m": round(n_params / 1e6, 1),
        "batch": batch,
        "seq": seq,
        "step_ms": round(dt / measure * 1000, 2),
    }
    peak = _peak_flops()
    if peak:  # unknown accelerator generation: no MFU, not a wrong one
        out["mfu"] = round(flops_per_step * measure / dt / peak, 4)
    return out


def bench_transformer(batch: int = 8, seq: int = 2048, measure: int = 20,
                      n_heads: int = 16, head_dim: int = 64):
    """Flagship LM full train step (fwd+loss+grad+adamw) on one chip:
    tokens/sec/chip and analytic MFU. Remat only when the activations
    need it: flash attention keeps activations O(T·block), so at 200M
    both bench shapes fit HBM without remat and its recompute is pure
    MFU loss (seen on the earlier platform, not re-measured here); more
    total tokens than that force it back on (the fit is a
    batch*seq property: b=16 @ 2k already blows memory without it).

    ``head_dim``: 64 is the older comparability shape; 128 (same d_model,
    same params) is the TPU-FIRST flagship shape — d=64 fills only half
    the MXU's 128-deep contraction/output width, structurally capping
    every attention matmul at 50% of peak. head_dim 128 is what one
    designs for this hardware (the 1B row always did). The MFU figures
    this docstring used to quote predate the current chip: not measured."""
    from tony_tpu.models import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=32_000, d_model=1024, n_layers=8, n_heads=n_heads,
        head_dim=head_dim, d_ff=4096, max_seq=seq, dtype="bfloat16",
        remat=batch * seq > 16384,
        remat_policy="dots", layer_scan_unroll=8,
    )
    return _bench_lm_train(cfg, batch, seq, measure)


def bench_transformer_1b(batch: int = 4, seq: int = 2048, measure: int = 8):
    """1.0B-parameter LM full train step on ONE v5e chip — the
    realistic-size MFU row (MFU should RISE with model size; a 200M-only
    story undersells the stack). Fits 16 GB HBM with
    adafactor (factored second moments — the standard memory-lean
    optimizer at this scale; adamw's 12 bytes/param of fp32 state does
    not fit), NO remat (flash keeps activations O(T·block); recompute
    is pure MFU loss), head_dim 128 (fills the 128-deep MXU
    contraction), and the fully-unrolled layer loop. That it fits and
    runs on the current chip is chip_smoke.py's step-1b phase; its MFU
    there is not measured."""
    import optax

    from tony_tpu.models import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=32_000, d_model=2048, n_layers=13, n_heads=16,
        head_dim=128, d_ff=8192, max_seq=seq, dtype="bfloat16", remat=False,
        layer_scan_unroll=13,
    )
    out = _bench_lm_train(
        cfg, batch, seq, measure, optimizer=optax.adafactor(1e-3), warmup=2
    )
    out["optimizer"] = "adafactor"
    return out


def bench_decode(batch: int = 8, prompt_len: int = 128, new_tokens: int = 128,
                 n_kv_heads: int = 4, windows: int = 3):
    """KV-cache greedy decode on the flagship LM with GQA (the decode
    bandwidth lever — the cache holds n_kv_heads of the 16 query heads),
    through a persistent DecodeSession — weights fuse once, each call
    dispatches only the compiled loop (the serving shape; per-call
    re-fusion once cost more than half of a 128-token call's wall). Wall
    tok/s is best-of-N calls."""
    from tony_tpu.models import DecodeSession, TransformerConfig, init_params

    cfg = TransformerConfig(
        vocab_size=32_000, d_model=1024, n_layers=8, n_heads=16, head_dim=64,
        d_ff=4096, max_seq=2048, dtype="bfloat16", remat=False,
        n_kv_heads=n_kv_heads,
    )
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.key(0))  # tony: noqa[TONY-X001] — one-shot init compile, not a step path
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size,
                                          (batch, prompt_len)),
        jnp.int32,
    )
    session = DecodeSession(params, cfg)

    def timed(n: int) -> float:
        return best_of_windows(
            lambda: float(jnp.sum(session.generate(prompt, max_new_tokens=n))),
            windows,
        )

    # Two horizons; the difference isolates the marginal decode step from
    # the prefill + dispatch cost that a single-horizon wall divide would
    # smear into "step_ms". The horizons are LONG (2x and 4x new_tokens,
    # i.e. steps averaged over a prompt+512 context) so that per-call
    # wall noise divides over 256 steps.
    short_n, long_n = new_tokens * 2, new_tokens * 4
    dt_wall = timed(new_tokens)
    dt_short = timed(short_n)
    dt_long = timed(long_n)
    step_s = max(dt_long - dt_short, 1e-9) / (long_n - short_n)
    return {
        "tokens_per_sec_per_chip": round(batch / step_s),
        "step_ms": round(step_s * 1000, 3),
        "generate_wall_tokens_per_sec": round(batch * new_tokens / dt_wall),
        "prefill_plus_overhead_ms": round(
            (dt_wall - new_tokens * step_s) * 1000, 2
        ),
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "n_kv_heads": n_kv_heads,
    }


def bench_moe(batch: int = 4, seq: int = 2048, measure: int = 8):
    """MoE trunk train step on one chip (4 experts, top-2, with the Switch
    balance + router z losses active): tokens/sec/chip."""
    from tony_tpu.models import TransformerConfig, make_train_step
    from tony_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg = TransformerConfig(
        vocab_size=32_000, d_model=1024, n_layers=8, n_heads=16, head_dim=64,
        d_ff=4096, max_seq=seq, dtype="bfloat16", remat=True,
        remat_policy="dots", n_experts=4, expert_top_k=2,
        layer_scan_unroll=8,
    )
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    init_fn, step_fn = make_train_step(cfg, mesh)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq)),
        jnp.int32,
    )
    with jax.sharding.set_mesh(mesh):
        state = init_fn(jax.random.key(0))
        for _ in range(2):
            state, metrics = step_fn(state, tokens)
        float(metrics["loss"])
        dt = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(measure):
                state, metrics = step_fn(state, tokens)
            float(metrics["loss"])  # tony: noqa[TONY-X002] — intended per-window timing fence
            dt = min(dt, time.perf_counter() - t0)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    return {
        "tokens_per_sec_per_chip": round(batch * seq * measure / dt),
        "params_m": round(n_params / 1e6, 1),
        "batch": batch,
        "seq": seq,
        "moe_entropy": round(float(metrics["moe_entropy"]), 3),
        "moe_drop_rate": round(float(metrics["moe_drop_rate"]), 4),
    }


def bench_moe_decode(batch: int = 8, windows: int = 3):
    """MoE decode step times at E=4 and E=16 through the one expert path
    decode has (dropless, grouped by expert: ``_moe_mlp_decode``). Long
    differencing horizons (256 vs 896 steps) so per-call wall noise
    divides out."""
    from tony_tpu.models import DecodeSession, TransformerConfig, init_params

    out = {"batch": batch, "top_k": 2}
    steps = {}
    for n_experts in (4, 16):
        cfg = TransformerConfig(
            vocab_size=32_000, d_model=512, n_layers=4, n_heads=8,
            head_dim=64, d_ff=1024, max_seq=1024, dtype="bfloat16",
            remat=False, n_experts=n_experts, expert_top_k=2,
        )
        params = jax.jit(lambda k, c=cfg: init_params(k, c))(  # tony: noqa[TONY-X001] — one-shot init compile, not a step path
            jax.random.key(0)
        )
        prompt = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, 16)),
            jnp.int32,
        )
        session = DecodeSession(params, cfg)

        def timed(n, s=session, p=prompt):
            return best_of_windows(
                lambda: float(jnp.sum(s.generate(p, max_new_tokens=n))),
                windows,
            )

        steps[n_experts] = max(timed(896) - timed(256), 1e-9) / 640
        out[f"step_ms_e{n_experts}"] = round(steps[n_experts] * 1000, 3)
    out["e16_over_e4_step_ratio"] = round(steps[16] / steps[4], 2)
    return out


def bench_serving(
    slots: int = 16,
    n_requests: int = 64,
    prefill_chunk: int = 32,
    # 32 on the TPU defaults, 8 on the CPU micro: both were chosen
    # against the earlier platform's per-dispatch cost and have not been
    # re-derived on the current chip (ROADMAP.md Queue 1 item 1).
    decode_window: int = 32,
    prefill_batch: int = 4,
    d_model: int = 1024,
    n_layers: int = 8,
    n_heads: int = 16,
    head_dim: int = 64,
    n_kv_heads: int = 4,
    vocab: int = 32_000,
    max_seq: int = 2048,
    prompt_rng: tuple = (16, 96),
    out_mean: float = 48.0,
    out_clip: tuple = (8, 192),
    bucket: int = 32,
    arrival_mean_ms: float = 3.0,
    seed: int = 0,
):
    """Continuous-batching serving wall throughput vs the single-shot
    ``generate`` server on the SAME mixed workload and hardware — the
    number that closes the 12.4k-marginal vs 5.5k-wall gap ROADMAP calls
    out. Workload: ``n_requests`` with uniform prompt lengths and
    exponential (heavy-tail-ish, the realistic shape) output budgets,
    Poisson-ish arrivals.

    The single-shot comparator is the BEST static server one can build
    from ``DecodeSession.generate``: requests batched ``slots`` at a
    time in arrival order, prompts padded to one width (one prefill
    executable), horizons bucketed to multiples of ``bucket`` (how real
    static servers bound their compile count), weights pre-fused, every
    signature pre-warmed so neither side's wall contains compile time.
    Its structural tax is padding: every row pays its group's bucketed
    MAX output budget while the engine retires each stream at its own
    budget and refills the slot — that, not kernel speed, is the gap
    being measured. Both sides count the same useful tokens
    (sum of per-request budgets) over their wall.

    Two comparators come back: ``single_shot_*`` (the strict same-slots
    static server above) and ``generate_wall_*`` — the decode_gqa-shaped
    figure (batch 8, uniform prompt/new lengths) that BASELINE.json's
    5,512 tok/s records; ``generate_wall_speedup`` is the acceptance
    ratio the serving issue names (≥ 2×)."""
    from tony_tpu.models import DecodeSession, TransformerConfig, init_params
    from tony_tpu.serving import ServingEngine

    cfg = TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_layers=n_layers,
        n_heads=n_heads, head_dim=head_dim, d_ff=4 * d_model,
        max_seq=max_seq, dtype="bfloat16", remat=False,
        n_kv_heads=n_kv_heads,
    )
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.key(0))  # tony: noqa[TONY-X001] — one-shot init compile, not a step path
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, vocab, rng.integers(prompt_rng[0],
                                            prompt_rng[1] + 1)).astype(
            np.int32
        )
        for _ in range(n_requests)
    ]
    outs = np.clip(
        np.round(rng.exponential(out_mean, n_requests)).astype(int),
        out_clip[0], out_clip[1],
    )
    arrivals_s = np.cumsum(
        rng.exponential(arrival_mean_ms / 1000.0, n_requests)
    )
    useful = int(outs.sum())

    # -- the decode_gqa-shaped generate_wall figure -----------------------
    # One batch-8 uniform-length generate call on the same weights — the
    # shape behind BASELINE.json's decode_gqa.generate_wall_tokens_per_sec
    # (prompt 128 / new 128 there; scaled by max_seq for micro configs).
    session = DecodeSession(params, cfg)
    ref_len = min(128, max_seq // 4)
    ref_prompt = jnp.asarray(
        rng.integers(0, vocab, (8, ref_len)), jnp.int32
    )
    gw = best_of_windows(lambda: float(jnp.sum(
        session.generate(ref_prompt, max_new_tokens=ref_len)
    )))
    generate_wall_rate = 8 * ref_len / gw

    # -- single-shot comparator -------------------------------------------
    width = max(p.size for p in prompts)

    def batch_of(group):
        rows = [np.concatenate([np.zeros(width - p.size, np.int32), p])
                for p in group]
        while len(rows) < slots:  # fixed batch: a static server pads
            rows.append(rows[0])
        return jnp.asarray(np.stack(rows), jnp.int32)

    groups = [
        (batch_of(prompts[i:i + slots]),
         int(-(-int(outs[i:i + slots].max()) // bucket) * bucket))
        for i in range(0, n_requests, slots)
    ]
    for batch, horizon in groups:  # warm every signature out of the wall
        float(jnp.sum(session.generate(batch, max_new_tokens=horizon)))
    t0 = time.perf_counter()
    for batch, horizon in groups:
        float(jnp.sum(session.generate(batch, max_new_tokens=horizon)))
    single_wall = time.perf_counter() - t0
    single_rate = useful / single_wall

    # -- continuous batching ----------------------------------------------
    # Right-size the slot KV rows to the workload's admission bound
    # (prompt + budget + one chunk of slack) instead of cfg.max_seq —
    # every decode step's attention reads scale with the row length.
    max_len = min(max_seq, prompt_rng[1] + out_clip[1] + prefill_chunk)
    engine = ServingEngine(
        session.params, cfg, slots=slots, max_len=max_len,
        prefill_chunk=prefill_chunk, decode_window=decode_window,
        prefill_batch=prefill_batch, seed=seed,
    )
    # Warm both engine executables before the clock starts.
    engine.submit(prompts[0], max_new_tokens=2)
    while engine.stats()["retired"] < 1:
        engine.step()
    engine.inter_token_ms_samples.clear()
    engine.ttft_ms_samples.clear()
    # Drive the loop on THIS thread (submitting arrivals as their
    # Poisson clock comes due) — the threaded serve_forever path
    # measured ~15% slower here from GIL contention with the submitting
    # thread, and a bench should report the engine, not the bench.
    reqs = []
    due = iter(zip(prompts, outs, arrivals_s))
    nxt = next(due)
    sustained_tokens = 0
    sustained_wall = 0.0
    t0 = time.perf_counter()
    while nxt is not None or not all(r.done() for r in reqs):
        while nxt is not None and time.perf_counter() - t0 >= nxt[2]:
            reqs.append(engine.submit(nxt[0], max_new_tokens=int(nxt[1])))
            nxt = next(due, None)
        # Saturated-window accounting: iterations that START with a
        # non-empty queue are the steady state a deployed engine lives
        # in; the ramp/drain boundary of a FINITE workload (arrivals
        # stop, slots empty out) is a bench artifact, so it is reported
        # separately (wall_tokens_per_sec) rather than averaged in.
        saturated = engine.stats()["queue_depth"] > 0
        tok_before = engine.tokens_generated
        it_t0 = time.perf_counter()
        did = engine.step()
        if saturated:
            sustained_wall += time.perf_counter() - it_t0
            sustained_tokens += engine.tokens_generated - tok_before
        if not did and nxt is not None:
            time.sleep(0.0005)
    serving_wall = time.perf_counter() - t0
    engine.close()
    serving_rate = useful / serving_wall
    sustained_rate = (sustained_tokens / sustained_wall
                      if sustained_wall > 0 else serving_rate)
    inter = np.asarray(engine.inter_token_ms_samples, float)
    ttft = np.asarray(engine.ttft_ms_samples, float)
    return {
        "wall_tokens_per_sec": round(serving_rate),
        "sustained_tokens_per_sec": round(sustained_rate),
        "generate_wall_tokens_per_sec": round(generate_wall_rate),
        "generate_wall_speedup": round(
            sustained_rate / generate_wall_rate, 2
        ),
        "single_shot_wall_tokens_per_sec": round(single_rate),
        "single_shot_speedup": round(sustained_rate / single_rate, 2),
        "inter_token_p50_ms": round(float(np.percentile(inter, 50)), 2),
        "inter_token_p95_ms": round(float(np.percentile(inter, 95)), 2),
        "ttft_p50_ms": round(float(np.percentile(ttft, 50)), 2),
        "ttft_p95_ms": round(float(np.percentile(ttft, 95)), 2),
        "generated_tokens": useful,
        "slots": slots,
        "n_requests": n_requests,
        "prefill_chunk": prefill_chunk,
        "decode_window": decode_window,
        "out_mean": float(out_mean),
        "d_model": d_model,
    }


# CPU smoke variant: same engine, same comparator, a model small enough
# that the whole section stays under about a minute — seeds the portable
# (ratio) serving gate for non-TPU runs.
SERVING_CPU_MICRO = dict(
    slots=16, n_requests=128, prefill_chunk=32, decode_window=8,
    prefill_batch=4, d_model=128, n_layers=2, n_heads=4, head_dim=32,
    n_kv_heads=2, vocab=1024, max_seq=256, prompt_rng=(8, 48),
    out_mean=32.0, out_clip=(8, 96), bucket=32, arrival_mean_ms=2.0,
)


def bench_serving_fleet(
    max_replicas: int = 3,
    slots: int = 4,
    prefill_chunk: int = 16,
    decode_window: int = 4,
    d_model: int = 128,
    n_layers: int = 2,
    n_heads: int = 4,
    head_dim: int = 32,
    n_kv_heads: int = 2,
    vocab: int = 512,
    max_seq: int = 128,
    prompt_rng: tuple = (8, 24),
    out_tokens: int = 16,
    # Stepped + bursty arrivals: (n_requests, arrival_mean_ms) phases.
    # Phase 1 cruises on one replica; phase 2 steps the rate up ~12x
    # (the autoscale trigger); phase 3 falls back to cruise.
    phases: tuple = ((12, 25.0), (56, 2.0), (12, 25.0)),
    tick_ms: float = 25.0,
    scale_up_queue_depth: int = 2,
    hysteresis_ticks: int = 2,
    cooldown_ms: int = 400,
    seed: int = 0,
):
    """Autoscaled serving fleet under a stepped/bursty arrival process:
    ``max_replicas`` engine replicas (each a real ``ServingEngine``
    behind a real ``ServingServer``) fronted by the ``FleetRouter``,
    with the ``Autoscaler`` ticking on the router's aggregated signals
    and actuating 1→N as the burst lands.

    What the numbers mean:

    * ``fleet_sustained_tokens_per_sec`` — useful tokens retired during
      the burst window over that window's wall: the figure that should
      SCALE with replicas (a 1-replica fleet saturates at roughly the
      engine's micro rate / slots ratio).
    * ``ttft_p95_ms`` — engine-reported submit→first-token p95 across
      every request, queue wait included (what a client feels during
      the burst before capacity arrives).
    * ``autoscale_reaction_ms`` — burst onset to the first scale-up
      ACTUATION (replica in rotation). Replicas are pre-warmed, so
      this isolates the control loop (poll → hysteresis → cooldown →
      add), not XLA compile or checkpoint restore; the fleet e2e test
      covers the cold path.

    The actuation here swaps a pre-built warm replica into the router —
    the daemon's launch path (WAL, slice placement, addr discovery) is
    benched by ``bench_scheduler`` and tested in tests/test_fleet.py;
    this bench isolates serving-plane behavior under load."""
    from tony_tpu.fleet.autoscale import AutoscalePolicy, Autoscaler
    from tony_tpu.fleet.router import FleetRouter
    from tony_tpu.models import DecodeSession, TransformerConfig, init_params
    from tony_tpu.observability.metrics import MetricsRegistry
    from tony_tpu.serving import ServingEngine
    from tony_tpu.serving.http import ServingServer

    cfg = TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_layers=n_layers,
        n_heads=n_heads, head_dim=head_dim, d_ff=4 * d_model,
        max_seq=max_seq, dtype="float32", remat=False,
        n_kv_heads=n_kv_heads,
    )
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.key(0))  # tony: noqa[TONY-X001] — one-shot init compile, not a step path
    session = DecodeSession(params, cfg)
    rng = np.random.default_rng(seed)

    # Pre-build and WARM every replica the autoscaler may bring into
    # rotation (compile out of the wall; reaction measures control).
    replicas = []
    for i in range(max_replicas):
        eng = ServingEngine(
            session.params, cfg, slots=slots,
            prefill_chunk=prefill_chunk, decode_window=decode_window,
            registry=MetricsRegistry(), seed=seed,
        ).start()
        warm = eng.submit(
            rng.integers(0, vocab, prompt_rng[1]).astype(np.int32), 2
        )
        warm.result(timeout=300)
        eng.ttft_ms_samples.clear()
        eng.inter_token_ms_samples.clear()
        srv = ServingServer(eng, port=0, host="127.0.0.1")
        port = srv.start()
        replicas.append((eng, srv, f"127.0.0.1:{port}"))

    router = FleetRouter(health_interval_s=3600.0, retries=2,
                         wake_timeout_s=5.0)
    scaler = Autoscaler(AutoscalePolicy(
        min_replicas=1, max_replicas=max_replicas,
        scale_up_queue_depth=scale_up_queue_depth,
        hysteresis_ticks=hysteresis_ticks, cooldown_ms=cooldown_ms,
        scale_down_idle_ms=10 ** 9,  # bounded wall: no down-phase here
    ))
    router.add_replica("r0", replicas[0][2])
    desired = [1]
    scale_events: list = []

    # Arrival schedule (relative seconds) + the burst-onset timestamp.
    arrivals: list = []
    t_acc = 0.0
    for n_req, mean_ms in phases:
        for _ in range(n_req):
            t_acc += float(rng.exponential(mean_ms / 1000.0))
            arrivals.append(t_acc)
    burst_rel = arrivals[phases[0][0]]

    stop = threading.Event()
    lock = threading.Lock()
    results: list = []
    t0 = time.perf_counter()

    def control_loop():
        while not stop.wait(tick_ms / 1000.0):
            router.poll_once()
            decision = scaler.tick(router.signals(), desired[0])
            if decision is None or decision.target == desired[0]:
                continue
            now_rel = time.perf_counter() - t0
            for i in range(desired[0], decision.target):
                router.add_replica(f"r{i}", replicas[i][2])
            for i in range(decision.target, desired[0]):
                router.drain_replica(f"r{i}")
            desired[0] = decision.target
            scale_events.append(
                (now_rel, decision.target, decision.reason)
            )

    def client(prompt, rid):
        code, raw, _ = router.route_generate({
            "prompt": [int(x) for x in prompt],
            "max_new_tokens": out_tokens, "request_id": rid,
        })
        done_rel = time.perf_counter() - t0
        out = json.loads(raw) if code == 200 else {}
        with lock:
            results.append({
                "code": code, "done_rel": done_rel,
                "tokens": int(out.get("length", 0)),
                "ttft_ms": float(out.get("ttft_ms", 0.0)),
            })

    ctrl = threading.Thread(target=control_loop, daemon=True)
    ctrl.start()
    workers = []
    for idx, due in enumerate(arrivals):
        delay = due - (time.perf_counter() - t0)
        if delay > 0:
            time.sleep(delay)
        prompt = rng.integers(
            0, vocab, int(rng.integers(prompt_rng[0], prompt_rng[1] + 1))
        )
        w = threading.Thread(target=client,
                             args=(prompt, f"fleet-{idx}"), daemon=True)
        w.start()
        workers.append(w)
    for w in workers:
        w.join(timeout=120)
    stop.set()
    ctrl.join(timeout=10)
    wall = time.perf_counter() - t0

    router.stop()
    for eng, srv, _ in replicas:
        srv.stop()
        eng.close()

    ok = [r for r in results if r["code"] == 200]
    total_tokens = sum(r["tokens"] for r in ok)
    burst_n = phases[0][0] + phases[1][0]
    burst_done = [r["done_rel"] for r in ok
                  if burst_rel <= r["done_rel"]]
    burst_done = sorted(burst_done)[:max(1, burst_n - phases[0][0])]
    burst_wall = (burst_done[-1] - burst_rel) if burst_done else wall
    burst_tokens = out_tokens * len(burst_done)
    ttft = np.asarray([r["ttft_ms"] for r in ok], float)
    up_events = [e for e in scale_events if e[1] > 1]
    # Clamped at 0: a scale-up actuated DURING burst ramp-up (cruise
    # load already tripping hysteresis as the burst lands) reacted
    # early, not slowly. The gated failures are "slow" and "never"
    # (the 9e9 sentinel fails the lower-is-better gate loudly).
    reaction_ms = (
        round(max(0.0, (up_events[0][0] - burst_rel) * 1000.0), 1)
        if up_events else 9e9
    )
    return {
        "fleet_wall_tokens_per_sec": round(total_tokens / wall),
        "fleet_sustained_tokens_per_sec": round(
            burst_tokens / max(burst_wall, 1e-6)
        ),
        "ttft_p50_ms": round(float(np.percentile(ttft, 50)), 2),
        "ttft_p95_ms": round(float(np.percentile(ttft, 95)), 2),
        "autoscale_reaction_ms": reaction_ms,
        "replicas_peak": max([e[1] for e in scale_events],
                             default=desired[0]),
        "scale_ups": len(up_events),
        "requests_ok": len(ok),
        "requests_failed": len(results) - len(ok),
        "generated_tokens": total_tokens,
        "slots": slots,
        "max_replicas": max_replicas,
        "d_model": d_model,
    }


def bench_resnet50(batch: int = 32, size: int = 224, measure: int = 20):
    """ResNet-50 full train step (fwd+loss+grad+adam), images/sec/chip —
    the BASELINE config-5 workload."""
    from tony_tpu.models import (
        ResNetConfig,
        make_image_classifier_step,
        resnet_apply,
        resnet_init,
    )
    from tony_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg = ResNetConfig(depth=50, width=64, n_classes=1000, dtype="bfloat16")
    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    init_fn, step_fn = make_image_classifier_step(
        lambda key: resnet_init(key, cfg),
        lambda params, images: resnet_apply(params, images, cfg),
        mesh,
        config=cfg,
    )
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.normal(size=(batch, size, size, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 1000, (batch,)), jnp.int32)
    with jax.sharding.set_mesh(mesh):
        state = init_fn(jax.random.key(0))
        for _ in range(3):
            state, metrics = step_fn(state, images, labels)
        float(metrics["loss"])  # fence
        dt = float("inf")  # best of 2 (see bench_transformer)
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(measure):
                state, metrics = step_fn(state, images, labels)
            float(metrics["loss"])  # tony: noqa[TONY-X002] — intended per-window timing fence
            dt = min(dt, time.perf_counter() - t0)
    return {
        "images_per_sec_per_chip": round(batch * measure / dt, 1),
        "batch": batch,
        "image_size": size,
        "step_ms": round(dt / measure * 1000, 2),
    }


def _step_stats(walls_s: list[float]) -> dict:
    """Per-step wall stats: the mean hides a bimodal pipeline (fast
    overlapped steps + periodic stalls when the prefetch queue drains),
    so the JSON line carries p50/p95 too — a data-plane regression shows
    up in the tail before it moves the average."""
    arr = np.asarray(walls_s) * 1000.0
    return {
        "mean_ms": round(float(arr.mean()), 2),
        "p50_ms": round(float(np.percentile(arr, 50)), 2),
        "p95_ms": round(float(np.percentile(arr, 95)), 2),
    }


def _io_rates(snap0: dict, snap1: dict) -> dict:
    """Data-plane sub-rates from two observability-registry snapshots
    bracketing the streamed window: sustained read and H2D throughput
    (bytes over the time actually spent in reads/puts — the overlapped
    rates, not wall-clock divides) plus the mean consumer stall per
    batch. These attribute a regression to its layer without a rerun."""
    def dc(name):
        return (snap1["counters"].get(name, 0.0)
                - snap0["counters"].get(name, 0.0))

    def dh(name):
        a = snap1["histograms"].get(name, {"count": 0, "sum": 0.0})
        b = snap0["histograms"].get(name, {"count": 0, "sum": 0.0})
        return a["count"] - b["count"], a["sum"] - b["sum"]

    from tony_tpu.io.reader import (
        IO_BYTES_READ_COUNTER,
        IO_H2D_BYTES_COUNTER,
        IO_H2D_MS_HISTOGRAM,
        IO_QUEUE_WAIT_MS_HISTOGRAM,
        IO_READ_MS_HISTOGRAM,
    )

    _, read_ms = dh(IO_READ_MS_HISTOGRAM)
    _, h2d_ms = dh(IO_H2D_MS_HISTOGRAM)
    n_wait, wait_ms = dh(IO_QUEUE_WAIT_MS_HISTOGRAM)
    return {
        "read_mb_per_sec": round(
            dc(IO_BYTES_READ_COUNTER) / 1e3 / read_ms, 1
        ) if read_ms > 0 else 0.0,
        "h2d_mb_per_sec": round(
            dc(IO_H2D_BYTES_COUNTER) / 1e3 / h2d_ms, 1
        ) if h2d_ms > 0 else 0.0,
        "queue_wait_ms_mean": round(wait_ms / n_wait, 2) if n_wait else 0.0,
    }


def bench_input_pipeline(lm_measure: int = 16, resnet_measure: int = 20,
                         workloads: tuple = ("lm", "resnet")):
    """Prove the data plane can FEED the chip. Writes
    a real on-disk tokens corpus, streams it through ShardedRecordReader
    (parallel span reads) → ``device_prefetch`` (background-thread H2D,
    depth 4) into the same train steps the synthetic benches run, and
    reports streamed vs synthetic per-step stats (the gap is the input
    pipeline's uncovered cost). Second point at ResNet scale: raw uint8
    image records (150,528 B each, the shape where bytes — not tokens —
    are the constraint) transferred as uint8 and decoded ON DEVICE
    (resnet_apply's cast+scale), with the sustained disk→HBM byte rate
    and the registry-attributed io sub-rates. Every step is fenced by a
    loss readback so the per-step distribution (p50/p95) is real.

    ``workloads`` selects the sections — the post-PR-4 streamed-ResNet
    re-measurement runs ``("resnet",)`` alone (the 200M LM section is
    pointless on hosts where that model cannot hit steady state)."""
    from tony_tpu import observability
    from tony_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    registry = observability.default_registry()
    rng = np.random.default_rng(0)
    out = {}
    warm = 3

    def timed_steps(n, one_step):
        walls = []
        for _ in range(n):
            t0 = time.perf_counter()
            one_step()  # must end fenced (module fence rule)
            walls.append(time.perf_counter() - t0)
        return walls

    # -- LM: 200M flagship config, same shape as bench_transformer --------
    if "lm" in workloads:
        out.update(_bench_input_lm(mesh, registry, rng, lm_measure, warm,
                                   timed_steps))
    if "resnet" in workloads:
        out.update(_bench_input_resnet(mesh, registry, rng, resnet_measure,
                                       warm, timed_steps))
    return out


def _bench_input_lm(mesh, registry, rng, lm_measure, warm, timed_steps):
    import os as _os
    import tempfile

    from tony_tpu.io import ShardedRecordReader, sharded_batches
    from tony_tpu.models import TransformerConfig, make_train_step

    out = {}
    batch, seq = 8, 2048
    cfg = TransformerConfig(
        vocab_size=32_000, d_model=1024, n_layers=8, n_heads=16,
        head_dim=64, d_ff=4096, max_seq=seq, dtype="bfloat16",
        remat=False, layer_scan_unroll=8,
    )
    init_fn, step_fn = make_train_step(cfg, mesh)
    rows = (lm_measure + warm) * batch
    corpus = rng.integers(0, cfg.vocab_size, (rows, seq), dtype=np.uint16)
    with tempfile.NamedTemporaryFile(suffix=".tokens", delete=False) as f:
        f.write(corpus.tobytes())
        lm_path = f.name
    try:
        with jax.sharding.set_mesh(mesh):
            state_box = [init_fn(jax.random.key(0))]
            synth = jnp.asarray(corpus[:batch], jnp.uint16)

            def synth_step():
                state_box[0], m = step_fn(state_box[0], synth)
                float(m["loss"])

            timed_steps(warm, synth_step)
            synth_walls = timed_steps(lm_measure, synth_step)

            reader = ShardedRecordReader(
                [lm_path], fmt="tokens", dtype=np.uint16, record_len=seq,
                batch_size=batch,
            )
            with reader:
                it = sharded_batches(reader, mesh)

                def stream_step():
                    state_box[0], m = step_fn(state_box[0], next(it))
                    float(m["loss"])

                io0 = registry.snapshot()  # pre-warm: rates cover
                timed_steps(warm, stream_step)  # the whole stream session
                stream_walls = timed_steps(lm_measure, stream_step)
                io1 = registry.snapshot()
        synth_dt, stream_dt = sum(synth_walls), sum(stream_walls)
        out["lm_200m"] = {
            "synthetic_step_ms": round(synth_dt / lm_measure * 1000, 2),
            "streamed_step_ms": round(stream_dt / lm_measure * 1000, 2),
            "overhead_pct": round((stream_dt / synth_dt - 1) * 100, 1),
            "synthetic": _step_stats(synth_walls),
            "streamed": _step_stats(stream_walls),
            "io": _io_rates(io0, io1),
            "batch": batch, "seq": seq,
        }
    finally:
        _os.unlink(lm_path)
    return out


def _bench_input_resnet(mesh, registry, rng, resnet_measure, warm,
                        timed_steps):
    import os as _os
    import tempfile

    from tony_tpu.io import ShardedRecordReader, device_prefetch
    from tony_tpu.models import (
        ResNetConfig,
        make_image_classifier_step,
        resnet_apply,
        resnet_init,
    )

    out = {}
    # -- ResNet-50: uint8 image records, bytes are the constraint ---------
    ibatch, size = 32, 224
    rec = size * size * 3
    rcfg = ResNetConfig(depth=50, width=64, n_classes=1000, dtype="bfloat16")
    rinit, rstep = make_image_classifier_step(
        lambda key: resnet_init(key, rcfg),
        lambda params, images: resnet_apply(params, images, rcfg),
        mesh,
        config=rcfg,
    )
    rows = (resnet_measure + warm) * ibatch
    images = rng.integers(0, 256, (rows, rec), dtype=np.uint8)
    with tempfile.NamedTemporaryFile(suffix=".tokens", delete=False) as f:
        f.write(images.tobytes())
        img_path = f.name
    try:
        from jax.sharding import NamedSharding, PartitionSpec as P

        labels = jnp.asarray(rng.integers(0, 1000, (ibatch,)), jnp.int32)
        sharding = NamedSharding(mesh, P(("dp", "ep")))
        with jax.sharding.set_mesh(mesh):
            state_box = [rinit(jax.random.key(0))]
            # Synthetic feeds the SAME uint8 contract the streamed path
            # uses (decode happens on device in resnet_apply), pre-placed
            # so its step time is pure compute.
            synth = jax.device_put(
                images[:ibatch].reshape(ibatch, size, size, 3), sharding
            )

            def synth_step():
                state_box[0], m = rstep(state_box[0], synth, labels)
                float(m["loss"])

            timed_steps(warm, synth_step)
            synth_walls = timed_steps(resnet_measure, synth_step)

            reader = ShardedRecordReader(
                [img_path], fmt="tokens", dtype=np.uint8, record_len=rec,
                batch_size=ibatch,
            )
            with reader:
                def img_batches():
                    for b in reader:
                        if b.shape[0] == ibatch:
                            # reshape is metadata-only; bytes stay uint8
                            # until the on-device decode inside the step
                            yield b.reshape(ibatch, size, size, 3)

                # Deep pipeline, wide transfer pool: at ~4.8 MB/batch the
                # put dominates the 18 ms step on slow transports, so up
                # to 6 transfers proceed concurrently while the consumer
                # steps (~38 MB of host batches in flight — noise next to
                # the model). On fast PCIe the extra workers just idle.
                with device_prefetch(
                    img_batches(), sharding, depth=8, transfer_workers=6,
                ) as it:
                    def stream_step():
                        state_box[0], m = rstep(
                            state_box[0], next(it), labels
                        )
                        float(m["loss"])

                    io0 = registry.snapshot()  # pre-warm (see LM)
                    timed_steps(warm, stream_step)
                    stream_walls = timed_steps(resnet_measure, stream_step)
                    io1 = registry.snapshot()
        synth_dt, stream_dt = sum(synth_walls), sum(stream_walls)
        # Attribution microbenches: where does a streamed-vs-synthetic gap
        # come from? Host-side reader throughput vs a bare device_put of
        # one batch. The background transfer thread plus deep prefetch
        # is what hides a slow blocking put behind the step.
        reader2 = ShardedRecordReader(
            [img_path], fmt="tokens", dtype=np.uint8, record_len=rec,
            batch_size=ibatch,
        )
        with reader2:
            t0 = time.perf_counter()
            nbytes = sum(b.nbytes for b in reader2)
            host_rate = nbytes / (time.perf_counter() - t0) / 1e6
        one = jnp.asarray(images[:ibatch].reshape(ibatch, size, size, 3))
        np.asarray(one.reshape(-1)[0])
        t0 = time.perf_counter()
        for _ in range(4):
            one = jax.device_put(
                images[:ibatch].reshape(ibatch, size, size, 3)
            )
        np.asarray(one.reshape(-1)[0])
        h2d_rate = 4 * ibatch * rec / (time.perf_counter() - t0) / 1e6
        out["resnet50"] = {
            "synthetic_step_ms": round(synth_dt / resnet_measure * 1000, 2),
            "streamed_step_ms": round(stream_dt / resnet_measure * 1000, 2),
            "overhead_pct": round((stream_dt / synth_dt - 1) * 100, 1),
            "disk_to_hbm_mb_per_sec": round(
                ibatch * rec * resnet_measure / stream_dt / 1e6, 1
            ),
            "host_reader_mb_per_sec": round(host_rate, 1),
            "h2d_device_put_mb_per_sec": round(h2d_rate, 1),
            "synthetic": _step_stats(synth_walls),
            "streamed": _step_stats(stream_walls),
            "io": _io_rates(io0, io1),
            "prefetch_depth": 8,
            "transfer_workers": 6,
            "batch": ibatch,
        }
    finally:
        _os.unlink(img_path)
    return out


def bench_flash_attention(seq: int, batch: int, heads: int = 8,
                          head_dim: int = 64, measure: int = 30):
    """Pallas flash kernel vs the blockwise-XLA fallback (force_jax=True),
    forward pass, causal self-attention."""
    from tony_tpu.ops import flash_attention

    rng = np.random.default_rng(0)
    shape = (batch, seq, heads, head_dim)
    q, k, v = (
        jnp.asarray(rng.normal(size=shape), jnp.bfloat16) for _ in range(3)
    )

    def timed(force_jax: bool) -> float:
        fn = jax.jit(
            # fold a reduction in so the timed fence is one scalar readback
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, force_jax=force_jax)
                .astype(jnp.float32)
            )
        )
        out = fn(q, k, v)
        float(out)  # fence
        t0 = time.perf_counter()
        for _ in range(measure):
            out = fn(q, k, v)
        float(out)
        return (time.perf_counter() - t0) / measure * 1000

    pallas_ms = timed(False)
    xla_ms = timed(True)
    return {
        "seq": seq,
        "batch": batch,
        "pallas_ms": round(pallas_ms, 3),
        "blockwise_xla_ms": round(xla_ms, 3),
        "speedup": round(xla_ms / pallas_ms, 2),
    }


# The per-job script bench_scheduler submits: compile one instrumented
# classifier step (the plan-keyed compile the warm pool's cache serves)
# and stamp the first-step completion time for submit-to-first-step.
_SCHED_JOB_SCRIPT = """\
import os, time
import tony_tpu.runtime as rt
ctx = rt.initialize()
import jax
import jax.numpy as jnp
import numpy as np
from tony_tpu.models import MnistConfig
from tony_tpu.models.train import make_classifier_step
from tony_tpu.parallel.mesh import MeshSpec, build_mesh

mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
init_fn, step_fn = make_classifier_step(
    MnistConfig(arch="cnn", dtype="float32"), mesh)
rng = np.random.default_rng(0)
images = jnp.asarray(rng.normal(size=(8, 28, 28, 1)), jnp.float32)
labels = jnp.asarray(rng.integers(0, 10, (8,)), jnp.int32)
state = init_fn(jax.random.key(0))
state, metrics = step_fn(state, images, labels)
float(metrics["loss"])
with open(os.environ["FIRST_STEP_OUT"], "w") as f:
    f.write(str(time.time()))
"""


def bench_scheduler(jobs: int = 3, provision_ms: int = 4000):
    """Multi-tenant scheduler warm-pool amortization: N identical jobs
    through one ``SchedulerDaemon`` on a 1-slice pool. Job 1 pays the
    full cold path (slice provisioning — modeled at ``provision_ms``,
    far below the minutes a real queued-resource create takes — plus a
    cold XLA compile); jobs 2..N lease the slice warm: provisioning
    skipped, compiles served from the slice's pool-owned cache. The
    headline is warm vs cold submit-to-first-step and jobs/hour over
    the drained batch."""
    import sys as _sys
    import tempfile as _tempfile
    from pathlib import Path as _Path

    if jobs < 2:
        raise ValueError("bench_scheduler needs >= 2 jobs: the warm "
                         "figure is jobs 2..N")

    from tony_tpu.conf import keys as _keys
    from tony_tpu.conf.configuration import TonyConfiguration
    from tony_tpu.scheduler import SchedulerDaemon
    from tony_tpu.scheduler.pool import (
        COLD_PROVISIONS_COUNTER, WARM_HITS_COUNTER,
    )

    with _tempfile.TemporaryDirectory(prefix="tony-bench-sched-") as root:
        d = _Path(root)
        script = d / "first_step.py"
        script.write_text(_SCHED_JOB_SCRIPT)
        conf = TonyConfiguration()
        conf.set(_keys.K_SCHED_TICK_MS, 50)
        conf.set(_keys.K_SCHED_MAX_SLICES, 1)
        conf.set(_keys.K_SCHED_LOCAL_PROVISION_MS, provision_ms)
        daemon = SchedulerDaemon(d / "sched", conf=conf).start(
            serve_http=False
        )
        # Executor children ALWAYS run on CPU: this bench measures the
        # orchestration layer (provision/staging/compile-cache
        # amortization), and on a TPU host the parent bench process
        # already holds the chip — libtpu is exclusive per host, so a
        # TPU child could never initialize anyway.
        platform = "cpu"
        lat_ms: list[float] = []
        t_batch0 = time.perf_counter()
        try:
            for i in range(jobs):
                c = TonyConfiguration()
                c.set(_keys.K_EXECUTES, str(script))
                c.set(_keys.K_PYTHON_BINARY, _sys.executable)
                c.set(_keys.instances_key("worker"), 1)
                c.set(_keys.instances_key("ps"), 0)
                # Children must land on the same backend the bench runs
                # on (a CPU bench box must not have executors probe TPUs).
                c.set(_keys.K_SHELL_ENV,
                      f"FIRST_STEP_OUT={d}/step-{i}.ts,"
                      f"JAX_PLATFORMS={platform}")
                t0 = time.time()
                job_id = daemon.submit(c)
                state = daemon.wait_job(job_id, timeout_s=600)
                ts_file = d / f"step-{i}.ts"
                if state.value != "SUCCEEDED" or not ts_file.is_file():
                    raise RuntimeError(
                        f"scheduler bench job {i} ended {state.value} "
                        f"without a first step"
                    )
                lat_ms.append((float(ts_file.read_text()) - t0) * 1000)
            wall_s = time.perf_counter() - t_batch0
            counters = daemon.registry.snapshot()["counters"]
        finally:
            daemon.shutdown()
    cold = lat_ms[0]
    warm = sum(lat_ms[1:]) / (len(lat_ms) - 1)
    warm_hits = counters.get(WARM_HITS_COUNTER, 0)
    provisions = counters.get(COLD_PROVISIONS_COUNTER, 0)
    return {
        "jobs": jobs,
        # A config parameter of the bench, not a measurement — named
        # WITHOUT the _ms suffix so the gate's direction heuristic
        # leaves it ungated (raising the model must not read as a
        # latency regression). Unit is milliseconds.
        "provision_model": provision_ms,
        "cold_submit_to_step_ms": round(cold, 1),
        "warm_submit_to_step_ms": round(warm, 1),
        "warm_cold_speedup": round(cold / warm, 3),
        "jobs_per_hour": round(jobs / (wall_s / 3600.0), 1),
        "warm_hit_rate": round(warm_hits / max(warm_hits + provisions, 1),
                               3),
        **_bench_scheduler_ha(),
    }


def _bench_scheduler_ha(queued_jobs: int = 8):
    """Control-plane HA sub-metrics for ``bench_scheduler``:

    * ``recovery_ms`` — a dead leader's base dir (journal seeded with
      ``queued_jobs`` queued submissions: exactly the bytes a SIGKILL
      leaves behind) to a fresh daemon's ``start()`` returning with the
      queue rebuilt and the first snapshot published. Recovery runs
      synchronously inside ``start()``, so the wall around it IS the
      SIGKILL-to-first-post-recovery-tick window.
    * ``failover_ms`` — an active/standby pair on one base dir; the
      leader dies the way SIGKILL kills it (loop stopped dead, flock
      dropped, heartbeat left to go stale un-renewed) to the standby
      holding the seat with recovery done.
    """
    import tempfile as _tempfile
    from pathlib import Path as _Path

    from tony_tpu.conf import keys as _keys
    from tony_tpu.conf.configuration import TonyConfiguration
    from tony_tpu.scheduler import SchedulerDaemon
    from tony_tpu.scheduler import journal as _wal
    from tony_tpu.scheduler.journal import SchedulerJournal

    out: dict[str, float] = {}
    with _tempfile.TemporaryDirectory(prefix="tony-bench-ha-") as root:
        base = _Path(root) / "sched"
        base.mkdir()
        j = SchedulerJournal(base / _wal.JOURNAL_FILE)
        now = int(time.time() * 1000)
        for i in range(queued_jobs):
            j.append(_wal.J_JOB_QUEUED, ts_ms=now,
                     job_id=f"job_{i + 1:04d}_bench",
                     app_dir=str(base / f"app-{i}"), priority=0,
                     tenant="default", submit_ms=now, seq_no=i + 1)
        conf = TonyConfiguration()
        conf.set(_keys.K_SCHED_TICK_MS, 50)
        # Zero slots: the recovered queue must REBUILD, not launch —
        # this measures the control plane, not executor spawn time.
        conf.set(_keys.K_SCHED_MAX_SLICES, 0)
        t0 = time.perf_counter()
        daemon = SchedulerDaemon(base, conf=conf).start(serve_http=False)
        recovery_ms = (time.perf_counter() - t0) * 1000
        restored = len(daemon._jobs)
        daemon.shutdown()
        if daemon.recovered_ms is None or restored != queued_jobs:
            raise RuntimeError(
                f"recovery bench restored {restored}/{queued_jobs} jobs"
            )
        out["recovery_ms"] = round(recovery_ms, 1)

        pair = _Path(root) / "pair"
        pair.mkdir()

        def _pair_conf(node: str) -> TonyConfiguration:
            c = TonyConfiguration()
            c.set(_keys.K_SCHED_TICK_MS, 50)
            c.set(_keys.K_SCHED_MAX_SLICES, 0)
            c.set(_keys.K_SCHED_HA_LEASE_MS, 600)
            c.set(_keys.K_SCHED_HA_NODE_ID, node)
            return c

        a = SchedulerDaemon(pair, conf=_pair_conf("bench-a")).start(
            serve_http=False
        )
        b = SchedulerDaemon(pair, conf=_pair_conf("bench-b")).start(
            serve_http=False
        )
        if not a.election.is_leader or b.election.is_leader:
            raise RuntimeError("failover bench pair did not settle "
                               "into active/standby")
        # Crash the leader the way SIGKILL does: loop stopped dead (no
        # clean release — the heartbeat goes stale un-renewed), then
        # the kernel drops the flock.
        a._stop.set()
        a._wake.set()
        a._thread.join(timeout=30)
        t1 = time.perf_counter()
        a.election.abandon()
        deadline = t1 + 30
        while time.perf_counter() < deadline:
            if b.election.is_leader and b.recovered_ms is not None:
                break
            time.sleep(0.005)
        failover_ms = (time.perf_counter() - t1) * 1000
        took_over = b.election.is_leader
        b.shutdown()
        if not took_over:
            raise RuntimeError("standby never took the seat")
        out["failover_ms"] = round(failover_ms, 1)
    return out


def bench_checkpoint(saves: int = 6, store_ms: int = 20,
                     train_gap_ms: int = 80):
    """Checkpoint pipeline amortization on the REAL lm_train optimizer
    tree (``make_train_step``'s TrainState: step + params + adamw
    moments, a couple of real steps run so the moments are populated).

    Three claims, each a gated sub-metric:

    * **save wall off the step path** — mean ``save(blocking=True)``
      wall vs the pipelined ``save()`` CALL wall against a store whose
      per-PUT latency is modeled at ``store_ms`` (a remote-object-store
      RTT; local-fs puts are too fast to show the effect the pipeline
      exists for). Between saves both arms "train" for a modeled
      ``train_gap_ms`` (the checkpoint-interval wall a real loop has —
      the window the pipeline persists inside; back-to-back saves
      would measure pure backpressure instead of the steady state).
      ``save_offpath_speedup`` is the ratio.
    * **differential bytes** — per-save shard bytes, full rewrites vs
      differential saves under a frozen-fine-tune update pattern (one
      third of the leaves mutated per save; the rest — frozen layers /
      untouched adam moments — byte-identical). ``full_over_diff_speedup``
      is the bytes ratio.
    * **commit lag** — ``commit_lag_ms``: last ``save()`` return → every
      submitted step committed (markers down), the window a crash can
      cost beyond the last marker.
    """
    import tempfile as _tempfile
    from pathlib import Path as _Path

    from tony_tpu.checkpoint import CheckpointManager
    from tony_tpu.models import TransformerConfig, make_train_step
    from tony_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg = TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, head_dim=16,
        d_ff=256, max_seq=64, dtype="float32", remat=False,
    )
    mesh = build_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    init_fn, step_fn = make_train_step(cfg, mesh)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (4, 65)), jnp.int32
    )
    with jax.sharding.set_mesh(mesh):
        state = init_fn(jax.random.key(0))
        for _ in range(2):  # populate the adam moments with real values
            state, _ = step_fn(state, tokens)

    leaves, treedef = jax.tree_util.tree_flatten(state)
    state_bytes = sum(
        np.asarray(leaf).nbytes for leaf in leaves
    )

    def mutate(tree, salt: float):
        """Frozen-fine-tune shape: every third leaf changes, the rest
        stay byte-identical (what a diff save may skip)."""
        flat, td = jax.tree_util.tree_flatten(tree)
        out = []
        for i, leaf in enumerate(flat):
            if i % 3 == 0 and jnp.issubdtype(leaf.dtype, jnp.floating):
                out.append(leaf + jnp.asarray(salt, leaf.dtype))
            else:
                out.append(leaf)
        return jax.tree_util.tree_unflatten(td, out)

    class _ModeledStore:
        """A store whose every PUT pays a modeled remote RTT."""

        def __init__(self, inner):
            self._inner = inner

        def put_file(self, step, name, data):
            time.sleep(store_ms / 1000.0)
            return self._inner.put_file(step, name, data)

        def __getattr__(self, attr):
            return getattr(self._inner, attr)

    def shard_bytes(root: _Path, step: int) -> int:
        return (root / f"step_{step}" / "process_0.npz").stat().st_size

    with _tempfile.TemporaryDirectory(prefix="tony-bench-ckpt-") as root:
        d = _Path(root)
        # Arm 1: full rewrites, blocking — the pre-pipeline step-path
        # cost (snapshot + encode + 3 modeled PUTs on the caller).
        full_dir = d / "full"
        mgr_full = CheckpointManager(full_dir, differential=False,
                                     max_to_keep=saves + 2)
        mgr_full._store = _ModeledStore(mgr_full._store)
        cur = state
        blocking_ms = []
        for i in range(1, saves + 1):
            cur = mutate(cur, float(i))
            t0 = time.perf_counter()
            mgr_full.save(i, cur, blocking=True)
            blocking_ms.append((time.perf_counter() - t0) * 1000.0)
            time.sleep(train_gap_ms / 1000.0)
        bytes_full = shard_bytes(full_dir, saves)
        # Arm 2: differential saves through the pipeline — the call wall
        # is what the train loop pays; commit runs behind it.
        diff_dir = d / "diff"
        mgr_diff = CheckpointManager(diff_dir, differential=True,
                                     full_every=10**6, pipeline_depth=2,
                                     max_to_keep=saves + 2)
        mgr_diff._store = _ModeledStore(mgr_diff._store)
        cur = state
        call_ms = []
        for i in range(1, saves + 1):
            cur = mutate(cur, float(i))
            t0 = time.perf_counter()
            mgr_diff.save(i, cur)
            call_ms.append((time.perf_counter() - t0) * 1000.0)
            time.sleep(train_gap_ms / 1000.0)
        t_drain = time.perf_counter()
        while mgr_diff.last_committed_step != saves:
            if time.perf_counter() - t_drain > 120:
                raise RuntimeError("checkpoint pipeline never drained")
            time.sleep(0.001)
        commit_lag_ms = (time.perf_counter() - t_drain) * 1000.0
        mgr_diff.wait()
        bytes_diff = shard_bytes(diff_dir, saves)

    blocking = sum(blocking_ms) / len(blocking_ms)
    # Backpressured calls (depth exceeded) are real step-path cost and
    # stay in the mean on purpose.
    call = sum(call_ms) / len(call_ms)
    return {
        "saves": saves,
        # Modeled per-PUT store latency and per-interval training wall
        # — bench parameters, named WITHOUT unit suffixes so the gate's
        # direction heuristic leaves them ungated. Unit: milliseconds.
        "store_model": store_ms,
        "train_gap_model": train_gap_ms,
        "state_mb": round(state_bytes / 1e6, 3),
        "blocking_save_ms": round(blocking, 2),
        "pipeline_save_call_ms": round(call, 2),
        "save_offpath_speedup": round(blocking / max(call, 1e-6), 2),
        "full_save_kb": round(bytes_full / 1024.0, 1),
        "diff_save_kb": round(bytes_diff / 1024.0, 1),
        "full_over_diff_speedup": round(bytes_full / max(bytes_diff, 1),
                                        2),
        "commit_lag_ms": round(commit_lag_ms, 1),
    }


def bench_autotune(trial_budget: int = 4):
    """The measured autotuner's loop, closed and gated (two claims):

    - ``tuned_over_default_speedup``: a COLD ``tune_train_step`` search
      over the remat candidates of a tiny lm config. The ratio is >= 1.0
      by construction (the default is ``candidates[0]`` and the winner
      is the min over all trials including it), so the 1.0 baseline
      gates the search *machinery* — a broken ranking, a default that
      stopped being measured, or a record whose winner loses to its own
      default all read as a regression.
    - ``search_trials_warm``: the SAME call again must be answered from
      the persisted record with ZERO new measurements — the warm-reuse
      analog of the compile-cache hits==2/misses==0 gate.
    """
    import tempfile as _tempfile

    from tony_tpu.models import TransformerConfig
    from tony_tpu.parallel import autotune
    from tony_tpu.parallel.mesh import MeshSpec, build_mesh

    cfg = TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=2, head_dim=32,
        d_ff=256, max_seq=128, dtype="float32", remat=False,
    )
    mesh = build_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    with _tempfile.TemporaryDirectory(prefix="tony-bench-tune-") as td:
        rec_cold = autotune.tune_train_step(
            cfg, mesh, global_batch=4, seq=64,
            trial_budget=trial_budget, cache_dir=td,
        )
        rec_warm = autotune.tune_train_step(
            cfg, mesh, global_batch=4, seq=64,
            trial_budget=trial_budget, cache_dir=td,
        )
    speedup = (
        rec_cold["default_ms"] / rec_cold["best_ms"]
        if rec_cold.get("best_ms") and rec_cold.get("default_ms")
        else float("nan")
    )
    return {
        "tuned_over_default_speedup": round(speedup, 3),
        "search_trials_warm": rec_warm["trials_this_run"],
        # Bench parameters / context, named without unit suffixes so the
        # direction heuristic leaves them ungated.
        "search_trials_cold": rec_cold["trials_this_run"],
    }


def bench_rollup(targets: int = 24, tasks_per_target: int = 8,
                 ticks: int = 12, queries: int = 200):
    """The fleet rollup's control-plane costs at synthetic-fleet scale,
    hermetic (injected scrape documents, no HTTP, no jobs):

    - ``scrape_fan_in_ms``: one full scrape pass over every target's
      /api/metrics document (parse + normalize, the per-tick fan-in);
    - ``rollup_tick_ms``: mean full tick — scrape, fold (counter deltas,
      gauge folds, histogram merges across all scopes), TSDB record,
      SLO evaluation;
    - ``query_p95_ms``: p95 of range reads against the populated store
      (the /api/query path a dashboard hammers);
    - ``series_bytes_on_disk`` / ``series``: store shape after a
      checkpoint, ungated context numbers.

    Counters advance and gauges wobble per tick so the fold exercises
    the delta path, not the first-sight shortcut."""
    import tempfile as _tempfile

    from tony_tpu.observability.events import EventLog
    from tony_tpu.observability.goodput import GOODPUT_RATIO_GAUGE
    from tony_tpu.observability.rollup import FleetRollup, SloObjective, Target
    from tony_tpu.observability.stepstats import MFU_GAUGE
    from tony_tpu.observability.tsdb import TimeSeriesStore
    from tony_tpu.serving.scheduler import SERVING_TTFT_MS_HISTOGRAM

    bounds = [float(2 ** i) for i in range(16)]
    tick_state = {"n": 0}

    def doc_for(idx: int) -> dict:
        n = tick_state["n"]
        hist = {
            "count": 100 * (n + 1),
            "sum": 2500.0 * (n + 1),
            "buckets": [[b, min(100 * (n + 1), int(b) * (n + 1))]
                        for b in bounds],
        }
        tasks = {
            f"worker:{t}": {
                "counters": {"train_steps_total": 50.0 * n + t},
                "gauges": {"loss": 1.0 / (n + 1), MFU_GAUGE: 0.5,
                           "tokens_per_sec": 900.0 + t},
                "histograms": {},
            }
            for t in range(tasks_per_target)
        }
        return {
            "coordinator": {
                "counters": {"train_steps_total": 50.0 * n * tasks_per_target},
                "gauges": {GOODPUT_RATIO_GAUGE: 0.8 + 0.01 * (idx % 10)},
                "histograms": {SERVING_TTFT_MS_HISTOGRAM: hist},
            },
            "heartbeats": {f"worker:{t}": float(n + 1)
                           for t in range(tasks_per_target)},
            "heartbeat_age_s": {f"worker:{t}": 0.5
                                for t in range(tasks_per_target)},
            "tasks": tasks,
        }

    fleet = [Target(f"job{i}", "job", f"host:{i}",
                    tenant=f"tenant{i % 4}") for i in range(targets)]

    def fetch(url: str, timeout_s: float) -> dict:
        idx = int(url.split("host:")[1].split("/")[0])
        return doc_for(idx)

    base_ms = 1_700_000_400_000
    with _tempfile.TemporaryDirectory(prefix="tony-bench-rollup-") as td:
        rollup = FleetRollup(
            None,
            tsdb=TimeSeriesStore(td),
            events=EventLog(),
            objectives=[SloObjective(
                "goodput", "tony_goodput_ratio|fleet", "min", 0.9
            )],
            fast_window_s=60, slow_window_s=300,
            fetch_json=fetch,
        )
        rollup.discover_targets = lambda: list(fleet)

        t0 = time.perf_counter()
        scraped = [rollup._scrape(t) for t in fleet]
        fan_in_ms = (time.perf_counter() - t0) * 1e3
        assert all(s is not None for s in scraped)

        walls = []
        for n in range(ticks):
            tick_state["n"] = n
            t0 = time.perf_counter()
            rollup.tick(now_ms=base_ms + n * 15_000)
            walls.append((time.perf_counter() - t0) * 1e3)

        names = rollup.tsdb.names()
        q_walls = []
        for i in range(queries):
            series = names[i % len(names)]
            name, _, scope = series.rpartition("|")
            t0 = time.perf_counter()
            rollup.query_series(name, agg="avg", scope=scope,
                                since_s=3600, step_s=60)
            q_walls.append((time.perf_counter() - t0) * 1e3)
        q_walls.sort()

        rollup.tsdb.checkpoint()
        stats = rollup.tsdb.stats()

    return {
        "scrape_fan_in_ms": round(fan_in_ms, 2),
        "rollup_tick_ms": round(sum(walls) / len(walls), 2),
        "query_p95_ms": round(q_walls[int(len(q_walls) * 0.95)], 3),
        # Shape / context, named without direction suffixes (ungated).
        "targets": targets,
        "series": stats["series"],
        "series_bytes_on_disk": stats["disk_bytes"],
    }


# ---------------------------------------------------------------------------
# Regression gate (`bench.py --check`)
# ---------------------------------------------------------------------------
# Earlier rounds let real regressions sail through because only the
# headline mnist number was eyeballed (mnist, resnet50 and the flash 2k
# speedup all slid unnoticed). The gate makes every SUB-metric first-class: a baseline
# per metric per platform persists in BASELINE.json, and any >10% drop
# exits nonzero.

BASELINE_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BASELINE.json")
BASELINE_KEY = "bench_baselines"  # platform -> {metric path -> value}
DEFAULT_THRESHOLD = 0.10

# Direction by name suffix. Anything matching neither list is a shape /
# config parameter (batch, seq, params_m, ...) and is not gated.
_HIGHER_SUFFIXES = ("per_sec", "per_sec_per_chip", "mfu", "speedup",
                    "mb_per_sec", "vs_baseline", "per_hour", "hit_rate")
_LOWER_SUFFIXES = ("_ms", "_pct", "ms_mean", "step_ms", "p50_ms", "p95_ms",
                   "retraces_total", "trials_warm")


def metric_direction(name: str) -> str | None:
    """'higher' / 'lower' / None (ungated parameter)."""
    leaf = name.rsplit(".", 1)[-1]
    if leaf == "mfu" or leaf.endswith(_HIGHER_SUFFIXES):
        return "higher"
    if leaf.endswith(_LOWER_SUFFIXES):
        return "lower"
    return None


def collect_submetrics(line: dict) -> dict[str, float]:
    """Flatten one bench JSON line into {dotted.path: value} for every
    gated (direction-carrying, numeric, finite) sub-metric. Errored
    extras (`{"error": ...}` from _safe) contribute nothing — their
    metrics go MISSING, which --check reports as a failure rather than
    silently shrinking the gate."""
    out: dict[str, float] = {}

    def walk(node, path: str) -> None:
        if isinstance(node, dict):
            if "error" in node:
                return
            for k, v in node.items():
                walk(v, f"{path}.{k}" if path else str(k))
            return
        if isinstance(node, bool) or not isinstance(node, (int, float)):
            return
        if metric_direction(path) and np.isfinite(node):
            out[path] = float(node)

    if isinstance(line.get("value"), (int, float)):
        out["mnist_train_steps_per_sec_per_chip"] = float(line["value"])
    walk(line.get("extras", {}), "")
    return out


def check_regressions(
    current: dict[str, float],
    baseline: dict[str, float],
    threshold: float = DEFAULT_THRESHOLD,
) -> list[str]:
    """Every baseline metric that regressed past ``threshold`` (or went
    missing), as human-readable complaints. Empty list = gate passes.
    Metrics present only in ``current`` are new and pass free — run
    --update-baseline to start gating them."""
    problems: list[str] = []
    for name in sorted(baseline):
        base = baseline[name]
        if name not in current:
            problems.append(f"{name}: missing from this run "
                            f"(baseline {base:g})")
            continue
        cur = current[name]
        direction = metric_direction(name) or "higher"
        if base == 0:
            # A zero baseline on a lower-is-better COUNT is absolute:
            # "the steady-state step never re-traces" — any non-zero
            # current is a regression, no threshold to scale against.
            if direction == "lower" and cur > 0:
                problems.append(
                    f"{name}: {cur:g} regressed from a zero baseline "
                    f"(was clean, now is not)"
                )
            continue  # ratio gates need a non-zero base to scale against
        if direction == "higher" and cur < base * (1 - threshold):
            problems.append(
                f"{name}: {cur:g} is {(1 - cur / base) * 100:.1f}% below "
                f"baseline {base:g}"
            )
        elif direction == "lower" and cur > base * (1 + threshold):
            # Percent-point metrics near zero (a 1.3% io overhead) would
            # otherwise gate on fractions of a point — pure noise. They
            # get 5 points of absolute slack on top of the ratio.
            if name.endswith("_pct") and cur - base <= 5.0:
                continue
            problems.append(
                f"{name}: {cur:g} is {(cur / base - 1) * 100:.1f}% above "
                f"baseline {base:g}"
            )
    return problems


def load_baselines(path: str = BASELINE_FILE) -> dict:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    table = doc.get(BASELINE_KEY, {})
    return table if isinstance(table, dict) else {}


def save_baselines(platform: str, metrics: dict[str, float],
                   path: str = BASELINE_FILE) -> None:
    """Merge this platform's baselines into BASELINE.json — per METRIC,
    not per platform: a partial-workload run (`--update-baseline` after
    a resnet-only re-measure) must refresh only the metrics it produced,
    never silently drop the transformer/decode/flash gates it didn't run
    (that would reopen exactly the silent-regression window the gate
    closes). Other keys in the file — north star, configs — pass through
    untouched. Retire a truly dead metric by hand-editing the file."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {}
    table = doc.setdefault(BASELINE_KEY, {}).setdefault(platform, {})
    table.update(metrics)
    doc[BASELINE_KEY][platform] = {k: table[k] for k in sorted(table)}
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=False)
        f.write("\n")
    os.replace(tmp, path)


def _bench_platform() -> str:
    d = jax.devices()[0]
    return d.device_kind or d.platform


def _safe(fn, *args, **kwargs):
    """One extra must not sink the whole bench line: the driver records
    exactly one JSON object per round, so a failure in a single extra
    degrades to an inline error string instead of losing every other
    number — and ``main`` exits non-zero when any extra carries one.

    With the jit sanitizer armed (the bench default), each workload's
    extras additionally carry ``retraces_total`` — the re-traces its
    instrumented dispatches incurred, measured as a tracker delta around
    the workload. Gated as a lower-is-better metric: a steady-state
    workload's baseline is 0, so ONE silent recompile fails --check."""
    from tony_tpu.analysis import jit_sanitizer

    armed = jit_sanitizer.enabled()
    before = jit_sanitizer.tracker().retraces() if armed else 0
    try:
        out = fn(*args, **kwargs)
    except Exception as exc:  # recorded, never raised
        return {"error": f"{type(exc).__name__}: {exc}"[:300]}
    if armed and isinstance(out, dict) and "error" not in out:
        out.setdefault(
            "retraces_total", jit_sanitizer.tracker().retraces() - before
        )
    return out


def run_benches() -> dict:
    steps_per_sec_per_chip = bench_mnist()
    if jax.devices()[0].platform == "tpu":
        extras = {
            "transformer": _safe(bench_transformer),
            "transformer_long_context": _safe(
                bench_transformer, batch=2, seq=8192, measure=6
            ),
            # TPU-first flagship long-context shape: head_dim 128 (same
            # d_model/params) fills the 128-deep MXU contraction the d=64
            # rows leave half-empty — see bench_transformer's docstring.
            # The d=64 rows above stay for comparability.
            "transformer_hd128": _safe(
                bench_transformer, measure=12, n_heads=8, head_dim=128
            ),
            "transformer_long_context_hd128": _safe(
                bench_transformer, batch=2, seq=8192, measure=6,
                n_heads=8, head_dim=128,
            ),
            "transformer_16k_hd128": _safe(
                bench_transformer, batch=1, seq=16384, measure=5,
                n_heads=8, head_dim=128,
            ),
            "transformer_1b": _safe(bench_transformer_1b),
            "resnet50": _safe(bench_resnet50),
            "decode_gqa": _safe(bench_decode),
            "serving": _safe(bench_serving),
            "serving_fleet": _safe(bench_serving_fleet),
            "moe": _safe(bench_moe),
            "moe_decode": _safe(bench_moe_decode),
            "input_pipeline": _safe(bench_input_pipeline),
            "scheduler": _safe(bench_scheduler),
            "checkpoint": _safe(bench_checkpoint),
            "autotune": _safe(bench_autotune),
            "rollup": _safe(bench_rollup),
            "flash_attention_2k": _safe(
                bench_flash_attention, seq=2048, batch=4
            ),
            "flash_attention_8k": _safe(
                bench_flash_attention, seq=8192, batch=1
            ),
            "device": jax.devices()[0].device_kind,
        }
        # The default-config vs hd128 MFU gap (ROADMAP: 0.53 vs 0.65 —
        # the half-filled MXU tax): a derived, GATED sub-metric so
        # closing (or reopening) the gap moves --check, instead of
        # hiding in a side-by-side read of two rows.
        t = extras.get("transformer")
        t128 = extras.get("transformer_hd128")
        if (isinstance(t, dict) and isinstance(t128, dict)
                and t.get("mfu") and t128.get("mfu")):
            extras["mfu_gap"] = {
                "default_over_hd128_mfu": round(t["mfu"] / t128["mfu"], 4)
            }
    else:
        # CPU smoke stays seconds, not hours: the 200M transformer and the
        # 8k attention sweeps are TPU-only. The serving engine's micro
        # variant DOES run here — its acceptance figure (continuous
        # batching vs single-shot) is a ratio, portable across hosts.
        extras = {"skipped": "transformer/flash extras are TPU-only",
                  "serving": _safe(bench_serving, **SERVING_CPU_MICRO),
                  "serving_fleet": _safe(bench_serving_fleet),
                  "scheduler": _safe(bench_scheduler),
                  "checkpoint": _safe(bench_checkpoint),
                  "autotune": _safe(bench_autotune),
                  "rollup": _safe(bench_rollup),
                  "device": jax.devices()[0].device_kind}
    # Final aggregated telemetry snapshot (observability.metrics): the
    # instrumented train steps populate the default registry while the
    # benches above run, so the perf trajectory picks up the
    # dispatch-count/step-time series for free alongside the headline
    # numbers.
    from tony_tpu import observability

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "metric": "mnist_train_steps_per_sec_per_chip",
        "value": round(steps_per_sec_per_chip, 2),
        "unit": f"steps/sec/chip (batch={BATCH}, cnn, adam)",
        "vs_baseline": round(
            steps_per_sec_per_chip / BASELINE_STEPS_PER_SEC_PER_CHIP, 3
        ),
        "extras": extras,
        "metrics": observability.default_registry().summary(),
    }


def _load_line(path: str) -> dict:
    """A bench line from a file: either a bare JSON object or the last
    JSON-parseable line of a log (the driver's record format)."""
    with open(path) as f:
        text = f.read()
    try:
        return json.loads(text)
    except ValueError:
        pass
    for raw in reversed(text.splitlines()):
        raw = raw.strip()
        if raw.startswith("{"):
            try:
                return json.loads(raw)
            except ValueError:
                continue
    raise ValueError(f"no JSON bench line found in {path}")


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        description="tony_tpu benchmark harness / perf-regression gate"
    )
    p.add_argument("--check", action="store_true",
                   help="compare sub-metrics against the persisted "
                        "baseline; exit 1 on any >threshold drop")
    p.add_argument("--update-baseline", action="store_true",
                   help="persist this run's sub-metrics as the new "
                        "baseline for this platform")
    p.add_argument("--input", metavar="PATH",
                   help="use an existing bench JSON line instead of "
                        "running the benches")
    p.add_argument("--baseline", default=BASELINE_FILE,
                   help=f"baseline file (default {BASELINE_FILE})")
    p.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                   help="fractional regression tolerance (default 0.10)")
    args = p.parse_args(argv)

    rc = 0
    if args.input:
        line = _load_line(args.input)
    else:
        # Warm persistent compile cache: repeat bench invocations (the
        # per-PR driver rounds) skip every XLA compile that the model
        # zoo's plan-instrumented steps share with a prior round.
        from tony_tpu.parallel.plan import configure_compile_cache

        configure_compile_cache()
        line = run_benches()
        print(json.dumps(line))
        # A phase that failed in THIS run fails the run (a line read
        # back with --input is only gated, below).
        for name, extra in sorted(line["extras"].items()):
            if isinstance(extra, dict) and "error" in extra:
                print(f"bench: {name} FAILED: {extra['error']}",
                      file=sys.stderr)
                rc = 1

    if not (args.check or args.update_baseline):
        return rc

    platform = (line.get("extras") or {}).get("device") or _bench_platform()
    current = collect_submetrics(line)
    # Check BEFORE update: `--check --update-baseline` must gate against
    # the PRIOR baseline (update-first would make the check vacuous and
    # bless the very regression it was asked to catch).
    if args.check:
        baseline = load_baselines(args.baseline).get(platform, {})
        if not baseline:
            print(f"bench --check: no baseline for platform {platform!r} "
                  f"in {args.baseline}; run --update-baseline first",
                  file=sys.stderr)
        else:
            problems = check_regressions(current, baseline, args.threshold)
            for prob in problems:
                print(f"bench --check: REGRESSION {prob}", file=sys.stderr)
            if problems:
                rc = 1
            else:
                print(f"bench --check: {len(baseline)} gated metrics "
                      f"within {args.threshold * 100:.0f}% of baseline "
                      f"({platform})", file=sys.stderr)
    if args.update_baseline:
        save_baselines(platform, current, args.baseline)
        print(f"bench: baseline for {platform!r} updated "
              f"({len(current)} metrics) in {args.baseline}",
              file=sys.stderr)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
