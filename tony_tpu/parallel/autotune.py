"""Measured program autotuner: close the MFU gap the planner can't.

``plan_for`` picks the *mesh* — which axes, how many ways, which trunk.
This module tunes the *program* on that mesh: the knobs the planner
takes as fixed and whose measured best was, on the earlier platform,
worth 10–40% of a step (flash block sizes at 2k, head_dim 64 vs 128,
the decode loop's wall vs its marginal step). The knobs:

* Pallas flash-attention ``(block_q, block_k)`` — the generalized
  ``tools/sweep_flash_blocks.py`` wall stage (the kernel-trace sweeps
  stay in the tool; per-kernel durations miss inter-kernel pipelining,
  so only the WALL fwd+bwd measurement decides a pin);
* remat policy (``full`` vs ``dots``) — recompute-vs-HBM, numerics
  unchanged;
* pipeline microbatch count and schedule;
* buffer donation;
* an XLA flag set, stored per record and applied before backend init.

Results persist as one JSON record per tune key in a
``tony-tune-records/`` directory BESIDE the PR-6 compile cache (same
remote-URI sidecar mirroring, same atomic tmp+rename writes) with the
same degrade-to-miss contract as ``plan-measurements.json``: a missing,
torn, corrupt, or stale-keyed record reads as "never searched" — one
re-search is the cost of a wrong miss, a crash would cost the job. The
tune key rides ``plan_cache_key`` and therefore the backend
fingerprint, so a jax-version bump or topology change invalidates a
record structurally instead of serving a stale pin.

Fleet semantics: retries / resumes / re-submits land on the same record
dir (``tony.tune.record-dir``, default beside the compile cache) and
reuse the persisted winner with ZERO search trials — the warm-reuse
counter is a gated bench sub-metric, analogous to compile-cache
hits==2/misses==0. In production the PR-10 stepstats calibration loop
feeds live best step walls back into the record (``note_step_time``),
so tuning keeps improving after the offline search.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from dataclasses import dataclass
from typing import Any, Callable, Iterable, Mapping, Sequence

from tony_tpu.parallel import plan as plan_lib

# Metric names (rendered on /metrics, summarized into bench lines and
# the history panel). Registered lazily, like plan.py's compile-cache
# counters: importing this module never touches the registry.
TUNE_SEARCH_TRIALS_COUNTER = "tony_tune_search_trials_total"
TUNE_RECORD_HITS_COUNTER = "tony_tune_record_hits_total"
TUNE_RECORD_MISSES_COUNTER = "tony_tune_record_misses_total"
TUNE_SEARCH_MS_HISTOGRAM = "tony_tune_search_ms"

# Searches run seconds to minutes (each trial pays a compile), so the
# buckets match tony_compile_ms's scale, not the Prometheus default.
_SEARCH_BUCKETS = (
    100.0, 500.0, 1000.0, 5000.0, 15000.0, 60000.0, 300000.0, 1800000.0,
)

# Subdirectory holding one JSON record per tune key, beside the XLA
# artifact cache (or its local sidecar for remote gs:// caches).
_TUNE_DIR = "tony-tune-records"
_RECORD_VERSION = 1


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_str(name: str, default: str) -> str:
    return os.environ.get(name, "") or default


def enabled() -> bool:
    """Consumption switch (``tony.tune.enabled`` → ``TONY_TUNE_ENABLED``):
    when off, ``lookup`` always misses and nothing is applied. The
    search entry points stay callable either way (an operator running
    ``tune_train_step`` by hand asked for it explicitly)."""
    from tony_tpu import constants

    return plan_lib._env_bool(constants.TONY_TUNE_ENABLED, True)


def default_trial_budget() -> int:
    from tony_tpu import constants

    return max(1, _env_int(constants.TONY_TUNE_TRIAL_BUDGET, 12))


# ---------------------------------------------------------------------------
# Knobs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Knobs:
    """One point in the program-tuning space. ``None`` means "leave the
    stack's default" — a record whose winning knobs are all-None is a
    measured confirmation that the defaults already win. ``xla_flags``
    is stored per record but only applied by ``apply_xla_flags`` before
    backend init (flags cannot retarget a live backend)."""

    block_q: int | None = None
    block_k: int | None = None
    remat_policy: str | None = None
    microbatches: int | None = None
    pipeline_schedule: str | None = None
    donate_state: bool | None = None
    xla_flags: tuple = ()

    def describe(self) -> dict[str, Any]:
        """Only the knobs this point actually sets (CLI/panel display)."""
        out = {
            k: v for k, v in dataclasses.asdict(self).items()
            if v is not None and v != ()
        }
        if "xla_flags" in out:
            out["xla_flags"] = list(out["xla_flags"])
        return out


def knobs_from_dict(raw: Mapping[str, Any] | None) -> Knobs:
    """A ``Knobs`` from a persisted record's dict, ignoring unknown
    fields (an older tony reading a newer record must not crash)."""
    if not isinstance(raw, Mapping):
        return Knobs()
    fields = {f.name for f in dataclasses.fields(Knobs)}
    kept = {k: v for k, v in raw.items() if k in fields}
    if isinstance(kept.get("xla_flags"), list):
        kept["xla_flags"] = tuple(kept["xla_flags"])
    try:
        return Knobs(**kept)
    except TypeError:
        return Knobs()


# ---------------------------------------------------------------------------
# Record persistence (degrade-to-miss, like plan-measurements.json)
# ---------------------------------------------------------------------------


def tune_key(
    label: str,
    *,
    config: Any = None,
    mesh=None,
    extra: Mapping[str, Any] | None = None,
    backend: Mapping[str, Any] | None = None,
) -> str:
    """The identity a tune record is valid for: (label, model config,
    mesh topology, backend fingerprint incl. jax version, caller
    extras). Rides ``plan_cache_key`` so tune records and compiled
    executables invalidate on exactly the same axes."""
    return plan_lib.plan_cache_key(
        label, config=config, mesh=mesh, extra=extra, backend=backend
    )


def record_dir(cache_dir: str | None = None) -> str | None:
    """Where tune records live: ``tony.tune.record-dir`` when set, else
    beside the active (or default) compile cache — remote URIs get the
    same local sidecar mirror the plan measurement table uses.
    None when the directory cannot be created (degrade to miss)."""
    from tony_tpu import constants

    base = cache_dir or _env_str(constants.TONY_TUNE_RECORD_DIR, "")
    if not base:
        base = plan_lib.active_cache_dir() or plan_lib.default_cache_dir()
    base = os.path.expanduser(base)
    if plan_lib._is_remote_uri(base):
        base = plan_lib._local_sidecar_dir(base)
    path = os.path.join(base, _TUNE_DIR)
    try:
        os.makedirs(path, exist_ok=True)
    except OSError:
        return None
    return path


def _record_path(key: str, cache_dir: str | None = None) -> str | None:
    base = record_dir(cache_dir)
    if base is None or not key:
        return None
    return os.path.join(base, f"{key}.json")


def load_record(key: str, *,
                cache_dir: str | None = None) -> dict[str, Any] | None:
    """The persisted record for ``key``, or None. EVERY failure mode —
    absent file, torn write, corrupt JSON, a record whose embedded key
    disagrees (a dir moved wholesale across keys), a version this tony
    doesn't speak — reads as a miss, never a crash and never a stale
    record served as fresh."""
    path = _record_path(key, cache_dir)
    if path is None:
        return None
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(data, dict):
        return None
    if data.get("key") != key or data.get("version") != _RECORD_VERSION:
        return None
    if not isinstance(data.get("best"), dict):
        return None
    return data


def save_record(record: Mapping[str, Any], *,
                cache_dir: str | None = None) -> None:
    """Atomic tmp+rename write (concurrent writers each land a complete
    file; last rename wins — both are valid records for the same key, so
    either outcome is correct). Unwritable dir: the search result is
    simply not persisted — the next process re-searches."""
    path = _record_path(str(record.get("key", "")), cache_dir)
    if path is None:
        return
    try:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(dict(record), f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass


def list_records(cache_dir: str | None = None) -> list[dict[str, Any]]:
    """Every valid record in the dir (invalid files skipped), for the
    ``tony tune`` CLI and the history panel."""
    base = record_dir(cache_dir)
    if base is None:
        return []
    out: list[dict[str, Any]] = []
    try:
        names = sorted(os.listdir(base))
    except OSError:
        return []
    for name in names:
        if not name.endswith(".json") or ".tmp." in name:
            continue
        key = name[:-len(".json")]
        rec = load_record(key, cache_dir=cache_dir)
        if rec is not None:
            out.append(rec)
    return out


# ---------------------------------------------------------------------------
# Search core
# ---------------------------------------------------------------------------


def _registry():
    from tony_tpu import observability

    return observability.default_registry()


# Re-entrancy guard: measurement trials build real train steps, and
# make_train_step consults lookup() — a trial must measure the CANDIDATE
# knobs, not a half-written record's.
_IN_SEARCH = False


def search(
    label: str,
    candidates: Sequence[Knobs],
    measure: Callable[[Knobs], float],
    *,
    key: str,
    trial_budget: int | None = None,
    cache_dir: str | None = None,
    force: bool = False,
) -> dict[str, Any]:
    """The one search loop every stage shares: warm-check the persisted
    record (hit → return it with ``trials_this_run == 0``), else measure
    up to ``trial_budget`` candidates and persist the winner.

    ``candidates[0]`` is the DEFAULT point by convention (usually
    ``Knobs()``): it is always measured first, so every record carries a
    ``default_ms`` and the tuned-over-default ratio the bench gates. A
    trial that raises or returns a non-finite/non-positive wall is
    recorded as failed and excluded from the ranking."""
    global _IN_SEARCH
    if trial_budget is None:
        trial_budget = default_trial_budget()
    trial_budget = max(1, int(trial_budget))
    reg = _registry()
    if not force:
        rec = load_record(key, cache_dir=cache_dir)
        if rec is not None:
            reg.counter(TUNE_RECORD_HITS_COUNTER).inc()
            rec = dict(rec)
            rec["trials_this_run"] = 0
            return rec
    reg.counter(TUNE_RECORD_MISSES_COUNTER).inc()

    trials: list[dict[str, Any]] = []
    best_ms = math.inf
    best = Knobs()
    default_ms: float | None = None
    t_search = time.perf_counter()
    was_in_search, _IN_SEARCH = _IN_SEARCH, True
    try:
        for knobs in list(candidates)[:trial_budget]:
            reg.counter(TUNE_SEARCH_TRIALS_COUNTER).inc()
            try:
                ms = float(measure(knobs))
            except Exception as exc:  # a failed point is data, not a crash
                trials.append({"knobs": knobs.describe(),
                               "error": f"{type(exc).__name__}: {exc}"[:200]})
                continue
            if not math.isfinite(ms) or ms <= 0:
                trials.append({"knobs": knobs.describe(), "error": "non-finite"})
                continue
            trials.append({"knobs": knobs.describe(), "ms": round(ms, 3)})
            if default_ms is None:
                default_ms = ms
            if ms < best_ms:
                best_ms, best = ms, knobs
    finally:
        _IN_SEARCH = was_in_search
    search_ms = (time.perf_counter() - t_search) * 1000.0
    reg.histogram(
        TUNE_SEARCH_MS_HISTOGRAM, buckets=_SEARCH_BUCKETS
    ).observe(search_ms)

    record: dict[str, Any] = {
        "version": _RECORD_VERSION,
        "key": key,
        "label": label,
        "backend": plan_lib._canonical(plan_lib.backend_fingerprint()),
        "best": dataclasses.asdict(best) | {
            "xla_flags": list(best.xla_flags)
        },
        "best_ms": round(best_ms, 3) if math.isfinite(best_ms) else None,
        "default_ms": (
            round(default_ms, 3) if default_ms is not None else None
        ),
        "trials": trials,
        "search_ms": round(search_ms, 1),
        "ts_ms": int(time.time() * 1000),
    }
    if math.isfinite(best_ms):
        save_record(record, cache_dir=cache_dir)
    record["trials_this_run"] = len(trials)
    return record


def lookup(
    label: str,
    *,
    config: Any = None,
    mesh=None,
    extra: Mapping[str, Any] | None = None,
    cache_dir: str | None = None,
) -> Knobs | None:
    """Consumption side: the winning knobs for this (label, config,
    topology, jax version), or None on any miss / while a search is
    measuring / when tuning is disabled. Free to call on every program
    build — one small JSON read."""
    if _IN_SEARCH or not enabled():
        return None
    rec = load_record(
        tune_key(label, config=config, mesh=mesh, extra=extra),
        cache_dir=cache_dir,
    )
    reg = _registry()
    if rec is None:
        reg.counter(TUNE_RECORD_MISSES_COUNTER).inc()
        return None
    reg.counter(TUNE_RECORD_HITS_COUNTER).inc()
    return knobs_from_dict(rec.get("best"))


def note_step_time(
    label: str,
    *,
    config: Any = None,
    mesh=None,
    extra: Mapping[str, Any] | None = None,
    step_ms: float,
    cache_dir: str | None = None,
) -> None:
    """Production feedback (PR-10 stepstats calibration loop): fold a
    live best step wall into the persisted record's ``live_best_ms`` so
    the record keeps learning after the offline search. Telemetry
    semantics — every failure is silent, throttling is the caller's
    (stepstats already rate-limits to real improvements)."""
    if not math.isfinite(step_ms) or step_ms <= 0:
        return
    key = tune_key(label, config=config, mesh=mesh, extra=extra)
    rec = load_record(key, cache_dir=cache_dir)
    if rec is None:
        return
    prev = rec.get("live_best_ms")
    if isinstance(prev, (int, float)) and step_ms >= float(prev):
        return
    rec["live_best_ms"] = round(float(step_ms), 3)
    save_record(rec, cache_dir=cache_dir)


def apply_xla_flags(knobs: Knobs) -> bool:
    """Append a record's XLA flag set to ``XLA_FLAGS`` — only effective
    BEFORE backend init, so call it at process start (the executor-
    launched user process preamble). Returns whether anything changed;
    flags already present are not duplicated."""
    if not knobs.xla_flags:
        return False
    current = os.environ.get("XLA_FLAGS", "")
    missing = [f for f in knobs.xla_flags if f not in current]
    if not missing:
        return False
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [current, *missing]))
    return True


# ---------------------------------------------------------------------------
# Stage: flash-attention block sizes (the generalized wall sweep)
# ---------------------------------------------------------------------------


def flash_block_candidates(
    seq: int, *, blocks: Iterable[int] = (256, 512, 1024, 2048)
) -> list[Knobs]:
    """The (block_q, block_k) grid, clamped to the sequence and deduped;
    ``Knobs()`` (the ``_default_blocks`` bucket pin) leads so the record
    always has a default to beat."""
    sizes = sorted({min(int(b), seq) for b in blocks if b > 0})
    return [Knobs()] + [
        Knobs(block_q=bq, block_k=bk) for bq in sizes for bk in sizes
    ]


def flash_wall_measure(
    seq: int, bh: int = 32, d: int = 64, *,
    iters: int = 10, windows: int = 3,
) -> Callable[[Knobs], float]:
    """The wall fwd+bwd measurement ``tools/sweep_flash_blocks.py``
    used to inline (moved here; the tool shims to this): grad of a sum
    through the public ``flash_attention``, best-of-``windows`` of
    ``iters`` calls, fenced by a scalar readback. Per-kernel trace
    durations miss inter-kernel pipelining, so only this wall number
    decides a block pin."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu.ops import flash_attention

    rng = np.random.default_rng(0)
    shape = (max(1, bh // 8), seq, 8, d)  # [B, T, H, D] public layout
    q4, k4, v4 = (
        jnp.asarray(rng.normal(size=shape), jnp.bfloat16) for _ in range(3)
    )

    def measure(knobs: Knobs) -> float:
        g = jax.jit(jax.grad(  # tony: noqa[TONY-X001] — search trial: one compile per candidate is the autotuner's job
            lambda q, k, v: flash_attention(
                q, k, v, block_q=knobs.block_q, block_k=knobs.block_k
            ).astype(jnp.float32).sum()
        ))
        float(g(q4, k4, v4).sum())  # warm + fence
        best = math.inf
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = g(q4, k4, v4)
            float(out.sum())  # tony: noqa[TONY-X002] — intended per-window timing fence
            best = min(best, time.perf_counter() - t0)
        return best / iters * 1e3

    return measure


def tune_flash_blocks(
    seq: int, bh: int = 32, d: int = 64, *,
    blocks: Iterable[int] = (256, 512, 1024, 2048),
    iters: int = 10, windows: int = 3,
    trial_budget: int | None = None,
    cache_dir: str | None = None,
    force: bool = False,
) -> dict[str, Any]:
    """Block-size stage: sweep the (block_q, block_k) wall grid for one
    attention shape and persist the winner under a shape-keyed record.
    The grid has |blocks|²+1 points — pass a ``trial_budget`` of at
    least that to cover it (the conf default 12 covers a 3×3 grid)."""
    candidates = flash_block_candidates(seq, blocks=blocks)
    key = tune_key(
        "flash_attention_wall", extra={"seq": seq, "bh": bh, "d": d}
    )
    return search(
        "flash_attention_wall", candidates,
        flash_wall_measure(seq, bh, d, iters=iters, windows=windows),
        key=key, trial_budget=trial_budget or len(candidates),
        cache_dir=cache_dir, force=force,
    )


# ---------------------------------------------------------------------------
# Stage: train-step program knobs
# ---------------------------------------------------------------------------


def apply_knobs_to_config(cfg, knobs: Knobs):
    """A config with the knob-controlled fields swapped in (remat
    policy today). Numerics-preserving by construction: remat changes
    what is recomputed, never what is computed."""
    if knobs.remat_policy and getattr(cfg, "remat_policy", None) is not None \
            and knobs.remat_policy != cfg.remat_policy:
        return dataclasses.replace(cfg, remat_policy=knobs.remat_policy)
    return cfg


def train_knob_candidates(
    cfg, *, microbatch_options: Sequence[int | None] = (None,),
) -> list[Knobs]:
    """The train-step grid: remat policy × microbatch count ×
    donation. ``Knobs()`` (stack defaults) leads. Kept deliberately
    small — each point pays a full XLA compile."""
    out = [Knobs()]
    for policy in ("full", "dots"):
        if policy != getattr(cfg, "remat_policy", "full"):
            out.append(Knobs(remat_policy=policy))
    for mb in microbatch_options:
        if mb is not None and mb > 1:
            out.append(Knobs(microbatches=mb))
            out.append(Knobs(microbatches=mb, pipeline_schedule="1f1b"))
    return out


def measure_train_step(
    cfg, mesh, knobs: Knobs, *,
    global_batch: int, seq: int,
    steps: int = 2, warmup: int = 1,
) -> float:
    """One trial: build the step with the candidate knobs, run
    ``warmup`` then time ``steps`` dispatches (scalar-readback fence).
    Returns mean step milliseconds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu.models.train import make_train_step
    from tony_tpu.ops import attention as attention_lib

    kwargs: dict[str, Any] = {}
    if knobs.microbatches is not None:
        kwargs["pipeline_microbatches"] = knobs.microbatches
    if knobs.pipeline_schedule:
        kwargs["pipeline_schedule"] = knobs.pipeline_schedule
    kcfg = apply_knobs_to_config(cfg, knobs)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(
            0, kcfg.vocab_size, (global_batch, seq + 1)
        ),
        jnp.int32,
    )
    prev_blocks = attention_lib.tuned_blocks()
    try:
        attention_lib.set_tuned_blocks(knobs.block_q, knobs.block_k)
        init_fn, step_fn = make_train_step(kcfg, mesh, **kwargs)
        with jax.sharding.set_mesh(mesh):
            state = init_fn(jax.random.key(0))
            metrics = None
            for _ in range(warmup):
                state, metrics = step_fn(state, tokens)
            float(metrics["loss"])  # host readback = real fence
            t0 = time.perf_counter()
            for _ in range(steps):
                state, metrics = step_fn(state, tokens)
            float(metrics["loss"])  # tony: noqa[TONY-X002] — intended timing fence
            dt = time.perf_counter() - t0
    finally:
        attention_lib.set_tuned_blocks(*prev_blocks)
    return dt / steps * 1000.0


def tune_train_step(
    cfg, mesh, *,
    global_batch: int, seq: int,
    candidates: Sequence[Knobs] | None = None,
    steps: int = 2, warmup: int = 1,
    trial_budget: int | None = None,
    cache_dir: str | None = None,
    force: bool = False,
) -> dict[str, Any]:
    """Train-step stage: measure the knob grid for (cfg, mesh) and
    persist the winner under the SAME identity ``make_train_step``
    looks up at build time — (model config, topology, jax version)
    only, batch/seq deliberately excluded because the builder cannot
    know them before the first batch arrives."""
    if candidates is None:
        candidates = train_knob_candidates(cfg)
    key = tune_key("lm_train_step", config=cfg, mesh=mesh)

    def measure(knobs: Knobs) -> float:
        return measure_train_step(
            cfg, mesh, knobs, global_batch=global_batch, seq=seq,
            steps=steps, warmup=warmup,
        )

    return search(
        "lm_train_step", candidates, measure, key=key,
        trial_budget=trial_budget, cache_dir=cache_dir, force=force,
    )
