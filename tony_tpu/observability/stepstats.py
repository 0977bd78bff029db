"""Step anatomy — per-step phase/collective telemetry + live MFU.

Everything upstream of this module sees the training step as one opaque
``step_time_ms`` scalar: the health detectors (PR 5) can say a task is
slow, the goodput ledger (PR 9) can say time was "productive", but
nothing can say WHERE a step's milliseconds went — input wait, H2D
transfer, compute, collectives, or host overhead. This module closes
that gap for every instrumented train step, with no profiler session
and no per-step device round trips:

* **wall** — the interval between consecutive dispatches of the
  instrumented step (``models/train._instrumented`` feeds it). In a
  steady-state loop that interval IS the full step wall, wherever the
  caller put its readback fence, and it never touches donated buffers.
* **data_wait** — host time blocked on the input pipeline: the larger
  of the wrapped batch iterator's measured ``next()`` wait
  (``StepStats.wrap_batches``) and the data plane's
  ``tony_io_batch_wait_ms`` accumulation over the same interval.
* **h2d** — the ``tony_io_h2d_ms`` delta (PR-4 prefetcher telemetry).
* **host** — the measured dispatch cost (trace + enqueue, the async
  part the chip never sees).
* **compute / collective** — the device residual
  (wall − data_wait − h2d − host), split by the active Plan's analytic
  communication share (``parallel.plan.estimate_phases`` — the same
  per-axis cost model the planner ranks candidates with). The split is
  an estimate; the RESIDUAL is measured, so the five phases always sum
  to the step wall exactly.

On top of the breakdown:

* **MFU** — analytic model flops (PaLM 6N + the causal-attention term,
  computed once from the model config) over measured wall × device
  count × per-chip peak — ``tony_mfu`` on every snapshot/heartbeat.
* **live calibration** — the best observed wall feeds
  ``plan.record_step_time`` (the PR-6 measurement table), so every
  production job recalibrates the planner's cost model instead of only
  bench sweeps; the resulting measured/estimated residual is published
  per plan as ``tony_plan_residual{plan=}``.
* **per-axis collective volume** — ``tony_collective_bytes_total{axis=}``
  accumulates the estimated per-step bytes each mesh axis moves.

All of it rides the existing heartbeat piggyback (gauges in the default
registry → ``$TONY_METRICS_FILE`` → ``/metrics``), is aggregated on
``/api/stepstats``, rendered by ``tony top`` and the history server's
"Step anatomy" panel, and watched by the ``mfu_collapse`` /
``comms_bound`` health detectors. See docs/DEPLOY.md "Step anatomy".
"""

from __future__ import annotations

import math
import os
import time
from typing import Any, Iterable, Iterator, Mapping

# The exclusive phase breakdown, in reporting order. lint_self checks
# each value is documented in docs/DEPLOY.md (operators filter on them).
PHASES = ("data_wait", "h2d", "compute", "collective", "host")

STEP_PHASE_GAUGE = "tony_step_phase_ms"          # labeled {phase=}
MFU_GAUGE = "tony_mfu"
MODEL_FLOPS_GAUGE = "tony_model_flops_per_step"
COLLECTIVE_BYTES_COUNTER = "tony_collective_bytes_total"  # labeled {axis=}
PLAN_RESIDUAL_GAUGE = "tony_plan_residual"       # labeled {plan=}

# Data-plane histograms whose SUM deltas attribute the input side
# (io/reader.py's declared names, re-declared here so this module stays
# importable without the data plane; absent series read as zero).
_IO_BATCH_WAIT_HISTOGRAM = "tony_io_batch_wait_ms"
_IO_H2D_HISTOGRAM = "tony_io_h2d_ms"

# Conf (tony.stepstats.*) reaches user processes as env, like TONY_IO_*.
_ENV_ENABLED = "TONY_STEPSTATS_ENABLED"
_ENV_CALIBRATE = "TONY_STEPSTATS_CALIBRATE"
_ENV_WINDOW = "TONY_STEPSTATS_WINDOW"

# Per-chip peak dense bf16 throughput, for MFU (bench.py imports this —
# one table, one MFU definition), keyed by jax device_kind. A device that
# is not here (a CPU included) has no peak, and its runs report no MFU.
PEAK_FLOPS = {
    "TPU v2": 46e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops_per_chip(device=None) -> float:
    """Peak dense flops/sec for one chip, by device kind. Lazy-imports
    jax; 0.0 without jax or for a device the table doesn't know — MFU
    is then simply not reported, never computed against a made-up
    peak."""
    if device is None:
        try:
            import jax
        except ImportError:
            return 0.0
        device = jax.devices()[0]
    return PEAK_FLOPS.get(getattr(device, "device_kind", ""), 0.0)


def model_flops_per_step(cfg, batch: int, seq: int) -> float | None:
    """Analytic model flops for one train step of ``cfg`` at
    (batch, seq): PaLM 6N counting plus the causal-attention term —
    model flops, not hardware flops (remat recompute is excluded on
    purpose, matching bench.py's MFU definition). None for configs that
    are not transformer-shaped (no d_model/n_layers): image classifiers
    get phases but not MFU — conv flops are not derivable from a param
    count."""
    d_model = getattr(cfg, "d_model", None)
    n_layers = getattr(cfg, "n_layers", None)
    vocab = getattr(cfg, "vocab_size", None)
    if not d_model or not n_layers or not vocab:
        return None
    n_heads = getattr(cfg, "n_heads", 8)
    head_dim = getattr(cfg, "head_dim", 64)
    n_kv = getattr(cfg, "n_kv_heads", 0) or n_heads
    d_ff = getattr(cfg, "d_ff", 4 * d_model)
    # MoE: every layer routes each token through top_k SwiGLU experts
    # (transformer.py's contract), so the ACTIVE mlp work per token is
    # top_k× the dense block, plus the router matmul — counting all
    # n_experts' params here would overstate flops by E/top_k, counting
    # the dense block alone understates by top_k.
    n_experts = getattr(cfg, "n_experts", 0) or 0
    top_k = (getattr(cfg, "expert_top_k", 0) or 1) if n_experts else 1
    n_params = n_layers * (
        d_model * (n_heads + 2 * n_kv) * head_dim
        + n_heads * head_dim * d_model
        + 3 * d_model * d_ff * top_k
        + d_model * n_experts
    ) + 2 * vocab * d_model
    return (
        6.0 * n_params * batch * seq
        + 6.0 * n_layers * batch * seq * seq * n_heads * head_dim
    )


def _env_bool(name: str, default: bool) -> bool:
    raw = os.environ.get(name, "")
    if not raw:
        return default
    return raw.strip().lower() in ("1", "true", "yes", "on")


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


class StepStats:
    """Per-step anatomy recorder for ONE instrumented step function.

    ``models/train.make_train_step`` builds one (attached to the
    returned step as ``step.stepstats``) and ``_instrumented`` drives it
    with ``step_begin``/``step_end`` around every dispatch. Nothing here
    synchronizes the device or touches donated arrays: the wall is the
    dispatch-to-dispatch interval, the input side comes from the data
    plane's own telemetry plus the optional ``wrap_batches`` iterator
    wrapper, and the compute/collective split is the plan cost model's.

    The first dispatch (trace + compile) is excluded — its wall is
    compile telemetry (``tony_compile_ms``), not step anatomy.
    """

    def __init__(
        self,
        *,
        cfg: Any = None,
        plan: Any = None,
        mesh: Any = None,
        microbatches: int | None = None,
        steps_per_call: int = 1,
        tokens_workload: bool = True,
        size_from_shapes: bool = True,
        registry=None,
        enabled: bool | None = None,
        calibrate: bool | None = None,
        window: int | None = None,
        clock=time.perf_counter,
        peak_flops: float | None = None,
    ) -> None:
        self.enabled = (
            _env_bool(_ENV_ENABLED, True) if enabled is None else enabled
        )
        self.calibrate = (
            _env_bool(_ENV_CALIBRATE, True) if calibrate is None
            else calibrate
        )
        self.window = max(window if window is not None
                          else _env_int(_ENV_WINDOW, 32), 1)
        self.cfg = cfg
        self.plan = plan
        self._mesh = mesh
        self._microbatches = microbatches
        self.steps_per_call = max(int(steps_per_call), 1)
        # tokens_workload: the step's batch argument is [B, T+1] tokens
        # whose shape sizes the flops/comm model; False (image
        # classifiers) keeps the phase breakdown and calibration but
        # skips MFU — conv flops are not derivable from these shapes.
        self._tokens_workload = tokens_workload
        # size_from_shapes=False: the builder sizes the workload itself
        # (make_train_step calls set_workload with the assembled GLOBAL
        # batch shape — the dispatch hook only ever sees the host-local
        # shard, which on a multi-process mesh understates flops and
        # mis-buckets calibration by the process count).
        self._size_from_shapes = size_from_shapes
        self.mfu: float | None = None
        self._registry = registry
        self._clock = clock
        self._peak_flops = peak_flops
        # Workload (global batch, seq) joins at the first dispatch from
        # the token shapes — only then can flops / comm volumes be sized.
        self.global_batch: int | None = None
        self.seq: int | None = None
        self._flops: float | None = None
        self._comm_share = 0.0
        self._comm_bytes: dict[str, float] = {}
        self._num_devices = 1
        self._sized = False
        # Rolling interval state.
        self._begins = 0
        self._last_begin: float | None = None
        self._pending_data_s = 0.0
        self._dispatch_s = 0.0
        self._io_wait_ms: float | None = None
        self._io_h2d_ms: float | None = None
        self.steps_observed = 0
        self._best_wall_ms = math.inf
        self._recorded_ms: float | None = None
        self._last_record_step = 0
        # Lazily-registered metric handles (no zero-noise on /metrics
        # from step functions that are built but never driven).
        self._gauges: dict[str, Any] | None = None

    # -- wiring -------------------------------------------------------------
    def wrap_batches(self, batches: Iterator[Any]) -> Iterator[Any]:
        """Wrap the train loop's batch iterator so host time blocked in
        ``next()`` is attributed to ``data_wait`` (the synthetic-corpus
        and generator paths that never touch ``tony_io_*``)."""
        if not self.enabled:
            return batches

        def timed() -> Iterator[Any]:
            while True:
                t0 = self._clock()
                try:
                    batch = next(batches)
                except StopIteration:
                    return
                self._pending_data_s += self._clock() - t0
                yield batch

        return timed()

    def set_workload(self, global_batch: int | None,
                     seq: int | None) -> None:
        """Size the flops / communication model once the batch shapes
        are known (the first dispatch). None/None keeps the phase
        machinery and calibration (bucketed at unspecified work) but
        disables the flops-derived outputs. Idempotent."""
        if self._sized:
            return
        self._sized = True
        self.global_batch = int(global_batch) if global_batch else None
        self.seq = int(seq) if seq else None
        if self.plan is None and self._mesh is not None:
            try:
                from tony_tpu.parallel import plan as plan_lib

                self.plan = plan_lib.plan_from_mesh(
                    self._mesh, microbatches=self._microbatches
                )
            except Exception:
                self.plan = None
        if self.plan is not None:
            self._num_devices = max(self.plan.num_devices, 1)
        if self.cfg is not None and self.global_batch and self.seq:
            self._flops = model_flops_per_step(
                self.cfg, self.global_batch, self.seq
            )
        if self.plan is not None and self.cfg is not None \
                and self._flops is not None:
            try:
                from tony_tpu.parallel import plan as plan_lib

                est = plan_lib.estimate_phases(
                    self.plan, self.cfg,
                    global_batch=self.global_batch, seq=self.seq,
                )
                total = est["compute"] + est["collective"]
                self._comm_share = (
                    est["collective"] / total if total > 0 else 0.0
                )
                self._comm_bytes = dict(est["comm_bytes"])
            except Exception:
                self._comm_share, self._comm_bytes = 0.0, {}
        if self._peak_flops is None:
            self._peak_flops = peak_flops_per_chip()

    # -- the per-dispatch hooks (driven by _instrumented) -------------------
    def step_begin(self, batch_shape=None) -> None:
        """Called at the TOP of every instrumented dispatch. The
        interval since the previous ``step_begin`` is the completed
        step's wall: it contains that step's dispatch, the caller's
        readback fence, and the next batch's fetch — everything one
        loop iteration costs."""
        if not self.enabled:
            return
        now = self._clock()
        if not self._sized and self._size_from_shapes:
            if self._tokens_workload and batch_shape is not None \
                    and len(batch_shape) >= 2:
                # tokens are [B, T+1]; the post-shift training sequence
                # is T — the same convention the planner and lm_loss use.
                self.set_workload(batch_shape[0],
                                  max(batch_shape[1] - 1, 1))
            elif not self._tokens_workload:
                self.set_workload(None, None)
        self._begins += 1
        last = self._last_begin
        self._last_begin = now
        if self._begins <= 2 or last is None:
            # The interval before the first dispatch is empty, and the
            # first dispatch's own interval (ending at the SECOND begin)
            # contains trace + XLA compile — its wall is compile
            # telemetry (tony_compile_ms), not step anatomy. A cold
            # 45 s compile must not publish as a 45000 ms compute phase.
            self._pending_data_s = 0.0
            self._read_io_baseline()
            return
        self._observe((now - last) * 1000.0)

    def step_end(self, dispatch_s: float) -> None:
        """Called as each dispatch returns, with its measured host cost
        (the async trace/enqueue time — the chip never sees it)."""
        self._dispatch_s = dispatch_s

    # -- accounting ---------------------------------------------------------
    def _io_sum(self, name: str) -> float:
        reg = self._reg()
        if reg is None:
            return 0.0
        h = reg.peek(name)
        if h is None or not hasattr(h, "snapshot"):
            return 0.0
        try:
            return float(h.snapshot().get("sum", 0.0))
        except (TypeError, ValueError):
            return 0.0

    def _read_io_baseline(self) -> None:
        self._io_wait_ms = self._io_sum(_IO_BATCH_WAIT_HISTOGRAM)
        self._io_h2d_ms = self._io_sum(_IO_H2D_HISTOGRAM)

    def _observe(self, call_wall_ms: float) -> None:
        if call_wall_ms <= 0:
            return
        wall = call_wall_ms / self.steps_per_call
        data_s = self._pending_data_s
        self._pending_data_s = 0.0
        io_wait = self._io_sum(_IO_BATCH_WAIT_HISTOGRAM)
        io_h2d = self._io_sum(_IO_H2D_HISTOGRAM)
        d_wait = max(io_wait - (self._io_wait_ms or 0.0), 0.0)
        d_h2d = max(io_h2d - (self._io_h2d_ms or 0.0), 0.0)
        self._io_wait_ms, self._io_h2d_ms = io_wait, io_h2d
        per = 1.0 / self.steps_per_call
        # The iterator wait and the reader's batch_wait histogram
        # overlap (a blocked next() IS reader wait when the framework
        # data plane feeds it): take the larger, never the sum.
        data_wait = min(max(data_s * 1000.0 * per, d_wait * per), wall)
        h2d = min(d_h2d * per, wall - data_wait)
        host = min((self._dispatch_s * 1000.0) * per,
                   wall - data_wait - h2d)
        device = wall - data_wait - h2d - host
        collective = device * self._comm_share
        compute = device - collective
        self.steps_observed += self.steps_per_call
        self._publish(wall, {
            "data_wait": data_wait, "h2d": h2d, "compute": compute,
            "collective": collective, "host": host,
        })
        if wall < self._best_wall_ms:
            self._best_wall_ms = wall
        # Attempt on EVERY observation, not only on a new best: the
        # best wall usually lands before the 3-step warmup is over, and
        # a perfectly steady loop would otherwise never record at all.
        # _maybe_record's own guards keep it to one write per real
        # improvement per window.
        self._maybe_record()

    # -- publishing ---------------------------------------------------------
    def _reg(self):
        if self._registry is None:
            from tony_tpu.observability import metrics as obs_metrics

            self._registry = obs_metrics.default_registry()
        return self._registry

    def _handles(self) -> dict[str, Any]:
        if self._gauges is None:
            reg = self._reg()
            handles: dict[str, Any] = {
                p: reg.gauge(STEP_PHASE_GAUGE, labels={"phase": p})
                for p in PHASES
            }
            if self._flops:
                # Only flops-modeled workloads register the MFU family:
                # a classifier job must not serve zero-valued tony_mfu.
                handles["flops"] = reg.gauge(MODEL_FLOPS_GAUGE)
                if self._peak_flops:
                    # ... and only on a known accelerator generation: an
                    # unknown peak (peak_flops_per_chip() == 0) must mean
                    # NO tony_mfu, not a constant-0.0 one poisoning the
                    # fleet median.
                    handles["mfu"] = reg.gauge(MFU_GAUGE)
            handles["bytes"] = {
                axis: reg.counter(COLLECTIVE_BYTES_COUNTER,
                                  labels={"axis": axis})
                for axis, v in self._comm_bytes.items() if v > 0
            }
            self._gauges = handles
        return self._gauges

    def _publish(self, wall_ms: float, phases: Mapping[str, float]) -> None:
        h = self._handles()
        for phase in PHASES:
            h[phase].set(round(phases[phase], 3))
        if self._flops and "flops" in h:
            h["flops"].set(self._flops)
            if "mfu" in h:
                mfu = self._flops / (
                    wall_ms / 1000.0 * self._num_devices * self._peak_flops
                )
                self.mfu = mfu
                h["mfu"].set(round(mfu, 5))
        for axis, counter in h["bytes"].items():
            counter.inc(self._comm_bytes[axis] * self.steps_per_call)
        # step_time_ms through report(): the straggler detector and the
        # history panel read the same gauge the train loop would set,
        # and report() drives the (throttled) snapshot publish for
        # loops that never call observability.report themselves.
        self._reg().report(step_time_ms=round(wall_ms, 3))

    # -- live calibration ---------------------------------------------------
    def _maybe_record(self) -> None:
        """Feed the best observed wall into the planner's measurement
        table (PR 6's ``record_step_time``) and into the autotune
        record's ``live_best_ms`` — throttled to a real improvement at
        most once per ``window`` steps, after enough observations that
        the best is a steady-state step."""
        if not self.calibrate or self.cfg is None:
            return
        if self.steps_observed < 3:
            return
        if self.steps_observed - self._last_record_step < self.window \
                and self._recorded_ms is not None:
            return
        if self._recorded_ms is not None \
                and self._best_wall_ms > self._recorded_ms * 0.99:
            return
        try:
            from tony_tpu.parallel import autotune as autotune_lib
            from tony_tpu.parallel import plan as plan_lib

            if self.plan is not None:
                plan_lib.record_step_time(
                    self.plan, self.cfg, self._best_wall_ms,
                    global_batch=self.global_batch, seq=self.seq,
                )
            # Close the measured-autotuner loop: a production step that
            # beats the record's offline best updates ``live_best_ms``,
            # so `tony tune` shows where search-time numbers drifted
            # from the fleet's reality. A no-op when no record exists.
            autotune_lib.note_step_time(
                "lm_train_step", config=self.cfg, mesh=self._mesh,
                step_ms=self._best_wall_ms,
            )
            self._recorded_ms = self._best_wall_ms
            self._last_record_step = self.steps_observed
            if self.plan is not None:
                residuals = plan_lib.calibration_residuals(
                    self.cfg, self._num_devices,
                    num_slices=getattr(self.plan, "num_slices", 1),
                    global_batch=self.global_batch, seq=self.seq,
                )
                r = residuals.get(self.plan.key())
                if r is not None:
                    self._reg().gauge(
                        PLAN_RESIDUAL_GAUGE,
                        labels={"plan": self.plan.key()},
                    ).set(round(r, 4))
        except Exception:
            # Calibration is telemetry: an unwritable cache dir or a
            # cfg the planner can't digest must never touch training.
            pass


# ---------------------------------------------------------------------------
# Aggregated views (/api/stepstats, `tony top`, the history panel)
# ---------------------------------------------------------------------------

def _inline_labels(key: str) -> tuple[str, dict[str, str]]:
    from tony_tpu.observability.metrics import parse_labeled_key

    return parse_labeled_key(key)


def counter_rate(prev: float, cur: float, dt_s: float) -> float:
    """Rate from two counter readings, clamped at zero: a task that
    restarted mid-session resets its process-local counters, and the
    reset must read as "no progress this interval", never a negative
    rate (the aggregator keeps the task id, so the drop is visible as a
    plain delta — rates must not amplify it)."""
    if dt_s <= 0:
        return 0.0
    return max(cur - prev, 0.0) / dt_s


def task_stepstats(snapshot: Mapping[str, Any]) -> dict[str, Any] | None:
    """Extract one task's step anatomy from its metrics snapshot
    (the aggregator's normalized form, or a final-status ``metrics``
    task entry): phase gauges, MFU, collective byte totals, and plan
    residuals. None when the task never published step anatomy."""
    gauges = snapshot.get("gauges") or {}
    counters = snapshot.get("counters") or {}
    phases: dict[str, float] = {}
    residuals: dict[str, float] = {}
    for key, value in gauges.items():
        base, labels = _inline_labels(str(key))
        if base == STEP_PHASE_GAUGE and labels.get("phase") in PHASES:
            phases[labels["phase"]] = float(value)
        elif base == PLAN_RESIDUAL_GAUGE and "plan" in labels:
            residuals[labels["plan"]] = float(value)
    if not phases:
        return None
    coll_bytes: dict[str, float] = {}
    for key, value in counters.items():
        base, labels = _inline_labels(str(key))
        if base == COLLECTIVE_BYTES_COUNTER and "axis" in labels:
            coll_bytes[labels["axis"]] = float(value)
    total = sum(phases.values())
    out: dict[str, Any] = {
        "phases": {p: round(phases.get(p, 0.0), 3) for p in PHASES},
        "step_time_ms": round(total, 3),
        "dominant_phase": max(phases, key=phases.get) if total else None,
        "shares": {
            p: round(phases.get(p, 0.0) / total, 4) if total else 0.0
            for p in PHASES
        },
    }
    mfu = gauges.get(MFU_GAUGE)
    if isinstance(mfu, (int, float)):
        out["mfu"] = float(mfu)
    steps = counters.get("train_steps_total")
    if isinstance(steps, (int, float)):
        out["steps"] = steps
    if coll_bytes:
        out["collective_bytes"] = coll_bytes
    if residuals:
        out["residuals"] = residuals
    return out


def stepstats_view(
    task_snapshots: Mapping[str, Mapping[str, Any]],
    step_rates: Mapping[str, float] | None = None,
) -> dict[str, Any]:
    """The ``/api/stepstats`` document: per-task anatomy plus a fleet
    roll-up (median MFU, modal dominant phase). ``task_snapshots`` maps
    task id → metrics snapshot — the aggregator's latest, or the
    terminal record's ``metrics.tasks``. ``step_rates`` (aggregator
    only: live steps/sec between a task's last two heartbeats, already
    clamped restart-safe by :func:`counter_rate`) annotates each task
    that has one — historical/terminal callers omit it."""
    tasks: dict[str, Any] = {}
    for task_id, snap in task_snapshots.items():
        if not isinstance(snap, Mapping):
            continue
        entry = task_stepstats(snap)
        if entry is not None:
            if step_rates and task_id in step_rates:
                entry["steps_per_sec"] = float(step_rates[task_id])
            tasks[task_id] = entry
    fleet: dict[str, Any] = {"tasks": len(tasks)}
    mfus = sorted(t["mfu"] for t in tasks.values() if "mfu" in t)
    if mfus:
        fleet["mfu_median"] = round(mfus[len(mfus) // 2], 5)
    dominant = [t["dominant_phase"] for t in tasks.values()
                if t.get("dominant_phase")]
    if dominant:
        fleet["dominant_phase"] = max(set(dominant), key=dominant.count)
    return {"tasks": tasks, "fleet": fleet}


def format_top(app_id: str, view: Mapping[str, Any], source: str) -> str:
    """The ``tony top`` table: one row per task — phase milliseconds,
    dominant phase, MFU — plus the fleet line."""
    fleet = view.get("fleet") or {}
    lines = [
        f"# {app_id} ({source}) — {fleet.get('tasks', 0)} task(s)"
        + (f", fleet mfu {fleet['mfu_median']:.4f}"
           if "mfu_median" in fleet else "")
        + (f", dominant {fleet['dominant_phase']}"
           if fleet.get("dominant_phase") else ""),
        f"{'TASK':16s} {'STEP_MS':>9s} "
        + " ".join(f"{p.upper():>10s}" for p in PHASES)
        + f" {'DOMINANT':>10s} {'MFU':>8s}",
    ]
    tasks = view.get("tasks") or {}
    for task_id in sorted(tasks):
        t = tasks[task_id]
        phases = t.get("phases") or {}
        mfu = t.get("mfu")
        lines.append(
            f"{task_id:16s} {t.get('step_time_ms', 0):9.2f} "
            + " ".join(f"{phases.get(p, 0.0):10.2f}" for p in PHASES)
            + f" {t.get('dominant_phase') or '-':>10s} "
            + (f"{mfu:8.4f}" if isinstance(mfu, (int, float)) else
               f"{'-':>8s}")
        )
    return "\n".join(lines)
