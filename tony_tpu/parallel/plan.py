"""Sharding-plan layer + persistent compile cache.

One place that decides HOW a program is sharded and compiled (the shape
SNIPPETS.md [1] / Titanax calls a ``Plan``), and one place that makes the
resulting XLA executable SURVIVE the process: every resilience feature
multiplies how often a job re-runs (session retries, checkpoint resumes,
scheduler re-submits), and each re-run used to pay a full cold XLA
compile — at fleet scale the dominant tax on the retry path.

Three cooperating pieces:

* ``Plan`` — a declarative description of one compiled program: mesh
  spec (+ multi-slice layout), microbatching for the pipeline trunk,
  schedule/virtual-stage knobs, and state donation. ``make_train_step``
  accepts a Plan; ``trunk`` says which compilation strategy it implies
  (GSPMD jit-with-shardings vs the shard_map pipeline).
* the planner — ``candidate_plans`` enumerates every legal factoring of
  the device count over (dp, pp, ep, sp, tp) for a model config;
  ``plan_for`` ranks them with an analytic cost model REFINED by
  measured ``step_time_ms``
  (``record_step_time`` persists measurements next to the compile
  cache; measured plans recalibrate the estimates of unmeasured ones).
* the compile cache — ``configure_compile_cache`` wires the JAX
  persistent compilation cache (``tony.compile.*`` conf → executor env →
  here), and ``timed_compile``/``instrument_jit`` classify every first
  compile as a hit or miss against a plan-key index kept inside the
  cache dir, emitting ``tony_compile_cache_hits_total`` /
  ``tony_compile_cache_misses_total`` / ``tony_compile_ms`` through the
  observability registry so cache effectiveness shows up on /metrics,
  bench snapshots, and ``tony doctor`` input.

The key index is deliberately framework-level: a plan cache key digests
the model config, mesh topology, jax version, and backend identity —
exactly the things whose change MUST invalidate a cached executable. A
key marker only ever means "this plan was compiled against this cache
dir before"; corrupt or partial markers degrade to a miss, never a
crash (the XLA cache itself already tolerates missing entries the same
way).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import time
from contextlib import contextmanager
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Any, Iterable, Mapping

from tony_tpu.parallel.mesh import AXES, MeshSpec, build_mesh

# Metric names (rendered on /metrics, summarized into bench lines).
# Registered lazily so importing this module never touches the registry.
_CACHE_HITS_COUNTER = "tony_compile_cache_hits_total"
_CACHE_MISSES_COUNTER = "tony_compile_cache_misses_total"
_COMPILE_MS_HISTOGRAM = "tony_compile_ms"

# Compile-time wall histogram buckets: compiles run seconds, not the
# Prometheus default's milliseconds.
_COMPILE_BUCKETS = (
    50.0, 100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0, 10000.0,
    30000.0, 60000.0, 120000.0,
)

# Subdirectory of the XLA cache dir holding the plan-key index (one JSON
# marker per compiled plan key) and the measured step-time table.
_KEY_INDEX_DIR = "tony-plan-keys"
_MEASUREMENTS_FILE = "plan-measurements.json"


def _is_remote_uri(path: str) -> bool:
    return "://" in path


def _local_sidecar_dir(cache_dir: str) -> str:
    """Where the key index / measurement table live for a REMOTE (gs://)
    XLA cache: jax reads the artifact cache from the bucket natively,
    but the sidecar files use plain open()/rename — they get a local
    mirror keyed by the URI, beside the default cache dir. Hits then
    mean "this host compiled this plan against this bucket before": the
    honest local approximation, instead of a marker layer that silently
    never records."""
    digest = hashlib.sha256(cache_dir.encode()).hexdigest()[:16]
    return os.path.join(
        os.path.dirname(default_cache_dir()), "plan-sidecar", digest
    )


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Plan:
    """A declarative compilation plan: how one program is sharded.

    ``microbatches=None`` selects the GSPMD trunk (one ``jax.jit`` with
    explicit in/out shardings — the pjit style); any integer selects the
    pipeline trunk (``shard_map`` with manual collectives inside
    ``forward_pipeline``). ``donate_state`` controls ``donate_argnums``
    on the step so params update in place in HBM.
    """

    mesh_spec: MeshSpec
    num_slices: int = 1
    microbatches: int | None = None
    pipeline_schedule: str = "gpipe"
    pipeline_virtual: int = 1
    donate_state: bool = True

    @property
    def trunk(self) -> str:
        return "pipeline" if self.microbatches is not None else "gspmd"

    @property
    def num_devices(self) -> int:
        return self.mesh_spec.num_devices

    def build_mesh(self, devices: list | None = None):
        return build_mesh(
            self.mesh_spec, devices=devices, num_slices=self.num_slices
        )

    def train_step_kwargs(self) -> dict[str, Any]:
        """kwargs for ``make_train_step`` implied by this plan."""
        return {
            "pipeline_microbatches": self.microbatches,
            "pipeline_schedule": self.pipeline_schedule,
            "pipeline_virtual": self.pipeline_virtual,
        }

    def key(self) -> str:
        """Short stable id for measurement tables and log lines."""
        s = self.mesh_spec
        parts = [f"dp{s.dp}", f"pp{s.pp}", f"ep{s.ep}", f"sp{s.sp}",
                 f"tp{s.tp}"]
        if self.num_slices > 1:
            parts.append(f"x{self.num_slices}sl")
        if self.microbatches is not None:
            parts.append(f"mb{self.microbatches}")
            if self.pipeline_schedule != "gpipe":
                parts.append(f"{self.pipeline_schedule}{self.pipeline_virtual}")
        return ".".join(parts)

    def describe(self) -> dict[str, Any]:
        return {
            "mesh": dict(zip(AXES, self.mesh_spec.shape)),
            "num_slices": self.num_slices,
            "trunk": self.trunk,
            "microbatches": self.microbatches,
            "schedule": self.pipeline_schedule,
            "virtual": self.pipeline_virtual,
        }


# ---------------------------------------------------------------------------
# Cache keying
# ---------------------------------------------------------------------------


def _canonical(obj: Any) -> Any:
    """JSON-stable form: dataclasses to dicts, tuples to lists, sets
    sorted. Unknown objects fall back to repr — stable across processes
    for the config objects used here (frozen dataclasses)."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {
            "__dataclass__": type(obj).__name__,
            **{k: _canonical(v)
               for k, v in dataclasses.asdict(obj).items()},
        }
    if isinstance(obj, Mapping):
        return {str(k): _canonical(v) for k, v in sorted(obj.items())}
    if isinstance(obj, (list, tuple)):
        return [_canonical(v) for v in obj]
    if isinstance(obj, (set, frozenset)):
        return sorted(repr(v) for v in obj)
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)


def backend_fingerprint(mesh=None) -> dict[str, Any]:
    """The backend identity a compiled executable is only valid for:
    jax version, platform, device kind, and device count. Computed from
    the mesh's devices when given (the plan's devices, not the
    ambient backend's)."""
    import jax

    fp: dict[str, Any] = {"jax": jax.__version__}
    try:
        if mesh is not None:
            devs = list(mesh.devices.flat)
        else:
            devs = jax.devices()
        fp["platform"] = devs[0].platform
        fp["device_kind"] = getattr(devs[0], "device_kind", "")
        fp["num_devices"] = len(devs)
    except Exception:
        # Pre-backend-init callers (key unit tests) still get the
        # version-sensitive part of the fingerprint.
        fp["platform"] = "uninitialized"
    return fp


def plan_cache_key(
    label: str,
    *,
    config: Any = None,
    mesh=None,
    plan: Plan | None = None,
    extra: Mapping[str, Any] | None = None,
    backend: Mapping[str, Any] | None = None,
) -> str:
    """Digest everything whose change must invalidate a cached
    executable: the step label, the model config, the mesh topology
    (axis names + shape), the plan knobs, the backend identity (jax
    version / platform / device kind+count), and any caller extras
    (e.g. decode's static argument values)."""
    payload: dict[str, Any] = {
        "label": label,
        "backend": _canonical(
            dict(backend) if backend is not None
            else backend_fingerprint(mesh)
        ),
    }
    if config is not None:
        payload["config"] = _canonical(config)
    if mesh is not None:
        payload["mesh"] = {
            "axes": list(mesh.axis_names),
            "shape": list(mesh.devices.shape),
        }
    if plan is not None:
        payload["plan"] = _canonical(plan)
    if extra:
        payload["extra"] = _canonical(dict(extra))
    blob = json.dumps(payload, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


# ---------------------------------------------------------------------------
# Persistent compile cache wiring
# ---------------------------------------------------------------------------


def default_cache_dir() -> str:
    """Where the cache goes when neither ``JAX_COMPILATION_CACHE_DIR``
    nor ``tony.compile.cache-dir`` names a place: one fixed, git-ignored
    path inside the checkout. The directory is part of the cache's key,
    so it must not move between runs — never $HOME (differs per user and
    per machine image), a temporary name, a pid or the time."""
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(checkout, ".tony_cache", "xla-cache")


def _env_bool(name: str, default: bool) -> bool:
    raw = os.environ.get(name, "")
    if not raw:
        return default
    return raw.strip().lower() in ("1", "true", "yes", "on")


def configure_compile_cache(
    cache_dir: str | None = None,
    enabled: bool | None = None,
    min_entry_size: int | None = None,
) -> str | None:
    """Switch on JAX's persistent compilation cache and drop the
    min-compile-time floor so even fast steps get cached (retry/resume
    wants EVERY executable back, not just the slow ones).

    Where the cache lives, first match wins:

    1. ``JAX_COMPILATION_CACHE_DIR`` — whoever runs the process placed
       the cache from outside. JAX reads the variable itself and this
       function sets no directory, whatever ``cache_dir``, the conf or a
       scheduler's per-slice pin say.
    2. ``cache_dir``, else the executor-exported ``TONY_COMPILE_CACHE_DIR``
       (the ``tony.compile.cache-dir`` conf key).
    3. ``default_cache_dir()``.

    ``enabled`` / ``min_entry_size`` default from
    ``TONY_COMPILE_CACHE_ENABLED`` / ``_MIN_ENTRY_SIZE``. Returns the
    directory in use, or None when disabled. Safe to call before or
    after backend init, and idempotent.
    """
    from tony_tpu import constants

    if enabled is None:
        enabled = _env_bool(constants.TONY_COMPILE_CACHE_ENABLED, True)
    if not enabled:
        return None
    if min_entry_size is None:
        try:
            min_entry_size = int(
                os.environ.get(constants.TONY_COMPILE_MIN_ENTRY_SIZE, "0")
            )
        except ValueError:
            min_entry_size = 0

    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not placed:
        if cache_dir is None:
            cache_dir = os.environ.get(constants.TONY_COMPILE_CACHE_DIR, "")
        cache_dir = os.path.expanduser(cache_dir) if cache_dir \
            else default_cache_dir()
        if not _is_remote_uri(cache_dir):
            try:
                os.makedirs(cache_dir, exist_ok=True)
            except OSError:
                return None  # unwritable location: run cold, don't crash
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update(
        "jax_persistent_cache_min_entry_size_bytes", min_entry_size
    )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return active_cache_dir()


def active_cache_dir() -> str | None:
    """The cache dir JAX is currently configured with (None = cold) —
    ``JAX_COMPILATION_CACHE_DIR`` where set, since JAX's config reads
    it."""
    import jax

    return jax.config.jax_compilation_cache_dir or None


def compile_cache_summary() -> str:
    """``compile cache: dir=... hits=N misses=M`` — where this process
    keeps its cache and what its instrumented first compiles found there
    (``tony_compile_cache_hits_total`` / ``_misses_total``), for a user
    script's closing log line."""
    counters = _registry().snapshot()["counters"]
    return (f"compile cache: dir={active_cache_dir()} "
            f"hits={int(counters.get(_CACHE_HITS_COUNTER, 0))} "
            f"misses={int(counters.get(_CACHE_MISSES_COUNTER, 0))}")


class CompileCache:
    """The plan-key index beside the XLA artifact cache.

    ``seen(key)`` — was this plan compiled against this cache dir
    before?  ``commit(key, meta)`` — record that it now has been. All
    failure modes (missing dir, corrupt marker JSON, truncated file,
    permission errors) read as "not seen": the cost of a wrong miss is
    one recount, the cost of a crash is the job.
    """

    def __init__(self, cache_dir: str | None) -> None:
        self.cache_dir = cache_dir
        if cache_dir and _is_remote_uri(cache_dir):
            cache_dir = _local_sidecar_dir(cache_dir)
        self._index = (
            os.path.join(cache_dir, _KEY_INDEX_DIR) if cache_dir else None
        )

    @classmethod
    def active(cls) -> "CompileCache":
        return cls(active_cache_dir())

    @property
    def enabled(self) -> bool:
        return self._index is not None

    def _marker(self, key: str) -> str | None:
        if self._index is None or not key:
            return None
        return os.path.join(self._index, f"{key}.json")

    def seen(self, key: str) -> bool:
        marker = self._marker(key)
        if marker is None:
            return False
        try:
            with open(marker) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return False  # absent / torn / corrupt => miss, never a crash
        return isinstance(data, dict) and data.get("key") == key

    def commit(self, key: str, meta: Mapping[str, Any] | None = None) -> None:
        marker = self._marker(key)
        if marker is None:
            return
        try:
            os.makedirs(self._index, exist_ok=True)
            payload = {"key": key, "ts_ms": int(time.time() * 1000)}
            if meta:
                payload.update(_canonical(dict(meta)))
            tmp = f"{marker}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                f.write(json.dumps(payload))
            os.replace(tmp, marker)
        except OSError:
            pass  # a cache that cannot record stays a cache that misses


def _registry():
    from tony_tpu import observability

    return observability.default_registry()


def _count_compile(hit: bool, wall_ms: float) -> None:
    reg = _registry()
    if hit:
        reg.counter(_CACHE_HITS_COUNTER).inc()
    else:
        reg.counter(_CACHE_MISSES_COUNTER).inc()
    reg.histogram(_COMPILE_MS_HISTOGRAM, buckets=_COMPILE_BUCKETS).observe(wall_ms)


@contextmanager
def timed_compile(key: str, cache: CompileCache | None = None,
                  meta: Mapping[str, Any] | None = None):
    """Wrap ONE first-compile region: classifies hit/miss against the
    plan-key index before running the body, times the body into
    ``tony_compile_ms``, and commits the key after success. The body is
    the first dispatch of a jitted callable — its wall includes trace +
    (persistently cached) XLA compile + one execution, which is exactly
    the cost a retry pays, so that is the number recorded."""
    cache = CompileCache.active() if cache is None else cache
    hit = cache.seen(key)
    t0 = time.perf_counter()
    yield
    _count_compile(hit, (time.perf_counter() - t0) * 1000.0)
    if not hit:
        cache.commit(key, meta)


def _args_signature(args, kwargs) -> list[str]:
    """Shape/dtype summary of every array-ish leaf: two submits of the
    same program with different batch shapes compile different
    executables, so the plan key must see the shapes — which only exist
    at the first call, not at build time."""
    import jax

    out: list[str] = []
    for leaf in jax.tree.leaves((args, kwargs)):
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            out.append(f"{getattr(leaf, 'dtype', '?')}{tuple(shape)}")
        else:
            out.append(repr(leaf))
    return out


def instrument_jit(jit_fn, key: str, *, cache: CompileCache | None = None,
                   meta: Mapping[str, Any] | None = None):
    """Wrap a jitted callable so its FIRST call runs under
    ``timed_compile`` (hit/miss + compile wall metrics) with the base
    ``key`` extended by the call's argument shapes/dtypes; later calls
    pass straight through.

    With ``TONY_JIT_SANITIZER`` armed, every call is additionally
    classified by the jit sanitizer: the first signature is the **cold**
    compile (accounted by ``tony_compile_cache_*`` exactly as before), a
    repeated signature is a runtime cache **hit** (touches no counter),
    and a NEW signature after the first is a **re-trace** — counted only
    into ``tony_retraces_total``, never into the compile-cache miss
    counter, so the two accountings can never double-count one dispatch.
    Strict mode raises past the per-key retrace budget, and the dispatch
    itself runs inside ``step_region`` so implicit D2H transfers raise
    with a stack. Sanitizer off: byte-for-byte the old behavior, zero
    per-call overhead."""
    state = {"first": True}

    def call(*args, **kwargs):
        from tony_tpu.analysis import jit_sanitizer

        sanitized = jit_sanitizer.enabled()
        if sanitized:
            sig = hashlib.sha256(
                json.dumps(_args_signature(args, kwargs)).encode()
            ).hexdigest()
            jit_sanitizer.note_dispatch(key, sig)
        if state["first"]:
            state["first"] = False
            full_key = hashlib.sha256(
                json.dumps([key, _args_signature(args, kwargs)])
                .encode()
            ).hexdigest()
            with timed_compile(full_key, cache=cache, meta=meta):
                with jit_sanitizer.step_region(key):
                    return jit_fn(*args, **kwargs)
        if sanitized:
            with jit_sanitizer.step_region(key):
                return jit_fn(*args, **kwargs)
        return jit_fn(*args, **kwargs)

    call.__wrapped__ = jit_fn
    call.plan_cache_key = key
    return call


# ---------------------------------------------------------------------------
# Planner: candidate enumeration
# ---------------------------------------------------------------------------


def _divisors(n: int) -> list[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def candidate_plans(
    cfg,
    num_devices: int,
    *,
    num_slices: int = 1,
    global_batch: int | None = None,
    seq: int | None = None,
    max_candidates: int = 64,
    require: Mapping[str, int] | None = None,
) -> list[Plan]:
    """Every legal Plan for ``cfg`` on ``num_devices`` devices.

    Legality is the hard-constraint set the trunks actually enforce:

    * tp divides n_heads (and n_kv_heads when grouped) — head-split
      collectives need whole heads per shard;
    * pp divides n_layers; the pipeline trunk needs microbatches, and
      the interleaved schedule needs n_layers % (pp * virtual) == 0;
    * sp divides the post-shift sequence (when known) — the ring walks
      equal chunks;
    * ep > 1 only with experts, and ep divides n_experts;
    * dp * ep (and, pipelined, * microbatches) divides the global batch
      when known;
    * multi-slice: dp % num_slices == 0 (dp is the only axis allowed to
      cross the DCN boundary — build_mesh rejects anything else).

    ``require`` pins axes (e.g. ``{"pp": 2}``) — how the dryrun asks the
    planner for trunk-coverage cases instead of hand-rolling shapes.
    """
    require = dict(require or {})
    n_heads = getattr(cfg, "n_heads", 1)
    n_kv = getattr(cfg, "n_kv_heads", 0) or n_heads
    n_layers = getattr(cfg, "n_layers", 1)
    n_experts = getattr(cfg, "n_experts", 0)
    seq = seq if seq is not None else getattr(cfg, "max_seq", None)

    def ok(axis: str, size: int) -> bool:
        if axis in require and require[axis] != size:
            return False
        if axis == "tp":
            return n_heads % size == 0 and n_kv % size == 0
        if axis == "pp":
            return n_layers % size == 0
        if axis == "sp":
            return size == 1 or (seq is None or seq % size == 0)
        if axis == "ep":
            return size == 1 or (n_experts > 0 and n_experts % size == 0)
        return True  # dp

    plans: list[Plan] = []
    for tp in _divisors(num_devices):
        if not ok("tp", tp):
            continue
        for sp in _divisors(num_devices // tp):
            if not ok("sp", sp):
                continue
            for ep in _divisors(num_devices // (tp * sp)):
                if not ok("ep", ep):
                    continue
                for pp in _divisors(num_devices // (tp * sp * ep)):
                    if not ok("pp", pp):
                        continue
                    dp = num_devices // (tp * sp * ep * pp)
                    if not ok("dp", dp):
                        continue
                    if num_slices > 1 and dp % num_slices:
                        continue
                    spec = MeshSpec(dp=dp, pp=pp, ep=ep, sp=sp, tp=tp)
                    if pp == 1:
                        if "microbatches" in require and \
                                require["microbatches"]:
                            continue
                        plans.append(Plan(spec, num_slices=num_slices))
                        continue
                    for m in _microbatch_options(
                        pp, dp, ep, global_batch, require
                    ):
                        plans.append(Plan(
                            spec, num_slices=num_slices, microbatches=m,
                        ))
    plans.sort(key=lambda p: estimate_cost(
        p, cfg, global_batch=global_batch, seq=seq
    ))
    return plans[:max_candidates]


def _microbatch_options(
    pp: int, dp: int, ep: int, global_batch: int | None,
    require: Mapping[str, int],
) -> list[int]:
    if "microbatches" in require:
        m = require["microbatches"]
        return [m] if m else []
    # Bubble shrinks with m, host/rdma overhead grows: try pp and 2*pp
    # (the interleave-friendly points), filtered by batch divisibility.
    # A KNOWN batch that no option divides yields NO pipeline plans for
    # this factoring — re-adding pp here would emit a plan that crashes
    # on shard_map divisibility at the very batch the caller declared.
    opts = [pp, 2 * pp]
    if global_batch is not None:
        return [m for m in opts if global_batch % (m * dp * ep) == 0]
    return opts


# ---------------------------------------------------------------------------
# Planner: cost model
# ---------------------------------------------------------------------------

# Relative per-byte cost of a collective on each axis — priors, not
# measurements of the current chip: tp rides the innermost ICI hops
# (cheapest), sp's ring overlaps with attention compute, ep's all_to_all
# is bursty, pp moves only stage-boundary activations point-to-point,
# and dp's gradient psum is the most latency-tolerant (overlappable)
# collective — but on a multi-slice mesh dp crosses the DCN and costs
# an order of magnitude more per byte.
_COMM_COST = {"tp": 1.0, "sp": 1.3, "ep": 1.8, "pp": 0.6, "dp": 0.4}
_DCN_PENALTY = 12.0

# Flop-equivalents per communicated ELEMENT: peak matmul throughput over
# ICI link bandwidth (v5e: ~197 TFLOP/s vs ~45 GB/s per link, bf16
# elements) ≈ 8k flops/element. This is what makes a 5%-of-step gradient
# psum and a 15%-of-step ring pass come out as 5% and 15% instead of
# rounding noise against the compute term.
_ELEM_UNIT = 8000.0

# Fixed launch overhead per collective hop, in the same flop-equivalent
# units as the compute term (~launch latency × peak flops). Bytes-based
# terms vanish for small models, but the hops do not — without this the
# toy-scale ranking degenerates to enumeration order and "shard the
# 16-token sequence 8 ways" ties with plain data parallelism. dp's psum
# overlaps with backward (cheapest); sp's ring and ep's all_to_all
# serialize against the layer (dearest).
_HOP_LATENCY = {"tp": 1.0, "sp": 1.5, "ep": 2.0, "pp": 1.0, "dp": 0.5}
_HOP_UNIT = 1e6


def estimate_phases(
    plan: Plan,
    cfg,
    *,
    global_batch: int | None = None,
    seq: int | None = None,
) -> dict[str, Any]:
    """The cost model's compute/communication decomposition for one
    plan: ``{"compute": units, "collective": units, "comm_bytes":
    {axis: bytes/step}}``. ``estimate_cost`` sums the two unit terms
    (the planner's ranking); the stepstats layer uses the RATIO
    (collective / total) to split a measured device residual into
    compute vs collective phases, and the per-axis byte estimates to
    drive ``tony_collective_bytes_total{axis=}``. Units are arbitrary
    but shared, so the share and the bytes are meaningful even before
    any measurement calibrates the absolute scale. An illegal plan
    (pipeline axis without microbatching) reads as infinite compute."""
    s = plan.mesh_spec
    d_model = getattr(cfg, "d_model", 512)
    d_ff = getattr(cfg, "d_ff", 4 * d_model)
    n_layers = getattr(cfg, "n_layers", 1)
    n_heads = getattr(cfg, "n_heads", 8)
    head_dim = getattr(cfg, "head_dim", 64)
    n_kv = getattr(cfg, "n_kv_heads", 0) or n_heads
    seq = seq or getattr(cfg, "max_seq", 1024)
    batch = global_batch or max(s.dp * s.ep, 1)

    # Model flops per step (PaLM 6N counting + causal attention term).
    n_params = n_layers * (
        d_model * (n_heads + 2 * n_kv) * head_dim
        + n_heads * head_dim * d_model
        + 3 * d_model * d_ff
    ) + 2 * getattr(cfg, "vocab_size", 32000) * d_model
    flops = 6.0 * n_params * batch * seq \
        + 6.0 * n_layers * batch * seq * seq * n_heads * head_dim
    compute = flops / plan.num_devices

    # MXU-fill penalty: each tp-split matmul contraction below 128
    # lanes leaves the array proportionally idle.
    def fill(dim: int) -> float:
        return max(1.0, 128.0 / max(dim, 1)) ** 0.5

    compute *= fill(d_ff // s.tp) * fill((n_heads // s.tp) * head_dim)

    # Pipeline bubble (gpipe): (pp-1) of (m + pp - 1) ticks are idle.
    if plan.microbatches:
        m = plan.microbatches
        compute *= (m + s.pp - 1) / m
    elif s.pp > 1:
        compute = math.inf  # pipeline axis without microbatching: illegal

    # Communication volumes per axis, in ELEMENTS (weights fold the
    # per-byte cost differences); ``elems`` feeds both the weighted
    # cost term and the bytes estimate stepstats reports.
    act = batch * seq * d_model / max(s.dp * s.ep * s.sp, 1)
    elems: dict[str, float] = {}
    if s.tp > 1:  # 4 (ag + rs) pairs per layer on the megatron split
        elems["tp"] = 4 * n_layers * act * (s.tp - 1) / s.tp
    if s.sp > 1:  # ring K/V pass per layer
        kv = batch * seq * n_kv * head_dim / max(s.dp * s.ep, 1)
        elems["sp"] = 2 * n_layers * kv * (s.sp - 1) / s.sp
    if s.ep > 1:  # token all_to_all both ways per layer
        elems["ep"] = 2 * n_layers * act * (s.ep - 1) / s.ep
    if s.pp > 1:
        # Stage-boundary activations: each microbatch carries act/m and
        # crosses pp-1 boundaries — total volume is m-independent (m
        # shows up as bubble relief above and per-hop launches below).
        elems["pp"] = act * (s.pp - 1)
    if s.dp > 1:  # gradient psum over the sharded params
        elems["dp"] = 2 * n_params * (s.dp - 1) / s.dp
    comm = sum(
        _COMM_COST[ax] * (
            _DCN_PENALTY if ax == "dp" and plan.num_slices > 1 else 1.0
        ) * v
        for ax, v in elems.items()
    )
    # Fixed launch overhead: (axis_size - 1) hops per collective round.
    hops = sum(
        _HOP_LATENCY[ax] * (getattr(s, ax) - 1) * n_layers
        for ax in ("tp", "sp", "ep", "pp")
    ) + _HOP_LATENCY["dp"] * (s.dp - 1)
    elem_bytes = 2.0 if "16" in str(getattr(cfg, "dtype", "")) else 4.0
    return {
        "compute": compute,
        "collective": comm * _ELEM_UNIT + hops * _HOP_UNIT,
        "comm_bytes": {ax: v * elem_bytes for ax, v in elems.items()},
    }


def estimate_cost(
    plan: Plan,
    cfg,
    *,
    global_batch: int | None = None,
    seq: int | None = None,
) -> float:
    """Relative step-time estimate (arbitrary units; only the ORDER of
    candidates matters — measured step times recalibrate the scale).

    compute: total model flops / devices, inflated by (a) the pipeline
    bubble (pp-1)/m on the gpipe trunk and (b) an MXU-fill penalty when
    a tp split drives the per-shard contraction dims under the 128-deep
    MXU width (splits that leave narrow matmuls waste the array even
    at perfect balance).
    comm: per-axis byte estimates weighted by ``_COMM_COST`` (see
    ``estimate_phases`` for the decomposition itself).
    """
    est = estimate_phases(plan, cfg, global_batch=global_batch, seq=seq)
    return est["compute"] + est["collective"]


# ---------------------------------------------------------------------------
# Planner: measured refinement + selection
# ---------------------------------------------------------------------------


def _measurements_path(cache_dir: str | None = None) -> str | None:
    cache_dir = cache_dir or active_cache_dir()
    if not cache_dir:
        return None
    if _is_remote_uri(cache_dir):
        cache_dir = _local_sidecar_dir(cache_dir)
        try:
            os.makedirs(cache_dir, exist_ok=True)
        except OSError:
            return None
    return os.path.join(cache_dir, _MEASUREMENTS_FILE)


def _model_bucket(cfg, num_devices: int, global_batch: int | None,
                  seq: int | None) -> str:
    """Measurements are comparable only at EQUAL WORK: one (model
    config, device count, global batch, sequence) bucket per table
    entry. Without batch/seq in the digest, a 100 ms step at batch 8
    poisons the ranking against a 220 ms step at batch 16 — the
    small-batch plan "wins" while doing half the work."""
    blob = json.dumps(
        {"cfg": _canonical(cfg), "n": num_devices,
         "batch": global_batch, "seq": seq},
        sort_keys=True,
    )
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def record_step_time(
    plan: Plan, cfg, step_time_ms: float, *,
    global_batch: int | None = None, seq: int | None = None,
    cache_dir: str | None = None,
) -> None:
    """Persist one measured step time for (cfg, plan) beside the compile
    cache — the feedback loop that turns the analytic ranking into a
    measured one. Keeps the best (minimum) observation per plan key.
    Pass the SAME ``global_batch``/``seq`` a later ``plan_for`` will ask
    with — they key the comparability bucket. Callers typically pass the
    ``step_time_ms`` their train loop already reports to the
    observability registry."""
    path = _measurements_path(cache_dir)
    if path is None or not math.isfinite(step_time_ms) or step_time_ms <= 0:
        return
    table = load_measurements(cache_dir=cache_dir)
    bucket = table.setdefault(
        _model_bucket(cfg, plan.num_devices, global_batch, seq), {}
    )
    prev = bucket.get(plan.key())
    if prev is None or step_time_ms < prev:
        bucket[plan.key()] = round(float(step_time_ms), 3)
    try:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(table, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass


def load_measurements(cache_dir: str | None = None) -> dict[str, dict]:
    path = _measurements_path(cache_dir)
    if path is None:
        return {}
    try:
        with open(path) as f:
            table = json.load(f)
    except (OSError, ValueError):
        return {}  # corrupt table = no refinement, never a crash
    return table if isinstance(table, dict) else {}


def plan_for(
    cfg,
    num_devices: int,
    *,
    num_slices: int = 1,
    global_batch: int | None = None,
    seq: int | None = None,
    cache_dir: str | None = None,
    require: Mapping[str, int] | None = None,
) -> Plan:
    """Pick the Plan for ``cfg`` on this topology.

    Candidates are ranked by the analytic cost model; when the
    measurement table holds step times for this (config, device count)
    bucket, measured plans compete on real milliseconds and unmeasured
    ones on estimates recalibrated by the measured/estimated ratio —
    so one swept data point immediately re-anchors the whole ranking.
    """
    plans = candidate_plans(
        cfg, num_devices, num_slices=num_slices,
        global_batch=global_batch, seq=seq, require=require,
    )
    if not plans:
        raise ValueError(
            f"no legal plan for {num_devices} devices with config {cfg!r}"
        )
    measured = load_measurements(cache_dir=cache_dir).get(
        _model_bucket(cfg, num_devices, global_batch, seq), {}
    )
    if not measured:
        return plans[0]
    est = {
        p.key(): estimate_cost(p, cfg, global_batch=global_batch, seq=seq)
        for p in plans
    }
    ratios = [
        measured[k] / est[k]
        for k in measured
        if k in est and math.isfinite(est[k]) and est[k] > 0
    ]
    scale = sum(ratios) / len(ratios) if ratios else 1.0

    def cost(p: Plan) -> float:
        k = p.key()
        return measured[k] if k in measured else est[k] * scale

    return min(plans, key=cost)


def shrink_plans(
    num_devices: int,
    *,
    num_slices: int = 1,
    cfg=None,
    require: Mapping[str, int] | None = None,
    max_candidates: int = 8,
) -> list[Plan]:
    """Candidate plans for a SHRUNKEN topology — the elastic-shrink
    oracle (``coordinator/healing.py``): the gang just lost a host and
    the coordinator must pick a sharding for the n−1 survivors without
    knowing the model config (that lives in the user process, which
    re-derives its own plan — ``plan_for`` or ``plan_from_mesh`` on its
    rebuilt mesh — with the chosen plan's key as the advisory note).

    ``cfg=None`` plans topology-only: every model-shape legality check
    degrades to its permissive default (tp|1-head etc.), so pin what you
    know via ``require`` — the coordinator pins ``{"dp": n}`` since data
    parallelism is the one axis a model-blind replan can always reshard.
    Candidates come back cost-ranked like ``candidate_plans`` (they ARE
    ``candidate_plans``, over a null config)."""
    return candidate_plans(
        cfg if cfg is not None else SimpleNamespace(),
        max(num_devices, 1),
        num_slices=max(num_slices, 1),
        require=require,
        max_candidates=max_candidates,
    )


def plan_from_mesh(mesh, *, microbatches: int | None = None,
                   num_slices: int = 1, **kwargs) -> Plan:
    """The Plan implied by an already-built mesh — for callers that
    constructed their mesh by hand (``make_train_step(cfg, mesh)``, the
    common example-script path) but still want plan-keyed telemetry and
    live calibration: axis sizes come straight from the mesh shape,
    unknown axis names replicate into dp=1 semantics (they size 1 on
    the 5-axis meshes this framework builds)."""
    shape = dict(mesh.shape)
    spec = MeshSpec(**{ax: int(shape.get(ax, 1)) for ax in AXES})
    return Plan(spec, num_slices=num_slices, microbatches=microbatches,
                **kwargs)


def calibration_residuals(
    cfg,
    num_devices: int,
    *,
    num_slices: int = 1,
    global_batch: int | None = None,
    seq: int | None = None,
    cache_dir: str | None = None,
) -> dict[str, float]:
    """Per-plan calibration residuals for one measurement bucket:
    ``measured/estimated`` normalized by the bucket's mean ratio (the
    same scale ``plan_for`` recalibrates unmeasured candidates with).
    A residual of 1.0 means the cost model ranks this plan exactly as
    the fleet's calibration predicts; spread across plans is model
    error, drift over time on ONE plan is the hardware or the input
    pipeline changing under the job. Served per task as
    ``tony_plan_residual{plan=}`` and aggregated on /api/stepstats."""
    measured = load_measurements(cache_dir=cache_dir).get(
        _model_bucket(cfg, num_devices, global_batch, seq), {}
    )
    if not measured:
        return {}
    try:
        plans = candidate_plans(
            cfg, num_devices, num_slices=num_slices,
            global_batch=global_batch, seq=seq,
        )
    except Exception:
        return {}
    est = {
        p.key(): estimate_cost(p, cfg, global_batch=global_batch, seq=seq)
        for p in plans
    }
    ratios = {
        k: measured[k] / est[k]
        for k in measured
        if k in est and math.isfinite(est[k]) and est[k] > 0
    }
    if not ratios:
        return {}
    scale = sum(ratios.values()) / len(ratios)
    if scale <= 0:
        return {}
    return {k: r / scale for k, r in ratios.items()}
