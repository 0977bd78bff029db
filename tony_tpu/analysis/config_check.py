"""Config preflight: a frozen ``TonyConfiguration`` against the
``conf/keys.py`` registry.

The reference validated little beyond resource parsing — a typo'd key
silently fell back to its default and the job ran wrong (or burned a
slice before failing). Every check here is pure and client-side:

* unknown ``tony.*`` keys, with edit-distance "did you mean" suggestions
  drawn from the static registry AND the dynamic per-job-type families
  (``tony.<job>.{instances,memory,vcores,gpus,tpus,resources,env}``);
* type/range checks derived from the defaults registry (bools must parse,
  ints must parse and be non-negative, memory strings must parse, the
  port range must be ``lo-hi``, enums must be legal values);
* cross-key rules: chief must resolve to a schedulable task, notebooks
  are single-instance, TPU asks under a non-JAX runtime, and every
  ``tony.<job>.tpus`` ask must land on a legal slice topology
  (``coordinator/backend.py``'s table — the same planner the scheduler
  runs, so preflight and scheduling cannot disagree).
"""

from __future__ import annotations

import difflib
import math
import re

from tony_tpu import constants
from tony_tpu.analysis.findings import ERROR, INFO, WARNING, Finding
from tony_tpu.conf import keys

# Dynamic per-job-type key families (keys.instances_key et al.).
_FAMILY_SUFFIXES = (
    "instances", "memory", "vcores", "gpus", "tpus", "resources", "env",
)
_FAMILY_RE = re.compile(
    r"tony\.([a-z][a-z0-9_]*)\.(" + "|".join(_FAMILY_SUFFIXES) + r")$"
)
_WELL_KNOWN_JOBS = (
    constants.WORKER_JOB_NAME, constants.PS_JOB_NAME,
    constants.CHIEF_JOB_NAME, constants.EVALUATOR_JOB_NAME,
    constants.NOTEBOOK_JOB_NAME, constants.DRIVER_JOB_NAME,
)

_FRAMEWORKS = ("jax", "tensorflow", "pytorch")
_PREFLIGHT_MODES = (
    constants.PREFLIGHT_OFF, constants.PREFLIGHT_WARN,
    constants.PREFLIGHT_STRICT,
)

# Keys whose values are enumerations rather than free strings.
_ENUM_KEYS: dict[str, tuple[str, ...]] = {
    keys.K_FRAMEWORK: _FRAMEWORKS,
    keys.K_PREFLIGHT_MODE: _PREFLIGHT_MODES,
}

# Integer keys where 0 is not a legal value (the generic int rule only
# requires >= 0): the data-plane pipeline needs at least one in-flight
# transfer, one read worker, and one record per chunk; a flight
# recorder with no ring slots records nothing and would dump empty
# blackboxes.
_MIN_ONE_KEYS = frozenset({
    keys.K_IO_PREFETCH_DEPTH,
    keys.K_IO_READ_WORKERS,
    keys.K_IO_CHUNK_RECORDS,
    keys.K_HEALTH_FLIGHT_LIMIT,
    # A zero proxy connect timeout fails every upstream attempt
    # instantly; a zero-slot or zero-chunk serving engine can never
    # admit a request, and a zero-depth queue sheds all load.
    keys.K_PROXY_CONNECT_TIMEOUT_MS,
    keys.K_SERVING_SLOTS,
    keys.K_SERVING_PREFILL_CHUNK,
    keys.K_SERVING_DECODE_WINDOW,
    keys.K_SERVING_MAX_QUEUE,
    # A fleet that may never have a replica can never serve; a
    # zero-interval health poll spins the router thread; a zero-tick
    # hysteresis defeats its own purpose (every tick actuates).
    keys.K_FLEET_MAX_REPLICAS,
    keys.K_FLEET_SCALE_UP_QUEUE_DEPTH,
    keys.K_FLEET_HYSTERESIS_TICKS,
    keys.K_FLEET_HEALTH_INTERVAL_MS,
    # A zero-tick scheduler loop spins; a zero-slice pool can never
    # place a job.
    keys.K_SCHED_TICK_MS,
    keys.K_SCHED_MAX_SLICES,
    # A zero-ms leadership lease makes every heartbeat already stale
    # (standbys would steal the epoch between any two writes); a
    # zero-record compaction threshold rewrites the journal on every
    # append.
    keys.K_SCHED_HA_LEASE_MS,
    keys.K_SCHED_HA_JOURNAL_MAX,
    # A zero-length capture window profiles nothing (0 must be an
    # explicit CLI omission, not a configured default).
    keys.K_PROFILE_DURATION_MS,
    # A zero-depth checkpoint pipeline can never accept a save; zero
    # persist workers never commit one; full-every=0 would divide the
    # compaction clock by nothing; a zero migration/flush window turns
    # live migration into a plain kill (disable it via
    # tony.ckpt.migrate-on-preempt / flush-on-evict instead).
    keys.K_CKPT_PIPELINE_DEPTH,
    keys.K_CKPT_PERSIST_WORKERS,
    keys.K_CKPT_FULL_EVERY,
    keys.K_CKPT_MIGRATE_TIMEOUT_MS,
    keys.K_CKPT_EVICT_FLUSH_WAIT_MS,
    # A zero-trial autotune search measures nothing and would persist
    # an empty record as if it were a tuned one.
    keys.K_TUNE_TRIAL_BUDGET,
    # A zero-interval rollup tick spins the collector; a zero staleness
    # bound evicts every target between any two scrapes; a zero scrape
    # timeout fails every scrape; zero retention at any resolution
    # discards a tier the query planner assumes exists; a history cap
    # of 0 would persist an empty timeline for every job.
    keys.K_ROLLUP_INTERVAL_MS,
    keys.K_ROLLUP_STALE_AFTER_MS,
    keys.K_ROLLUP_SCRAPE_TIMEOUT_MS,
    keys.K_ROLLUP_RETENTION_RAW_S,
    keys.K_ROLLUP_RETENTION_1M_S,
    keys.K_ROLLUP_RETENTION_10M_S,
    # Zero-width SLO windows average nothing; a zero budget period
    # divides the burn extrapolation by nothing.
    keys.K_SLO_FAST_WINDOW_S,
    keys.K_SLO_SLOW_WINDOW_S,
    keys.K_SLO_BUDGET_PERIOD_S,
    keys.K_HISTORY_MAX_EVENTS,
})

# Float keys that must be strictly positive: a zero straggler threshold
# or jitter factor would alert on every heartbeat of a healthy fleet.
_POSITIVE_FLOAT_KEYS = frozenset({
    keys.K_HEALTH_STRAGGLER_THRESHOLD,
    keys.K_HEALTH_LOSS_SPIKE_FACTOR,
    keys.K_HEALTH_HB_JITTER_FACTOR,
    keys.K_HEALTH_IO_STALL_RATIO,
    keys.K_HEALTH_MFU_COLLAPSE_RATIO,
    keys.K_HEALTH_COMMS_BOUND_RATIO,
    # A zero (or nan — the finite check above) shrink floor would let
    # elastic shrink walk a gang down to nothing one loss at a time.
    keys.K_HEAL_MIN_SHRINK_FRACTION,
    # A zero burn threshold declares every objective permanently
    # breached (burn rates are positive whenever data exists).
    keys.K_SLO_BURN_THRESHOLD,
})

_TRUE_FALSE = frozenset(
    {"true", "1", "yes", "on", "false", "0", "no", "off"}
)

# Path prefixes that are reboot-scoped (or outright RAM-backed) on every
# mainstream distro: an XLA compile cache rooted here is silently cold on
# every fresh run — the exact failure mode the cache exists to kill.
_SCRATCH_PREFIXES = ("/tmp/", "/var/tmp/", "/dev/shm/", "/run/")


def _is_scratch_path(path: str) -> bool:
    import tempfile

    p = path.rstrip("/") + "/"
    prefixes = set(_SCRATCH_PREFIXES)
    prefixes.add(tempfile.gettempdir().rstrip("/") + "/")
    return any(p.startswith(pre) for pre in prefixes)


def _known_static_keys() -> frozenset[str]:
    return frozenset(keys.DEFAULTS)


def _candidate_keys(job_names: set[str]) -> list[str]:
    """The did-you-mean pool: every static key plus every dynamic family
    key for both the configured and the well-known job types."""
    pool = set(keys.DEFAULTS)
    for job in set(_WELL_KNOWN_JOBS) | job_names:
        for suffix in _FAMILY_SUFFIXES:
            pool.add(f"{keys.TONY_PREFIX}{job}.{suffix}")
    return sorted(pool)


def _suggest(key: str, pool: list[str]) -> str:
    close = difflib.get_close_matches(key, pool, n=1, cutoff=0.75)
    return f"did you mean `{close[0]}`?" if close else ""


def _is_int(value) -> bool:
    try:
        int(value)
        return True
    except (TypeError, ValueError):
        return False


def _check_value(key: str, value, default) -> str | None:
    """Type/range validation for one known key; returns the complaint or
    None. Expected types derive from the defaults registry, with the
    handful of special formats carved out explicitly."""
    if key in _ENUM_KEYS:
        if str(value) not in _ENUM_KEYS[key]:
            return (
                f"must be one of {', '.join(_ENUM_KEYS[key])}; got {value!r}"
            )
        return None
    if key in (keys.K_HTTP_PORT, keys.K_AM_HTTP_PORT):
        if str(value) != "disabled" and not _is_int(value):
            return f"must be an integer port or 'disabled'; got {value!r}"
        return None
    if key == keys.K_SCHED_TENANT_QUOTAS:
        if str(value).strip() and not re.fullmatch(
            r"\s*[\w.-]+\s*=\s*\d+\s*(,\s*[\w.-]+\s*=\s*\d+\s*)*",
            str(value),
        ):
            return (
                f"must be 'tenant=N,tenant=N' pairs; got {value!r}"
            )
        return None
    if key == keys.K_AM_RPC_PORT_RANGE:
        m = re.fullmatch(r"\s*(\d+)\s*-\s*(\d+)\s*", str(value))
        if not m or int(m.group(1)) > int(m.group(2)):
            return f"must be 'lo-hi' with lo <= hi; got {value!r}"
        return None
    if isinstance(default, bool):
        if not (
            isinstance(value, bool)
            or str(value).strip().lower() in _TRUE_FALSE
        ):
            return f"must be a boolean; got {value!r}"
        return None
    if isinstance(default, int):
        if value == "" or value is None:
            return None  # empty = take the default (get_int contract)
        if not _is_int(value):
            return f"must be an integer; got {value!r}"
        floor = 1 if key in _MIN_ONE_KEYS else 0
        if int(value) < floor:
            return f"must be >= {floor}; got {value!r}"
        return None
    if isinstance(default, float):
        if value == "" or value is None:
            return None  # empty = take the default (get_float contract)
        try:
            f = float(value)
        except (TypeError, ValueError):
            return f"must be a number; got {value!r}"
        if not math.isfinite(f):
            # nan compares False against every threshold — a detector
            # configured with it never fires, silently.
            return f"must be a finite number; got {value!r}"
        if key in _POSITIVE_FLOAT_KEYS:
            if f <= 0:
                return f"must be > 0; got {value!r}"
        elif f < 0:
            return f"must be >= 0; got {value!r}"
        return None
    return None


def _check_family_value(job: str, suffix: str, value) -> str | None:
    from tony_tpu.utils import parse_memory_string_mb

    if suffix in ("instances", "vcores", "gpus", "tpus"):
        if not _is_int(value):
            return f"must be an integer; got {value!r}"
        if int(value) < 0:
            return f"must be >= 0; got {value!r}"
        return None
    if suffix == "memory":
        try:
            parse_memory_string_mb(value)
        except (TypeError, ValueError):
            return f"must be a memory size like '2g' or '512m'; got {value!r}"
    return None


def check_config(conf) -> list[Finding]:
    """All config-layer findings for a resolved ``TonyConfiguration``."""
    findings: list[Finding] = []
    static = _known_static_keys()
    job_names: set[str] = set(conf.job_types())
    pool = _candidate_keys(job_names)

    for key in sorted(conf):
        value = conf.get(key)
        if not str(key).startswith(keys.TONY_PREFIX):
            findings.append(Finding(
                "TONY-C008", INFO,
                f"key `{key}` is not under the tony.* namespace and is "
                f"ignored by the framework",
            ))
            continue
        if key in static:
            complaint = _check_value(key, value, keys.DEFAULTS[key])
            if complaint:
                findings.append(Finding(
                    "TONY-C002", ERROR, f"`{key}` {complaint}",
                ))
            continue
        fam = _FAMILY_RE.fullmatch(key)
        if fam:
            job, suffix = fam.group(1), fam.group(2)
            complaint = _check_family_value(job, suffix, value)
            if complaint:
                findings.append(Finding(
                    "TONY-C002", ERROR, f"`{key}` {complaint}",
                ))
            elif job not in _WELL_KNOWN_JOBS:
                # A near-miss of a well-known job name mints a whole new
                # job type silently (tony.wroker.instances=2 schedules a
                # "wroker" gang and leaves worker at its default).
                close = difflib.get_close_matches(
                    job, _WELL_KNOWN_JOBS, n=1, cutoff=0.8
                )
                if close:
                    findings.append(Finding(
                        "TONY-C009", WARNING,
                        f"job type `{job}` in `{key}` looks like a typo",
                        suggestion=f"did you mean `tony.{close[0]}.{suffix}`?",
                    ))
            continue
        findings.append(Finding(
            "TONY-C001", ERROR, f"unknown configuration key `{key}`",
            suggestion=_suggest(key, pool),
        ))

    findings.extend(_cross_key_checks(conf, job_names))
    return findings


def _get_int_safe(conf, key: str, default: int) -> int | None:
    try:
        return conf.get_int(key, default)
    except (TypeError, ValueError):
        return None  # already reported as TONY-C002


def _cross_key_checks(conf, job_names: set[str]) -> list[Finding]:
    findings: list[Finding] = []

    # Requested instances per job (0-instance families are configured but
    # schedule nothing).
    instances: dict[str, int] = {}
    for job in job_names:
        n = _get_int_safe(conf, keys.instances_key(job),
                          keys.default_instances(job))
        if n is not None:
            instances[job] = n

    # Chief must resolve to a schedulable task: the rendezvous barrier and
    # completion accounting both key off it.
    chief_name = conf.get_str(keys.K_CHIEF_NAME, constants.WORKER_JOB_NAME)
    chief_idx = _get_int_safe(conf, keys.K_CHIEF_INDEX, 0)
    scheduled = {j: n for j, n in instances.items() if n > 0}
    if scheduled:
        chief_n = instances.get(chief_name, 0)
        if chief_n == 0:
            findings.append(Finding(
                "TONY-C003", ERROR,
                f"chief job `{chief_name}` (tony.chief.name) has no "
                f"instances — the job can never complete",
                suggestion=f"set `{keys.instances_key(chief_name)}` >= 1 "
                           f"or point tony.chief.name at one of: "
                           f"{', '.join(sorted(scheduled))}",
            ))
        elif chief_idx is not None and chief_idx >= chief_n:
            findings.append(Finding(
                "TONY-C003", ERROR,
                f"tony.chief.index={chief_idx} is out of range for "
                f"{chief_n} `{chief_name}` instance(s)",
            ))

    # Notebooks are single-instance by construction (one proxy tunnel).
    nb = instances.get(constants.NOTEBOOK_JOB_NAME, 0)
    if nb > 1:
        findings.append(Finding(
            "TONY-C004", ERROR,
            f"tony.notebook.instances={nb}: notebook jobs are "
            f"single-instance (one task, one proxy tunnel)",
        ))

    # TPU asks under a non-JAX runtime: the TF/PyTorch runtimes here drive
    # CPU/GPU env contracts, not TPU slice bring-up.
    framework = conf.get_str(keys.K_FRAMEWORK, "jax")
    tpu_jobs = {
        job: t for job in job_names
        if (t := _get_int_safe(conf, keys.tpus_key(job), 0)) and t > 0
        and instances.get(job, 0) > 0
    }
    if tpu_jobs and framework in _FRAMEWORKS and framework != "jax":
        findings.append(Finding(
            "TONY-C005", WARNING,
            f"tony.{next(iter(sorted(tpu_jobs)))}.tpus > 0 with "
            f"tony.application.framework={framework}: only the jax "
            f"runtime initializes TPU slices",
        ))

    # Single-node apps with a multi-instance gang contradict themselves.
    try:
        single_node = conf.get_bool(keys.K_IS_SINGLE_NODE, False)
    except ValueError:
        single_node = False
    total = sum(scheduled.values())
    if single_node and total > 1:
        findings.append(Finding(
            "TONY-C007", WARNING,
            f"tony.application.single-node=true but {total} task "
            f"instances are configured",
        ))

    # A compile cache rooted on non-persistent scratch misses every run
    # while claiming to be enabled — worse than off, because nobody goes
    # looking for the cold-compile tax they believe they've paid off.
    try:
        cache_enabled = conf.get_bool(keys.K_COMPILE_CACHE_ENABLED, True)
    except ValueError:
        cache_enabled = True
    cache_dir = conf.get_str(keys.K_COMPILE_CACHE_DIR, "")
    if cache_enabled and cache_dir and _is_scratch_path(cache_dir):
        findings.append(Finding(
            "TONY-C010", WARNING,
            f"tony.compile.cache-dir={cache_dir} points at non-persistent "
            f"scratch — the XLA compile cache will be cold on every run",
            suggestion="use a durable-volume path (empty = "
                       ".tony_cache/xla-cache in the checkout), or set "
                       "tony.compile.cache-enabled=false",
        ))

    # Same trap for autotune records: a tune record dir on scratch is
    # silently cold every run, so every job pays the full search again
    # while believing it reused a persisted plan.
    try:
        tune_enabled = conf.get_bool(keys.K_TUNE_ENABLED, True)
    except ValueError:
        tune_enabled = True
    tune_dir = conf.get_str(keys.K_TUNE_RECORD_DIR, "")
    if tune_enabled and tune_dir and _is_scratch_path(tune_dir):
        findings.append(Finding(
            "TONY-C011", WARNING,
            f"tony.tune.record-dir={tune_dir} points at non-persistent "
            f"scratch — autotune records will be cold on every run and "
            f"every job repeats the full measured search",
            suggestion="use a home- or durable-volume path (empty = "
                       "beside the compile cache), or set "
                       "tony.tune.enabled=false",
        ))

    # Every TPU ask must land on a legal slice topology — run the real
    # planner so preflight can never disagree with the scheduler. With no
    # TPU ask the planner never runs, but an explicitly-set topology /
    # accelerator-type string is still validated (a bad value would only
    # explode later, on the first job that DOES ask for chips).
    topology = conf.get_str(keys.K_TPU_TOPOLOGY, "")
    accel = conf.get_str(keys.K_TPU_ACCELERATOR_TYPE, "")
    if tpu_jobs or topology or accel:
        from tony_tpu.coordinator.backend import plan_slices_from_conf

        try:
            plan_slices_from_conf(conf)
        except ValueError as exc:
            findings.append(Finding(
                "TONY-C006", ERROR, f"illegal TPU slice request: {exc}",
            ))
    return findings
