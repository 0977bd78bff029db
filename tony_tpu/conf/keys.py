"""All ``tony.*`` configuration keys and their defaults.

TPU-native analogue of the reference's ``TonyConfigurationKeys.java``
(tony-core/src/main/java/com/linkedin/tony/TonyConfigurationKeys.java:1-179).
Differences from the reference, by design:

* resources are TPU-first: every job type gets a ``tony.<job>.tpus`` family
  beside memory/vcores (the reference only had ``gpus``); the scheduler maps
  ``instances × tpus`` onto legal slice topologies (``tony.tpu.topology``).
* storage keys are generic URIs (local dir or ``gs://``) instead of HDFS.
* the framework switch gains a ``jax`` value (reference: tensorflow|pytorch,
  TonyConfigurationKeys.java:74-75).

Every ``K_*`` constant here must appear in ``tony-default.json`` with the
matching default, and vice versa — enforced both directions by
``tests/test_conf.py::test_config_parity`` (the analogue of the reference's
``TestTonyConfigurationFields.java:11-62``).
"""

from __future__ import annotations

TONY_PREFIX = "tony."

# --- application ----------------------------------------------------------
APPLICATION_PREFIX = TONY_PREFIX + "application."
K_APPLICATION_NAME = APPLICATION_PREFIX + "name"
K_FRAMEWORK = APPLICATION_PREFIX + "framework"           # jax | tensorflow | pytorch
K_IS_SINGLE_NODE = APPLICATION_PREFIX + "single-node"
K_ENABLE_PREPROCESS = APPLICATION_PREFIX + "enable-preprocess"
K_APPLICATION_TIMEOUT = APPLICATION_PREFIX + "timeout"   # ms, 0 = none
K_CLIENT_CONNECT_RETRIES = APPLICATION_PREFIX + "num-client-coordinator-connect-retries"
K_CLIENT_CONNECT_TIMEOUT_MS = APPLICATION_PREFIX + "coordinator-connect-timeout"
K_SECURITY_ENABLED = APPLICATION_PREFIX + "security.enabled"
K_DOCKER_ENABLED = APPLICATION_PREFIX + "docker.enabled"
K_DOCKER_IMAGE = APPLICATION_PREFIX + "docker.image"
# Job payload (the reference passes these as TonyClient CLI args --executes/
# --src_dir/--python_venv/--task_params/--shell_env and threads them through
# tony-final.xml; here they are first-class conf keys).
K_EXECUTES = APPLICATION_PREFIX + "executes"
K_SRC_DIR = APPLICATION_PREFIX + "src-dir"
K_PYTHON_VENV = APPLICATION_PREFIX + "python-venv"
K_PYTHON_BINARY = APPLICATION_PREFIX + "python-binary-path"
K_TASK_PARAMS = APPLICATION_PREFIX + "task-params"
K_SHELL_ENV = APPLICATION_PREFIX + "shell-env"

# --- task (executor) ------------------------------------------------------
TASK_PREFIX = TONY_PREFIX + "task."
K_TASK_HEARTBEAT_INTERVAL_MS = TASK_PREFIX + "heartbeat-interval"
K_TASK_MAX_MISSED_HEARTBEATS = TASK_PREFIX + "max-missed-heartbeats"
K_TASK_REGISTRATION_TIMEOUT_MS = TASK_PREFIX + "registration-timeout"
K_TASK_REGISTRATION_RETRY_MS = TASK_PREFIX + "registration-retry-interval"
# Consecutive failed heartbeat SENDS after which an executor declares the
# coordinator lost, reaps its user process group, and exits
# EXIT_CODE_LOST_COORDINATOR — a partitioned executor must not squat its
# TPU slice as a zombie.
K_TASK_MAX_HB_SEND_FAILURES = TASK_PREFIX + "max-heartbeat-send-failures"

# --- RPC transport ---------------------------------------------------------
RPC_PREFIX = TONY_PREFIX + "rpc."
K_RPC_CALL_TIMEOUT_MS = RPC_PREFIX + "call-timeout"      # per-call socket timeout

# --- coordinator (AM analogue) --------------------------------------------
# Descoped from the reference (see README "descoped keys"): tony.am.memory/
# vcores/gpus sized the AM's YARN container; the coordinator here is a plain
# subprocess with no resource caps to request.
AM_PREFIX = TONY_PREFIX + "am."
K_AM_RETRY_COUNT = AM_PREFIX + "retry-count"
# Failure-aware retry policy (resilience/policy.py): the n-th session retry
# backs off base*2^(n-1) ms (capped at max) times a deterministic jitter in
# [1, 1.5) drawn from the jitter seed (0 = derive from the app id). The
# budget refreshes whenever a retry advances the best complete checkpoint
# step (probed from tony.checkpoint.location).
K_AM_RETRY_BACKOFF_BASE_MS = AM_PREFIX + "retry-backoff-base"
K_AM_RETRY_BACKOFF_MAX_MS = AM_PREFIX + "retry-backoff-max"
K_AM_RETRY_JITTER_SEED = AM_PREFIX + "retry-jitter-seed"
K_AM_MONITOR_INTERVAL_MS = AM_PREFIX + "monitor-interval"
K_AM_RPC_PORT_RANGE = AM_PREFIX + "rpc-port-range"       # "10000-15000"
K_AM_STOP_GRACE_MS = AM_PREFIX + "stop-grace"            # wait for client finish signal
# Observability HTTP port on the coordinator (/metrics Prometheus text,
# /api/metrics, /api/events, /api/trace): an int ("0" = ephemeral, the
# bound port is advertised in <app_dir>/coordinator.http) or "disabled".
K_AM_HTTP_PORT = AM_PREFIX + "http-port"

# --- chief semantics (TonyConfigurationKeys.java:159-163) ------------------
CHIEF_PREFIX = TONY_PREFIX + "chief."
K_CHIEF_NAME = CHIEF_PREFIX + "name"
K_CHIEF_INDEX = CHIEF_PREFIX + "index"

# --- worker ---------------------------------------------------------------
WORKER_PREFIX = TONY_PREFIX + "worker."
K_WORKER_TIMEOUT = WORKER_PREFIX + "timeout"

# --- TPU resource model (new) ---------------------------------------------
TPU_PREFIX = TONY_PREFIX + "tpu."
K_TPU_TOPOLOGY = TPU_PREFIX + "topology"                 # e.g. "v5e-8", "" = auto
K_TPU_ACCELERATOR_TYPE = TPU_PREFIX + "accelerator-type" # e.g. "v5litepod-8"
K_TPU_SLICE_STRICT = TPU_PREFIX + "strict-slice-shapes"  # reject illegal topologies

# --- GCP control plane (new; the YarnClient-analogue substrate) ------------
GCP_PREFIX = TONY_PREFIX + "gcp."
K_GCP_PROJECT = GCP_PREFIX + "project"          # non-empty => TpuVmBackend
K_GCP_ZONE = GCP_PREFIX + "zone"                # e.g. "us-central1-a"
K_GCP_RUNTIME_VERSION = GCP_PREFIX + "runtime-version"  # TPU VM image
K_GCP_NETWORK = GCP_PREFIX + "network"          # "" = project default
K_AM_ADDRESS_HOST = AM_PREFIX + "address-host"  # reachable AM host for remote executors ("" = auto)

# --- data plane (io/reader.py) ---------------------------------------------
# Tuning for the sharded-reader → device_prefetch pipeline. The executor
# exports these to user processes as TONY_IO_* env, which the reader and
# prefetcher read as their defaults (explicit constructor args win).
IO_PREFIX = TONY_PREFIX + "io."
# Batches kept in flight host→device (incl. the one the step consumes):
# 1 = eager, 2 = double buffering, deeper absorbs slow/bursty transfers.
K_IO_PREFETCH_DEPTH = IO_PREFIX + "prefetch-depth"
# Concurrent span reads (local preads / GCS ranged GETs) per reader.
K_IO_READ_WORKERS = IO_PREFIX + "read-workers"
# Records per prefetch-queue chunk; one read span covers 4 chunks.
K_IO_CHUNK_RECORDS = IO_PREFIX + "chunk-records"

# --- compilation (parallel/plan.py) ----------------------------------------
# Persistent XLA compile cache: coordinator-driven retries, checkpoint
# resumes, and scheduler re-submits of an unchanged program skip
# compilation entirely. The client resolves cache-dir at staging (empty =
# the fixed .tony_cache/xla-cache inside each host's checkout; relative
# paths are absolutized so every process agrees on one dir), the executor
# exports TONY_COMPILE_* env, and runtime.initialize()/
# plan.configure_compile_cache wire jax — unless the process was started
# with JAX_COMPILATION_CACHE_DIR, which wins.
COMPILE_PREFIX = TONY_PREFIX + "compile."
K_COMPILE_CACHE_DIR = COMPILE_PREFIX + "cache-dir"
K_COMPILE_CACHE_ENABLED = COMPILE_PREFIX + "cache-enabled"
# Smallest XLA artifact worth persisting, bytes (0 = keep everything —
# the retry path wants every executable back, not just the big ones).
K_COMPILE_MIN_ENTRY_SIZE = COMPILE_PREFIX + "min-entry-size"

# --- health analytics (observability/health.py + flight.py) ----------------
# Streaming detectors fed by the heartbeat piggyback on the coordinator:
# straggler scoring (MAD z-score across tasks' step_time_ms), stalled
# train_steps_total watchdog, loss NaN/spike, heartbeat arrival jitter,
# and data-plane stall (tony_io_queue_wait_ms accumulation rate). Alerts
# emit `health_alert` lifecycle events and bump tony_health_alerts_total.
HEALTH_PREFIX = TONY_PREFIX + "health."
K_HEALTH_ENABLED = HEALTH_PREFIX + "enabled"
# Robust z-score above which a slow task is flagged a straggler.
K_HEALTH_STRAGGLER_THRESHOLD = HEALTH_PREFIX + "straggler-threshold"
# ms without train_steps_total advancing (while still heartbeating)
# before the progress watchdog alerts; 0 disables.
K_HEALTH_STALL_TIMEOUT_MS = HEALTH_PREFIX + "stall-timeout"
# loss > factor × its recent rolling median => spike alert.
K_HEALTH_LOSS_SPIKE_FACTOR = HEALTH_PREFIX + "loss-spike-factor"
# heartbeat arrival gap > factor × tony.task.heartbeat-interval => alert.
K_HEALTH_HB_JITTER_FACTOR = HEALTH_PREFIX + "heartbeat-jitter-factor"
# input-pipeline queue-wait accumulating faster than ratio × wall time.
K_HEALTH_IO_STALL_RATIO = HEALTH_PREFIX + "io-stall-ratio"
# tony_mfu below ratio × the task's own recent rolling median => the
# mfu_collapse detector fires (relative on purpose: absolute MFU varies
# by orders of magnitude across configs and hardware).
K_HEALTH_MFU_COLLAPSE_RATIO = HEALTH_PREFIX + "mfu-collapse-ratio"
# collective share of the step wall (tony_step_phase_ms) above this =>
# the comms_bound detector fires: the mesh spends its step on
# collectives, not compute.
K_HEALTH_COMMS_BOUND_RATIO = HEALTH_PREFIX + "comms-bound-ratio"
# Per-(detector, task) re-alert suppression window, ms.
K_HEALTH_ALERT_COOLDOWN_MS = HEALTH_PREFIX + "alert-cooldown"
# Ring size of the crash flight recorder (recent reports / RPC frame
# summaries / events kept for blackbox-*.json dumps).
K_HEALTH_FLIGHT_LIMIT = HEALTH_PREFIX + "flight-recorder-limit"

# --- self-healing actuation (coordinator/healing.py) ------------------------
# The loop that ACTS on the health plane's telemetry instead of only
# alerting: evict-and-replace a confirmed straggler mid-job (partial
# rendezvous patch, resume from the last complete checkpoint — never a
# whole-session restart), elastically shrink the gang to the surviving
# topology on hardware loss when no replacement is possible, and
# speculatively launch a backup copy of a slow-to-register task.
HEAL_PREFIX = TONY_PREFIX + "heal."
K_HEAL_ENABLED = HEAL_PREFIX + "enabled"
# A straggler alert must persist this long (score continuously above
# tony.health.straggler-threshold) before the coordinator evicts — one
# noisy sample must never cost a gang a re-rendezvous. 0 = evict on the
# first confirmed score.
K_HEAL_CONFIRM_WINDOW_MS = HEAL_PREFIX + "confirm-window"
# Evict-and-replace budget per job (0 = never replace; hardware losses
# then go straight to elastic shrink or the session retry path).
K_HEAL_MAX_EVICTIONS = HEAL_PREFIX + "max-evictions"
# Elastic shrink floor: the gang may shrink only while
# survivors / original >= this fraction (and never below 1 task, and
# never by removing the chief).
K_HEAL_MIN_SHRINK_FRACTION = HEAL_PREFIX + "min-shrink-fraction"
# Speculative re-execution (TonY's MapReduce heritage, TPU-native): when
# most of the gang has registered but one task is still missing past the
# delay, launch a backup copy — whichever copy registers first wins and
# the loser is killed.
K_HEAL_SPECULATIVE = HEAL_PREFIX + "speculative"
K_HEAL_SPECULATIVE_DELAY_MS = HEAL_PREFIX + "speculative-delay"

# --- checkpoint pipeline (checkpoint/) --------------------------------------
# The staged save pipeline + differential saves + live migration. The
# executor exports these to user processes as TONY_CKPT_* env, which
# CheckpointManager reads as its defaults (explicit constructor args
# win), like tony.io.*.
CKPT_PREFIX = TONY_PREFIX + "ckpt."
# Saves in flight behind the bounded pipeline (snapshot queue +
# persisting steps). 1 = at most one async save at a time (the
# pre-pipeline behavior); deeper absorbs slow/bursty stores.
K_CKPT_PIPELINE_DEPTH = CKPT_PREFIX + "pipeline-depth"
# Persist-stage upload workers per process (serialize + upload + commit
# run here, off the step path).
K_CKPT_PERSIST_WORKERS = CKPT_PREFIX + "persist-workers"
# Differential saves: leaves whose encoded bytes are unchanged since the
# last save are referenced, not rewritten.
K_CKPT_DIFFERENTIAL = CKPT_PREFIX + "differential"
# Every N-th save is a full rewrite (compaction): bounds chain length
# and lets GC retire donor steps.
K_CKPT_FULL_EVERY = CKPT_PREFIX + "full-every"
# Run the device→host materialization on the snapshot thread too (the
# caller's save() returns after only ISSUING the copies). Safe ONLY for
# train steps that do not donate their state buffers
# (plan.donate_state=False) — the default train step donates, so this
# defaults off.
K_CKPT_BG_SNAPSHOT = CKPT_PREFIX + "bg-snapshot"
# Preemption-as-live-migration: on a scheduler preemption
# (kill(preempted=True)) the coordinator orders every task to flush a
# checkpoint over the heartbeat-reply command channel and waits up to
# migrate-timeout ms for the commit marker before tearing down — the
# relaunch then resumes within ~one step-interval of the victim's last
# step instead of one checkpoint-interval behind.
K_CKPT_MIGRATE_ON_PREEMPT = CKPT_PREFIX + "migrate-on-preempt"
K_CKPT_MIGRATE_TIMEOUT_MS = CKPT_PREFIX + "migrate-timeout"
# Self-healing evictions order the same flush while the gang is still
# live (the straggler is slow, not dead) and wait up to
# evict-flush-wait ms, so the patched gang resumes near-current.
K_CKPT_FLUSH_ON_EVICT = CKPT_PREFIX + "flush-on-evict"
K_CKPT_EVICT_FLUSH_WAIT_MS = CKPT_PREFIX + "evict-flush-wait"

# --- goodput accounting (observability/goodput.py) --------------------------
# Per-job chip-second ledger: an exclusive breakdown of wall time ×
# chips into queued/provisioning/staging/compile/rendezvous/productive/
# stalled/wasted_by_failure/preempted/teardown, served on /api/goodput,
# /metrics, final-status.json, and `tony goodput <app_id>`.
GOODPUT_PREFIX = TONY_PREFIX + "goodput."
K_GOODPUT_ENABLED = GOODPUT_PREFIX + "enabled"
# Chip weight override (0 = auto: slice-plan chip total, else one per
# task) — lets heterogeneous deployments pin the billing unit.
K_GOODPUT_CHIPS = GOODPUT_PREFIX + "chips"

# --- step anatomy (observability/stepstats.py) ------------------------------
# Per-step phase/collective telemetry + live MFU in the USER process:
# the instrumented train step publishes tony_step_phase_ms{phase=},
# tony_mfu, and tony_collective_bytes_total{axis=} into the registry
# (riding the heartbeat piggyback), and feeds measured step times back
# into the planner's measurement table. The executor exports these as
# TONY_STEPSTATS_* env, like tony.io.*.
STEPSTATS_PREFIX = TONY_PREFIX + "stepstats."
K_STEPSTATS_ENABLED = STEPSTATS_PREFIX + "enabled"
# Feed best observed step walls into plan-measurements.json (the PR-6
# live-calibration loop); disable for jobs whose cache dir is shared
# with workloads that must not be recalibrated by this one.
K_STEPSTATS_CALIBRATE = STEPSTATS_PREFIX + "calibrate"
# Steps between calibration re-records (a record also requires the best
# wall to actually improve — the table keeps the minimum).
K_STEPSTATS_WINDOW = STEPSTATS_PREFIX + "window"

# --- measured program autotuner (parallel/autotune.py) ----------------------
# Persisted per-(model config, topology, jax version) program tuning:
# flash block sizes, remat policy, microbatching, donation, XLA flags,
# and the serving engine's KV-cache quantization. The executor exports
# these as TONY_TUNE_* env, like tony.stepstats.*.
TUNE_PREFIX = TONY_PREFIX + "tune."
# Consumption switch: when off, lookups always miss and nothing tuned
# is applied (explicit search entry points stay callable).
K_TUNE_ENABLED = TUNE_PREFIX + "enabled"
# Max measured candidates per search stage (each trial pays a compile).
K_TUNE_TRIAL_BUDGET = TUNE_PREFIX + "trial-budget"
# Tune-record directory; empty = beside the compile cache (remote URIs
# get the plan-measurements local sidecar mirror). A /tmp dir is
# silently cold every reboot — lint rule TONY-C011, like TONY-C010.
K_TUNE_RECORD_DIR = TUNE_PREFIX + "record-dir"

# --- on-demand profiling (observability/profiling.py) -----------------------
PROFILE_PREFIX = TONY_PREFIX + "profile."
# Default capture window, ms, when `tony profile` / POST /api/profile
# omits --duration-ms (bounded at 60s executor-side).
K_PROFILE_DURATION_MS = PROFILE_PREFIX + "duration-ms"
# Continuous per-device HBM gauge sampling interval in the USER process
# (tony_device_hbm_bytes{device=,kind=}); 0 disables.
K_PROFILE_HBM_INTERVAL_MS = PROFILE_PREFIX + "hbm-interval"

# --- proxy (proxy/server.py) ------------------------------------------------
PROXY_PREFIX = TONY_PREFIX + "proxy."
# Per-ATTEMPT upstream connect timeout, ms (attempts retry until the
# tunnel's connect deadline). Replaced a hardcoded 5 s: cross-region
# backends need more, a LAN serving mesh wants to fail over in less.
K_PROXY_CONNECT_TIMEOUT_MS = PROXY_PREFIX + "connect-timeout"

# --- serving engine (serving/) ---------------------------------------------
# Continuous-batching knobs for the ``serving`` task type. The executor
# exports these to user processes as TONY_SERVING_* env; examples/
# lm_serve.py (and any custom serving script) reads them as defaults.
SERVING_PREFIX = TONY_PREFIX + "serving."
# Fixed slot-batch width: concurrent decode streams per engine. Each
# slot owns a KV-cache row, so HBM cost scales linearly — see
# docs/DEPLOY.md "Serving" for the sizing rule.
K_SERVING_SLOTS = SERVING_PREFIX + "slots"
# Prefill chunk length, tokens: the longest a new prompt may stall the
# in-flight decode streams per engine iteration.
K_SERVING_PREFILL_CHUNK = SERVING_PREFIX + "prefill-chunk"
# Decode steps per host sync (the throughput/latency knob): 1 retires
# at EOS exactly per-token; deeper windows amortize the per-dispatch
# host cost over N tokens at up to N-1 wasted lane-steps per retiring
# stream and N-step admission latency.
K_SERVING_DECODE_WINDOW = SERVING_PREFIX + "decode-window"
# Admission backpressure: queued (not-yet-slotted) requests beyond this
# are shed (HTTP 503) instead of buffered.
K_SERVING_MAX_QUEUE = SERVING_PREFIX + "max-queue"
# HTTP port the serving task binds (0 = the executor-reserved chief
# port when available, else ephemeral).
K_SERVING_PORT = SERVING_PREFIX + "port"

# --- serving fleets (fleet/, actuated by scheduler/service.py) --------------
# An autoscaled replica group of serving jobs behind the fleet router.
# Read from the FLEET TEMPLATE conf at `tony fleet create` (frozen into
# the fleet's journaled spec); the daemon's own conf only needs the
# scheduler keys.
FLEET_PREFIX = TONY_PREFIX + "fleet."
# Replica-count bounds. min 0 = scale-to-zero: an idle fleet releases
# every slice back to the warm pool and cold-wakes on the next request.
K_FLEET_MIN_REPLICAS = FLEET_PREFIX + "min-replicas"
K_FLEET_MAX_REPLICAS = FLEET_PREFIX + "max-replicas"
# Autoscaler on/off (off = fleet stays at its created/`tony fleet
# scale` size; bounds still enforced).
K_FLEET_AUTOSCALE = FLEET_PREFIX + "autoscale"
# Scale-up triggers: queued requests per ready replica, and p95 TTFT
# (ms, 0 disables the latency signal). Both must persist for
# hysteresis-ticks daemon ticks, and actions are rate-limited by
# cooldown-ms.
K_FLEET_SCALE_UP_QUEUE_DEPTH = FLEET_PREFIX + "scale-up-queue-depth"
K_FLEET_TTFT_TARGET_MS = FLEET_PREFIX + "ttft-target-ms"
K_FLEET_HYSTERESIS_TICKS = FLEET_PREFIX + "hysteresis-ticks"
K_FLEET_COOLDOWN_MS = FLEET_PREFIX + "cooldown-ms"
# Scale-down trigger: empty queue AND slot utilization <= scale-down-
# util, sustained for scale-down-idle-ms.
K_FLEET_SCALE_DOWN_UTIL = FLEET_PREFIX + "scale-down-util"
K_FLEET_SCALE_DOWN_IDLE_MS = FLEET_PREFIX + "scale-down-idle-ms"
# Router front door: bind port (0 = ephemeral, advertised in the
# daemon's fleet state), retry budget for idempotent requests whose
# replica died mid-flight, and replica /healthz poll cadence.
K_FLEET_ROUTER_PORT = FLEET_PREFIX + "router-port"
K_FLEET_ROUTER_RETRIES = FLEET_PREFIX + "router-retries"
K_FLEET_HEALTH_INTERVAL_MS = FLEET_PREFIX + "health-interval-ms"
# Prefill/decode disaggregation (experimental, default symmetric): the
# first prefill-replicas replicas only prefill and export KV rows; the
# rest only decode from injected KV.
K_FLEET_DISAGGREGATION = FLEET_PREFIX + "disaggregation"
K_FLEET_PREFILL_REPLICAS = FLEET_PREFIX + "prefill-replicas"

# --- multi-tenant scheduler (scheduler/) ------------------------------------
# A persistent daemon that queues many jobs, gang-schedules them onto a
# POOL of slices, and reuses warm slices across jobs: a released slice
# keeps its bootstrap, staged venv blobs, and XLA compile cache, so the
# next compatible job skips provisioning + staging and compiles warm.
SCHEDULER_PREFIX = TONY_PREFIX + "scheduler."
# host:port of a running scheduler daemon. Non-empty switches the client
# submit path from "spawn a coordinator" to "POST the staged app dir to
# the scheduler" (the YARN-RM-submission analogue).
K_SCHED_ADDRESS = SCHEDULER_PREFIX + "address"
# The daemon's working dir (slices, staging, scheduler.addr,
# scheduler-state.json). Discovery fallback for `tony ps|queue`, the
# history server's queue/pool panel, and the daemon itself.
K_SCHED_BASE_DIR = SCHEDULER_PREFIX + "base-dir"
# Daemon bind port (0 = ephemeral; the bound port is advertised in
# <base_dir>/scheduler.addr the way coordinators advertise theirs).
K_SCHED_PORT = SCHEDULER_PREFIX + "port"
# Scheduling-loop tick, ms: queue pops, lease renewals, expiry sweeps.
K_SCHED_TICK_MS = SCHEDULER_PREFIX + "tick-interval"
# Pool capacity: slices provisioned at most, across all profiles.
K_SCHED_MAX_SLICES = SCHEDULER_PREFIX + "max-slices"
# A FREE slice idle longer than this is torn down (cloud slices bill
# while warm); 0 = keep warm forever.
K_SCHED_IDLE_TIMEOUT_MS = SCHEDULER_PREFIX + "slice-idle-timeout"
# A LEASED slice whose runner stops renewing for this long is reclaimed
# and retired (the holder may have crashed mid-job; its state is suspect).
K_SCHED_LEASE_TIMEOUT_MS = SCHEDULER_PREFIX + "lease-timeout"
# Simulated control-plane latency for LOCAL slice provisioning, ms —
# models the minutes a real TPU queued-resource create takes; 0 for
# tests that only care about ordering.
K_SCHED_LOCAL_PROVISION_MS = SCHEDULER_PREFIX + "local-provision-ms"
# Per-job submission attributes (read from the SUBMITTED job's conf).
K_SCHED_PRIORITY = SCHEDULER_PREFIX + "priority"   # higher preempts lower
K_SCHED_TENANT = SCHEDULER_PREFIX + "tenant"
# Max concurrently-RUNNING jobs per tenant (0 = unlimited), plus
# per-tenant overrides as "alice=2,bob=1".
K_SCHED_TENANT_QUOTA = SCHEDULER_PREFIX + "tenant-quota"
K_SCHED_TENANT_QUOTAS = SCHEDULER_PREFIX + "tenant-quotas"
# May a higher-priority submit preempt a running lower-priority job?
# (Preempted jobs requeue and resume from their best checkpoint step.)
K_SCHED_PREEMPTION = SCHEDULER_PREFIX + "preemption-enabled"
# --- control-plane HA (scheduler/{journal,election}.py) ---------------
# Stable identity of this daemon in the leader election's heartbeat
# file (default: hostname-pid). An active/standby pair needs distinct
# ids on a shared base-dir.
K_SCHED_HA_NODE_ID = SCHEDULER_PREFIX + "ha-node-id"
# Leadership lease, ms: the leader heartbeats at a third of this; a
# standby whose view of the heartbeat is staler than this steals the
# epoch. Failover detection latency trades directly against heartbeat
# I/O.
K_SCHED_HA_LEASE_MS = SCHEDULER_PREFIX + "ha-lease-ms"
# Journal compaction threshold: once this many records accumulate past
# the last snapshot, the next publish folds them in and truncates the
# journal (recovery replays at most this many records).
K_SCHED_HA_JOURNAL_MAX = SCHEDULER_PREFIX + "ha-journal-max-records"
# Size/age companions to the record-count threshold: the journal also
# rotates once its on-disk byte size or oldest-record age crosses these
# (0 = that dimension disabled). A quiet fleet with a chatty metric
# stream should not grow an unbounded journal just because record COUNT
# stays under ha-journal-max-records between publishes.
K_SCHED_JOURNAL_MAX_BYTES = SCHEDULER_PREFIX + "journal-max-bytes"
K_SCHED_JOURNAL_MAX_AGE_MS = SCHEDULER_PREFIX + "journal-max-age-ms"
# Run each attempt's coordinator as a DETACHED subprocess
# (start_new_session) instead of a daemon thread: the attempt survives
# the daemon's death, and a recovered/standby daemon re-attaches it via
# its pid file + observability port instead of restarting it. Costs the
# in-process spare-pool healing seam (detached coordinators heal like
# standalone ones).
K_SCHED_DETACHED = SCHEDULER_PREFIX + "detached-attempts"
# Thin-client resilience across a failover window: how many times (and
# from what base backoff, doubling each retry) submit/monitor/ps/queue
# retry a scheduler RPC that connection-refused — a daemon restart or
# standby takeover must not fail every in-flight client.
K_SCHED_CLIENT_RETRIES = SCHEDULER_PREFIX + "client-retries"
K_SCHED_CLIENT_BACKOFF_MS = SCHEDULER_PREFIX + "client-backoff-ms"

# --- storage / staging -----------------------------------------------------
# Descoped from the reference (README "descoped keys"): tony.other.namenodes
# (extra HDFS delegation tokens) and tony.yarn.queue have no substrate here.
K_STAGING_LOCATION = TONY_PREFIX + "staging.location"    # dir or gs:// URI
# Cap on the content-hash venv blob store under <staging>/blobs/ (the
# dedup store client._stage fills): after each stage, least-recently-
# used blobs beyond this many bytes are pruned. 0 = unbounded (operator
# owns cleanup). A dedup HIT refreshes the blob's mtime, so live venvs
# stay resident.
K_STAGING_BLOB_MAX_BYTES = TONY_PREFIX + "staging.blob-store-max-bytes"
K_LIB_PATH = TONY_PREFIX + "lib.path"                    # staged framework copy for executors
K_HISTORY_LOCATION = TONY_PREFIX + "history.location"
# Cap on events persisted per job into history (history/writer.py).
# Past the cap the MIDDLE of the timeline is dropped — the submission
# edge and the death edge are what debugging needs — and a
# ``{"truncated": true, "dropped": N}`` marker record is written where
# the gap is, which the reader and ``tony doctor`` surface.
K_HISTORY_MAX_EVENTS = TONY_PREFIX + "history.max-events"
# CheckpointManager directory (dir or gs:// URI). When set, the coordinator
# probes it between sessions for the newest complete step: retried tasks
# get TONY_RESUME_STEP/TONY_CHECKPOINT_DIR, and progress refreshes the
# retry budget. Empty = no probe (user scripts still checkpoint wherever
# they like; they just resume without coordinator help).
K_CHECKPOINT_LOCATION = TONY_PREFIX + "checkpoint.location"

# --- fault injection (resilience/faults.py) --------------------------------
# Inline JSON plan or a path to one; "" = no faults. Replaces the
# deprecated TEST_AM_CRASH / TEST_WORKER_TERMINATION env flags.
K_FAULT_PLAN = TONY_PREFIX + "fault.plan"

# --- history server (TonyConfigurationKeys.java:41-63) ---------------------
K_HTTP_PORT = TONY_PREFIX + "http.port"                  # "disabled" or int
K_HTTPS_PORT = TONY_PREFIX + "https.port"
K_HTTPS_CERT = TONY_PREFIX + "https.cert"                # PEM cert chain path
K_HTTPS_KEY = TONY_PREFIX + "https.key"                  # PEM private key path
K_SECRET_KEY = TONY_PREFIX + "secret.key"

# --- fleet observability rollup (observability/rollup.py, hosted by the
# history server) ------------------------------------------------------------
ROLLUP_PREFIX = TONY_PREFIX + "rollup."
K_ROLLUP_ENABLED = ROLLUP_PREFIX + "enabled"
# Collector tick period (discover + scrape + fold + record), ms.
K_ROLLUP_INTERVAL_MS = ROLLUP_PREFIX + "interval-ms"
# A target that stops answering keeps serving its last-good snapshot
# until this staleness bound, then its gauges/histograms are evicted
# from the fleet view (counter totals persist — the work happened).
K_ROLLUP_STALE_AFTER_MS = ROLLUP_PREFIX + "stale-after-ms"
# Per-target scrape timeout, ms. One slow coordinator must not stretch
# the whole tick past the interval.
K_ROLLUP_SCRAPE_TIMEOUT_MS = ROLLUP_PREFIX + "scrape-timeout-ms"
# TSDB retention per resolution, seconds: raw tick samples, 1-minute
# downsamples, 10-minute downsamples. Queries pick the finest
# resolution whose retention still covers the requested range.
K_ROLLUP_RETENTION_RAW_S = ROLLUP_PREFIX + "retention-raw-s"
K_ROLLUP_RETENTION_1M_S = ROLLUP_PREFIX + "retention-1m-s"
K_ROLLUP_RETENTION_10M_S = ROLLUP_PREFIX + "retention-10m-s"

# --- SLO objectives over the rolled-up series (observability/rollup.py) -----
SLO_PREFIX = TONY_PREFIX + "slo."
K_SLO_ENABLED = SLO_PREFIX + "enabled"
# Objective targets. Goodput/MFU are floors (burn = target/actual);
# TTFT is a ceiling (burn = actual/target); 0 disables that objective.
# MFU ships disabled — absolute MFU varies too much across hardware for
# a default floor to mean anything.
K_SLO_GOODPUT_RATIO_TARGET = SLO_PREFIX + "goodput-ratio-target"
K_SLO_SERVING_TTFT_P95_MS = SLO_PREFIX + "serving-ttft-p95-ms"
K_SLO_MFU_FLOOR = SLO_PREFIX + "mfu-floor"
# Multi-window burn evaluation: breach requires BOTH the fast and slow
# window's burn rate past the threshold (fast = responsive, slow =
# flap-resistant). Budget-period scales burn into an error-budget-
# remaining estimate (default 30 days).
K_SLO_FAST_WINDOW_S = SLO_PREFIX + "fast-window-s"
K_SLO_SLOW_WINDOW_S = SLO_PREFIX + "slow-window-s"
K_SLO_BURN_THRESHOLD = SLO_PREFIX + "burn-threshold"
K_SLO_BUDGET_PERIOD_S = SLO_PREFIX + "budget-period-s"

# --- client ---------------------------------------------------------------
K_CLIENT_MONITOR_INTERVAL_MS = TONY_PREFIX + "client.monitor-interval"

# --- profiler / tensorboard seam ------------------------------------------
K_PROFILER_ENABLED = TONY_PREFIX + "profiler.enabled"
K_TENSORBOARD_ENABLED = TONY_PREFIX + "tensorboard.enabled"

# --- preflight static analysis (analysis/) ---------------------------------
# off | warn | strict — strict refuses submission on any error finding.
K_PREFLIGHT_MODE = TONY_PREFIX + "preflight.mode"

# --- version info (gradle/version-info.gradle analogue; stamped into the
# conf at submission by tony_tpu.version.inject_version_info) ---------------
VERSION_INFO_PREFIX = TONY_PREFIX + "version-info."
K_VERSION_INFO_VERSION = VERSION_INFO_PREFIX + "version"
K_VERSION_INFO_REVISION = VERSION_INFO_PREFIX + "revision"
K_VERSION_INFO_BRANCH = VERSION_INFO_PREFIX + "branch"
K_VERSION_INFO_USER = VERSION_INFO_PREFIX + "user"
K_VERSION_INFO_DATE = VERSION_INFO_PREFIX + "date"
K_VERSION_INFO_URL = VERSION_INFO_PREFIX + "url"

DEFAULTS: dict[str, object] = {
    K_APPLICATION_NAME: "TonyTpuApplication",
    K_FRAMEWORK: "jax",
    K_IS_SINGLE_NODE: False,
    K_ENABLE_PREPROCESS: False,
    K_APPLICATION_TIMEOUT: 0,
    K_CLIENT_CONNECT_RETRIES: 3,
    K_CLIENT_CONNECT_TIMEOUT_MS: 60000,
    K_SECURITY_ENABLED: False,
    K_DOCKER_ENABLED: False,
    K_DOCKER_IMAGE: "",
    K_EXECUTES: "",
    K_SRC_DIR: "",
    K_PYTHON_VENV: "",
    K_PYTHON_BINARY: "python",
    K_TASK_PARAMS: "",
    K_SHELL_ENV: "",
    K_TASK_HEARTBEAT_INTERVAL_MS: 1000,
    K_TASK_MAX_MISSED_HEARTBEATS: 25,
    K_TASK_REGISTRATION_TIMEOUT_MS: 0,
    K_TASK_REGISTRATION_RETRY_MS: 500,
    K_TASK_MAX_HB_SEND_FAILURES: 5,
    K_RPC_CALL_TIMEOUT_MS: 60000,
    K_AM_RETRY_COUNT: 0,
    K_AM_RETRY_BACKOFF_BASE_MS: 1000,
    K_AM_RETRY_BACKOFF_MAX_MS: 60000,
    K_AM_RETRY_JITTER_SEED: 0,
    K_AM_MONITOR_INTERVAL_MS: 200,
    K_AM_RPC_PORT_RANGE: "10000-15000",
    K_AM_STOP_GRACE_MS: 30000,
    K_AM_HTTP_PORT: "0",
    K_CHIEF_NAME: "worker",
    K_CHIEF_INDEX: "0",
    K_WORKER_TIMEOUT: 0,
    K_TPU_TOPOLOGY: "",
    K_TPU_ACCELERATOR_TYPE: "",
    K_TPU_SLICE_STRICT: False,
    K_GCP_PROJECT: "",
    K_GCP_ZONE: "",
    K_GCP_RUNTIME_VERSION: "",  # empty = per-generation default (cloud.gcp)
    K_GCP_NETWORK: "",
    K_AM_ADDRESS_HOST: "",
    K_IO_PREFETCH_DEPTH: 2,
    K_IO_READ_WORKERS: 4,
    K_IO_CHUNK_RECORDS: 256,
    K_COMPILE_CACHE_DIR: "",
    K_COMPILE_CACHE_ENABLED: True,
    K_COMPILE_MIN_ENTRY_SIZE: 0,
    K_HEALTH_ENABLED: True,
    K_HEALTH_STRAGGLER_THRESHOLD: 3.0,
    K_HEALTH_STALL_TIMEOUT_MS: 60000,
    K_HEALTH_LOSS_SPIKE_FACTOR: 10.0,
    K_HEALTH_HB_JITTER_FACTOR: 5.0,
    K_HEALTH_IO_STALL_RATIO: 0.5,
    K_HEALTH_MFU_COLLAPSE_RATIO: 0.5,
    K_HEALTH_COMMS_BOUND_RATIO: 0.5,
    K_HEALTH_ALERT_COOLDOWN_MS: 30000,
    K_HEALTH_FLIGHT_LIMIT: 256,
    K_HEAL_ENABLED: False,
    K_HEAL_CONFIRM_WINDOW_MS: 10000,
    K_HEAL_MAX_EVICTIONS: 2,
    K_HEAL_MIN_SHRINK_FRACTION: 0.5,
    K_HEAL_SPECULATIVE: False,
    K_HEAL_SPECULATIVE_DELAY_MS: 30000,
    K_CKPT_PIPELINE_DEPTH: 2,
    K_CKPT_PERSIST_WORKERS: 1,
    K_CKPT_DIFFERENTIAL: True,
    K_CKPT_FULL_EVERY: 5,
    K_CKPT_BG_SNAPSHOT: False,
    K_CKPT_MIGRATE_ON_PREEMPT: True,
    K_CKPT_MIGRATE_TIMEOUT_MS: 20000,
    K_CKPT_FLUSH_ON_EVICT: True,
    K_CKPT_EVICT_FLUSH_WAIT_MS: 5000,
    K_GOODPUT_ENABLED: True,
    K_GOODPUT_CHIPS: 0,
    K_STEPSTATS_ENABLED: True,
    K_STEPSTATS_CALIBRATE: True,
    K_STEPSTATS_WINDOW: 32,
    K_TUNE_ENABLED: True,
    K_TUNE_TRIAL_BUDGET: 12,
    K_TUNE_RECORD_DIR: "",
    K_PROFILE_DURATION_MS: 2000,
    K_PROFILE_HBM_INTERVAL_MS: 5000,
    K_PROXY_CONNECT_TIMEOUT_MS: 5000,
    K_SERVING_SLOTS: 8,
    K_SERVING_PREFILL_CHUNK: 32,
    K_SERVING_DECODE_WINDOW: 1,
    K_SERVING_MAX_QUEUE: 1024,
    K_SERVING_PORT: 0,
    K_FLEET_MIN_REPLICAS: 1,
    K_FLEET_MAX_REPLICAS: 4,
    K_FLEET_AUTOSCALE: True,
    K_FLEET_SCALE_UP_QUEUE_DEPTH: 4,
    K_FLEET_TTFT_TARGET_MS: 0,
    K_FLEET_HYSTERESIS_TICKS: 2,
    K_FLEET_COOLDOWN_MS: 15000,
    K_FLEET_SCALE_DOWN_UTIL: 0.25,
    K_FLEET_SCALE_DOWN_IDLE_MS: 30000,
    K_FLEET_ROUTER_PORT: 0,
    K_FLEET_ROUTER_RETRIES: 2,
    K_FLEET_HEALTH_INTERVAL_MS: 1000,
    K_FLEET_DISAGGREGATION: False,
    K_FLEET_PREFILL_REPLICAS: 0,
    K_SCHED_ADDRESS: "",
    K_SCHED_BASE_DIR: "",
    K_SCHED_PORT: 0,
    K_SCHED_TICK_MS: 200,
    K_SCHED_MAX_SLICES: 4,
    K_SCHED_IDLE_TIMEOUT_MS: 600000,
    K_SCHED_LEASE_TIMEOUT_MS: 60000,
    K_SCHED_LOCAL_PROVISION_MS: 0,
    K_SCHED_PRIORITY: 0,
    K_SCHED_TENANT: "default",
    K_SCHED_TENANT_QUOTA: 0,
    K_SCHED_TENANT_QUOTAS: "",
    K_SCHED_PREEMPTION: True,
    K_SCHED_HA_NODE_ID: "",
    K_SCHED_HA_LEASE_MS: 5000,
    K_SCHED_HA_JOURNAL_MAX: 4096,
    K_SCHED_JOURNAL_MAX_BYTES: 16777216,
    K_SCHED_JOURNAL_MAX_AGE_MS: 86400000,
    K_SCHED_DETACHED: False,
    K_SCHED_CLIENT_RETRIES: 5,
    K_SCHED_CLIENT_BACKOFF_MS: 250,
    K_STAGING_LOCATION: "",
    K_STAGING_BLOB_MAX_BYTES: 0,
    K_LIB_PATH: "",
    K_HISTORY_LOCATION: "",
    K_HISTORY_MAX_EVENTS: 20000,
    K_CHECKPOINT_LOCATION: "",
    K_FAULT_PLAN: "",
    K_ROLLUP_ENABLED: True,
    K_ROLLUP_INTERVAL_MS: 15000,
    K_ROLLUP_STALE_AFTER_MS: 120000,
    K_ROLLUP_SCRAPE_TIMEOUT_MS: 2000,
    K_ROLLUP_RETENTION_RAW_S: 3600,
    K_ROLLUP_RETENTION_1M_S: 86400,
    K_ROLLUP_RETENTION_10M_S: 604800,
    K_SLO_ENABLED: True,
    K_SLO_GOODPUT_RATIO_TARGET: 0.9,
    K_SLO_SERVING_TTFT_P95_MS: 2000.0,
    K_SLO_MFU_FLOOR: 0.0,
    K_SLO_FAST_WINDOW_S: 300,
    K_SLO_SLOW_WINDOW_S: 3600,
    K_SLO_BURN_THRESHOLD: 1.0,
    K_SLO_BUDGET_PERIOD_S: 2592000,
    K_HTTP_PORT: "disabled",
    K_HTTPS_PORT: 19886,
    K_HTTPS_CERT: "",
    K_HTTPS_KEY: "",
    K_SECRET_KEY: "dev",
    K_CLIENT_MONITOR_INTERVAL_MS: 1000,
    K_PROFILER_ENABLED: False,
    K_TENSORBOARD_ENABLED: True,
    K_PREFLIGHT_MODE: "warn",
    K_VERSION_INFO_VERSION: "",
    K_VERSION_INFO_REVISION: "",
    K_VERSION_INFO_BRANCH: "",
    K_VERSION_INFO_USER: "",
    K_VERSION_INFO_DATE: "",
    K_VERSION_INFO_URL: "",
}

# --- dynamic per-job-type key families -------------------------------------
# Analogue of TonyConfigurationKeys.getInstancesKey/... (:124-151) and the
# discovery regex ``tony\.([a-z]+)\.instances`` (:119).
INSTANCES_REGEX = r"tony\.([a-z][a-z0-9_]*)\.instances$"
DEFAULT_MEMORY = "2g"
DEFAULT_VCORES = 1
DEFAULT_GPUS = 0
DEFAULT_TPUS = 0


def instances_key(job_name: str) -> str:
    return f"{TONY_PREFIX}{job_name}.instances"


def memory_key(job_name: str) -> str:
    return f"{TONY_PREFIX}{job_name}.memory"


def vcores_key(job_name: str) -> str:
    return f"{TONY_PREFIX}{job_name}.vcores"


def gpus_key(job_name: str) -> str:
    return f"{TONY_PREFIX}{job_name}.gpus"


def tpus_key(job_name: str) -> str:
    return f"{TONY_PREFIX}{job_name}.tpus"


def resources_key(job_name: str) -> str:
    return f"{TONY_PREFIX}{job_name}.resources"


def env_key(job_name: str) -> str:
    return f"{TONY_PREFIX}{job_name}.env"


def default_instances(job_name: str) -> int:
    """ps/worker default to 1 instance, everything else 0
    (TonyConfigurationKeys.getDefaultInstances:128-136)."""
    return 1 if job_name in ("ps", "worker") else 0
