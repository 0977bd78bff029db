"""Well-known names: environment variables, file names, job/task names.

TPU-native analogue of the reference's ``Constants.java``
(tony-core/src/main/java/com/linkedin/tony/Constants.java:1-92).  The
TF/PyTorch env names are kept byte-identical so that unmodified reference
training scripts keep working; the JAX block is new (the reference has no
JAX runtime).
"""

# ---------------------------------------------------------------------------
# Framework env contract: TensorFlow (Constants.java TF block)
# ---------------------------------------------------------------------------
TF_CONFIG = "TF_CONFIG"
CLUSTER_SPEC = "CLUSTER_SPEC"

# ---------------------------------------------------------------------------
# Framework env contract: PyTorch (Constants.java:25-28)
# ---------------------------------------------------------------------------
RANK = "RANK"
WORLD = "WORLD"
WORLD_SIZE = "WORLD_SIZE"
INIT_METHOD = "INIT_METHOD"
MASTER_ADDR = "MASTER_ADDR"
MASTER_PORT = "MASTER_PORT"

# ---------------------------------------------------------------------------
# Framework env contract: JAX (new — the TPU-native runtime).
# JAX_COORDINATOR_ADDRESS is read natively by jax.distributed.initialize()
# (jax/_src/distributed.py:77); process id/count have no native env fallback,
# so we export TONY_* names and provide tony_tpu.runtime.initialize().
# ---------------------------------------------------------------------------
JAX_COORDINATOR_ADDRESS = "JAX_COORDINATOR_ADDRESS"
TONY_COORDINATOR_ADDRESS = "TONY_COORDINATOR_ADDRESS"
TONY_NUM_PROCESSES = "TONY_NUM_PROCESSES"
TONY_PROCESS_ID = "TONY_PROCESS_ID"
JAX_LOCAL_DEVICE_IDS = "JAX_LOCAL_DEVICE_IDS"
TONY_SLICE_TOPOLOGY = "TONY_SLICE_TOPOLOGY"
# Per-task slice identity for multi-slice jobs (num_slices > 1): which
# slice this host belongs to and its index within the slice — set by the
# coordinator at launch (SlicePlan is per job type, task index tiles
# hosts_per_slice at a time).
TONY_SLICE_INDEX = "TONY_SLICE_INDEX"
TONY_SLICE_PROCESS_ID = "TONY_SLICE_PROCESS_ID"
TONY_NUM_SLICES = "TONY_NUM_SLICES"
# Megascale (DCN inter-slice transport) env the JAX runtime injects for
# multi-slice jobs — libtpu reads these to bring up the cross-slice mesh.
MEGASCALE_COORDINATOR_ADDRESS = "MEGASCALE_COORDINATOR_ADDRESS"
MEGASCALE_NUM_SLICES = "MEGASCALE_NUM_SLICES"
MEGASCALE_SLICE_ID = "MEGASCALE_SLICE_ID"
TONY_MESH_SHAPE = "TONY_MESH_SHAPE"

# ---------------------------------------------------------------------------
# Task identity env (Constants.java JOB_NAME/TASK_INDEX/TASK_NUM/SESSION_ID)
# ---------------------------------------------------------------------------
JOB_NAME = "JOB_NAME"
TASK_INDEX = "TASK_INDEX"
TASK_NUM = "TASK_NUM"
SESSION_ID = "SESSION_ID"
TB_PORT = "TB_PORT"
PROFILER_PORT = "PROFILER_PORT"
TONY_LOG_DIR = "TONY_LOG_DIR"
# Preprocess / single-node AM mode (Constants.java:34,48)
PREPROCESSING_JOB = "PREPROCESSING_JOB"
TASK_PARAM_KEY = "MODEL_PARAMS"
# Failure-aware retry env (resilience/): the newest complete checkpoint
# step the coordinator observed before retrying — retried sessions resume
# from it instead of recomputing from step 0 — and the checkpoint dir the
# coordinator probes (exported when tony.checkpoint.location is set).
TONY_RESUME_STEP = "TONY_RESUME_STEP"
TONY_CHECKPOINT_DIR = "TONY_CHECKPOINT_DIR"
# Raw tony.fault.plan JSON, forwarded into the user process so
# CheckpointManager can honor fail_checkpoint_write faults.
TONY_FAULT_PLAN = "TONY_FAULT_PLAN"
# Observability env (observability/): the job's trace id, minted by the
# coordinator and propagated coordinator -> executor -> user process so
# every span lands in one distributed trace; and the file the user
# process publishes its metrics snapshot to (the executor reads it and
# piggybacks the snapshot on its heartbeat).
TONY_TRACE_ID = "TONY_TRACE_ID"
TONY_METRICS_FILE = "TONY_METRICS_FILE"
# Data-plane tuning (tony.io.* conf → user-process env → io/reader.py
# defaults): prefetch depth, read workers, records per chunk.
TONY_IO_PREFETCH_DEPTH = "TONY_IO_PREFETCH_DEPTH"
TONY_IO_READ_WORKERS = "TONY_IO_READ_WORKERS"
TONY_IO_CHUNK_RECORDS = "TONY_IO_CHUNK_RECORDS"
# Persistent XLA compile cache (tony.compile.* conf → user-process env →
# parallel/plan.py configure_compile_cache): retried/resumed/re-submitted
# runs of an unchanged program skip compilation entirely.
TONY_COMPILE_CACHE_DIR = "TONY_COMPILE_CACHE_DIR"
TONY_COMPILE_CACHE_ENABLED = "TONY_COMPILE_CACHE_ENABLED"
TONY_COMPILE_MIN_ENTRY_SIZE = "TONY_COMPILE_MIN_ENTRY_SIZE"
# Continuous device-memory telemetry (tony.profile.hbm-interval conf →
# user-process env → runtime.initialize starts the HBM gauge monitor,
# observability/profiling.py; "0" disables).
TONY_PROFILE_HBM_INTERVAL_MS = "TONY_PROFILE_HBM_INTERVAL_MS"
# Continuous-batching serving engine (tony.serving.* conf → user-process
# env → examples/lm_serve.py / tony_tpu.serving defaults).
TONY_SERVING_SLOTS = "TONY_SERVING_SLOTS"
TONY_SERVING_PREFILL_CHUNK = "TONY_SERVING_PREFILL_CHUNK"
TONY_SERVING_DECODE_WINDOW = "TONY_SERVING_DECODE_WINDOW"
TONY_SERVING_MAX_QUEUE = "TONY_SERVING_MAX_QUEUE"
TONY_SERVING_PORT = "TONY_SERVING_PORT"
# Step anatomy (tony.stepstats.* conf → user-process env →
# observability/stepstats.py): per-step phase/MFU telemetry and the
# live planner-calibration feedback loop.
TONY_STEPSTATS_ENABLED = "TONY_STEPSTATS_ENABLED"
TONY_STEPSTATS_CALIBRATE = "TONY_STEPSTATS_CALIBRATE"
TONY_STEPSTATS_WINDOW = "TONY_STEPSTATS_WINDOW"
# Measured program autotuner (tony.tune.* conf → user-process env →
# parallel/autotune.py): persisted per-(model, topology, jax version)
# tune records — consumption switch, search trial budget, and the
# record dir (empty = beside the compile cache).
TONY_TUNE_ENABLED = "TONY_TUNE_ENABLED"
TONY_TUNE_TRIAL_BUDGET = "TONY_TUNE_TRIAL_BUDGET"
TONY_TUNE_RECORD_DIR = "TONY_TUNE_RECORD_DIR"
# Self-healing actuation (coordinator/healing.py): the incarnation of a
# task instance — 0 at first launch, bumped each time the coordinator
# evicts and replaces the task mid-job so stale executors/registrations/
# heartbeats fence out — and the JSON reshard note an elastically-shrunk
# gang's user processes receive (the coordinator's candidate_plans pick
# for the surviving topology: plan key + mesh axes + process count).
TONY_TASK_INCARNATION = "TONY_TASK_INCARNATION"
TONY_RESHARD_PLAN = "TONY_RESHARD_PLAN"
# The gang generation a (re)launched executor should CONFIRM when it
# registers: registrations echo it so a fold bumping the generation
# between a resync order and its registration cannot mark the task
# confirmed for a patch whose payload it never received.
TONY_GANG_GENERATION = "TONY_GANG_GENERATION"
# Checkpoint pipeline (tony.ckpt.* conf → user-process env →
# checkpoint/manager.py defaults): saves in flight behind the bounded
# pipeline, persist upload workers, differential on/off + full-save
# compaction interval, background D2H snapshot (safe only for
# non-donating train steps), and the flush-signal file the executor
# writes when a coordinator ``ckpt_flush`` command rides its heartbeat
# reply (live migration's "snapshot now, then die").
TONY_CKPT_PIPELINE_DEPTH = "TONY_CKPT_PIPELINE_DEPTH"
TONY_CKPT_PERSIST_WORKERS = "TONY_CKPT_PERSIST_WORKERS"
TONY_CKPT_DIFFERENTIAL = "TONY_CKPT_DIFFERENTIAL"
TONY_CKPT_FULL_EVERY = "TONY_CKPT_FULL_EVERY"
TONY_CKPT_BG_SNAPSHOT = "TONY_CKPT_BG_SNAPSHOT"
TONY_CKPT_FLUSH_FILE = "TONY_CKPT_FLUSH_FILE"

# The env contract forwarded into docker containers (utils.build_user_command
# emits one `-e VAR` per name; values resolve from the launching env).
DOCKER_FORWARD_ENV = (
    JOB_NAME, TASK_INDEX, TASK_NUM, SESSION_ID,
    CLUSTER_SPEC, TF_CONFIG,
    INIT_METHOD, RANK, WORLD, WORLD_SIZE, MASTER_ADDR, MASTER_PORT,
    JAX_COORDINATOR_ADDRESS, TONY_COORDINATOR_ADDRESS,
    TONY_NUM_PROCESSES, TONY_PROCESS_ID, TONY_SLICE_TOPOLOGY,
    TONY_SLICE_INDEX, TONY_SLICE_PROCESS_ID, TONY_NUM_SLICES,
    MEGASCALE_COORDINATOR_ADDRESS, MEGASCALE_NUM_SLICES, MEGASCALE_SLICE_ID,
    TB_PORT, PROFILER_PORT, TONY_LOG_DIR, PREPROCESSING_JOB, TASK_PARAM_KEY,
    TONY_RESUME_STEP, TONY_CHECKPOINT_DIR, TONY_FAULT_PLAN,
    TONY_TRACE_ID, TONY_METRICS_FILE,
    TONY_IO_PREFETCH_DEPTH, TONY_IO_READ_WORKERS, TONY_IO_CHUNK_RECORDS,
    TONY_COMPILE_CACHE_DIR, TONY_COMPILE_CACHE_ENABLED,
    TONY_COMPILE_MIN_ENTRY_SIZE, TONY_PROFILE_HBM_INTERVAL_MS,
    TONY_SERVING_SLOTS, TONY_SERVING_PREFILL_CHUNK,
    TONY_SERVING_DECODE_WINDOW, TONY_SERVING_MAX_QUEUE, TONY_SERVING_PORT,
    TONY_STEPSTATS_ENABLED, TONY_STEPSTATS_CALIBRATE, TONY_STEPSTATS_WINDOW,
    TONY_TUNE_ENABLED, TONY_TUNE_TRIAL_BUDGET, TONY_TUNE_RECORD_DIR,
    TONY_TASK_INCARNATION, TONY_RESHARD_PLAN, TONY_GANG_GENERATION,
    TONY_CKPT_PIPELINE_DEPTH, TONY_CKPT_PERSIST_WORKERS,
    TONY_CKPT_DIFFERENTIAL, TONY_CKPT_FULL_EVERY, TONY_CKPT_BG_SNAPSHOT,
    TONY_CKPT_FLUSH_FILE,
)

# The executor's self-termination code after losing the coordinator (N
# consecutive failed heartbeat sends): distinct from user-script codes so
# the failure classifier reads it as INFRA, not a program bug.
EXIT_CODE_LOST_COORDINATOR = 87

# Executor launch env (analogue of TonyApplicationMaster.java:1053-1055).
TONY_AM_ADDRESS = "TONY_AM_ADDRESS"
# gs:// URI of the staged app dir — TPU-VM bootstraps localize from it
# (cloud/bootstrap.py), the YARN-resource-localization analogue.
TONY_STAGED_URI = "TONY_STAGED_URI"
TONY_EXECUTOR_TOKEN = "TONY_EXECUTOR_TOKEN"  # role credential, not the secret
TONY_TASK_COMMAND = "TONY_TASK_COMMAND"
TONY_CONF_PATH = "TONY_CONF_PATH"

# ---------------------------------------------------------------------------
# File names (Constants.java tony.zip / tony-final.xml)
# ---------------------------------------------------------------------------
TONY_ARCHIVE = "tony.zip"
TONY_FINAL_CONF = "tony-final.json"
TONY_EXECUTOR_CONF = "tony-executor.json"  # secret-stripped, executor audience
TONY_DEFAULT_CONF = "tony-default.json"
TONY_SITE_CONF = "tony-site.json"
TONY_JOB_CONF = "tony.json"
TONY_STAGING_DIR = ".tony"
TONY_CONF_DIR_ENV = "TONY_CONF_DIR"

# ---------------------------------------------------------------------------
# Preflight static analysis (tony.preflight.mode; analysis/preflight.py)
# ---------------------------------------------------------------------------
PREFLIGHT_OFF = "off"        # never run
PREFLIGHT_WARN = "warn"      # run, report, submit anyway
PREFLIGHT_STRICT = "strict"  # run, refuse submission on any error finding
# Inline suppression marker matched by analysis/script_lint.py:
#   some_code()  # tony: noqa[TONY-S101]
LINT_NOQA_MARKER = "tony: noqa"

# ---------------------------------------------------------------------------
# Job / task names
# ---------------------------------------------------------------------------
WORKER_JOB_NAME = "worker"
PS_JOB_NAME = "ps"
CHIEF_JOB_NAME = "chief"
EVALUATOR_JOB_NAME = "evaluator"
NOTEBOOK_JOB_NAME = "notebook"
DRIVER_JOB_NAME = "driver"
AM_NAME = "am"

# ---------------------------------------------------------------------------
# Test / fault-injection env flags (Constants.java:69-74).  Each one is read
# at a single well-defined point; see tests/test_fault_injection.py.
# ---------------------------------------------------------------------------
TEST_AM_CRASH = "TEST_AM_CRASH"                          # coordinator exits on purpose
TEST_WORKER_TERMINATION = "TEST_WORKER_TERMINATION"      # coordinator kills workers when chief registers
TEST_TASK_EXECUTOR_HANG = "TEST_TASK_EXECUTOR_HANG"      # executor sleeps then dies
TEST_TASK_EXECUTOR_NUM_HB_MISS = "TEST_TASK_EXECUTOR_NUM_HB_MISS"  # heartbeater skips N pings
TEST_TASK_EXECUTOR_SKEW = "TEST_TASK_EXECUTOR_SKEW"      # "job#idx#ms" straggler simulation
