"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh so every
sharding/parallelism test runs without TPU hardware (the tony-mini idea from
the reference test strategy — SURVEY.md §4 — applied to devices), and arm
the runtime sync sanitizer so every e2e doubles as a race probe."""

import os

# Sync sanitizer ON for the whole tier-1 suite (opt-out with =0): every
# control-plane lock the suite exercises feeds the process-global
# lock-order graph, and the autouse fixture below fails the test during
# which an inversion was observed. setdefault BEFORE any tony_tpu
# import — the factories read the flag at lock-creation time.
os.environ.setdefault("TONY_SYNC_SANITIZER", "1")

# Jit sanitizer ON for the whole tier-1 suite (opt-out with =0): every
# instrument_jit dispatch the suite exercises is classified cold/hit/
# retrace in the process-global tracker, and every step region runs
# under a device-to-host transfer guard. The autouse fixture below
# fails the test during which an over-budget retrace or an implicit
# transfer was observed.
os.environ.setdefault("TONY_JIT_SANITIZER", "1")

# Forced (not setdefault): tests always run on the virtual 8-device CPU
# mesh, whatever the ambient environment selects — the chip is reached
# only through chip_smoke.py. Set before jax is imported, so no backend
# other than the CPU's ever initialises.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# A compile cache placed from outside would win over every tmp_path cache
# dir the tests configure (parallel/plan.configure_compile_cache) and
# turn their cold-compile counts into hits.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest


@pytest.fixture(autouse=True)
def _sync_sanitizer_gate():
    """Fail the test during which the sanitizer observed a lock-order
    inversion in the PROCESS-GLOBAL tracker (tests seeding deliberate
    inversions use private ``SyncTracker`` instances, which this gate
    never reads). Long-hold violations are hygiene telemetry, not
    failures — CPU-contended CI must not flake on hold times."""
    from tony_tpu.analysis import sync_sanitizer as _sync

    if not _sync.enabled():
        yield
        return
    tracker = _sync.tracker()
    mark = tracker.mark()
    yield
    inversions = tracker.violations_since(
        mark, kind=_sync.LOCK_ORDER_INVERSION
    )
    if inversions:
        import json

        pytest.fail(
            "sync sanitizer observed lock-order inversion(s):\n"
            + json.dumps(inversions, indent=2),
            pytrace=False,
        )


@pytest.fixture(autouse=True)
def _jit_sanitizer_gate():
    """Fail the test during which the jit sanitizer observed an implicit
    device-to-host transfer inside a step region, or a retrace past the
    budget, in the PROCESS-GLOBAL tracker (tests seeding deliberate
    violations use private ``JitTracker`` instances, which this gate
    never reads). In-budget retraces are telemetry, not failures — a
    test legitimately calls the same wrapper with a handful of shapes."""
    from tony_tpu.analysis import jit_sanitizer as _jit

    if not _jit.enabled():
        yield
        return
    tracker = _jit.tracker()
    mark = tracker.mark()
    yield
    since = tracker.violations_since(mark)
    bad = [
        v for v in since
        if v.get("kind") == _jit.GUARDED_TRANSFER or v.get("over_budget")
    ]
    if bad:
        import json

        pytest.fail(
            "jit sanitizer observed dispatch violation(s):\n"
            + json.dumps(bad, indent=2),
            pytrace=False,
        )
