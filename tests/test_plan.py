"""Plan layer (parallel/plan.py), persistent compile cache, and the
bench regression gate: cache keying (config/mesh/jax-version
sensitivity, corrupt-dir degradation), hit/miss metrics across
processes, planner candidate legality + measured refinement, the
TONY-C010 scratch-cache lint, and `bench.py --check` compare logic on
fixture JSON."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from tony_tpu.models import TransformerConfig
from tony_tpu.parallel import plan as plan_lib
from tony_tpu.parallel.mesh import MeshSpec, build_mesh

REPO = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).resolve().parent / "fixtures" / "bench"

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=4, n_heads=4, head_dim=8,
    d_ff=64, max_seq=64, dtype="float32", n_kv_heads=2,
)


# ---------------------------------------------------------------------------
# Cache keying
# ---------------------------------------------------------------------------


class TestPlanCacheKey:
    def test_identical_inputs_identical_key(self):
        mesh = build_mesh(MeshSpec(dp=4, tp=2), devices=jax.devices())
        a = plan_lib.plan_cache_key("step", config=CFG, mesh=mesh)
        b = plan_lib.plan_cache_key("step", config=CFG, mesh=mesh)
        assert a == b

    def test_model_config_invalidates(self):
        other = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
            d_ff=64, max_seq=64, dtype="float32", n_kv_heads=2,
        )
        assert plan_lib.plan_cache_key("step", config=CFG) != \
            plan_lib.plan_cache_key("step", config=other)

    def test_mesh_topology_invalidates(self):
        devs = jax.devices()
        m1 = build_mesh(MeshSpec(dp=4, tp=2), devices=devs)
        m2 = build_mesh(MeshSpec(dp=2, sp=2, tp=2), devices=devs)
        assert plan_lib.plan_cache_key("step", config=CFG, mesh=m1) != \
            plan_lib.plan_cache_key("step", config=CFG, mesh=m2)

    def test_jax_version_invalidates(self):
        base = plan_lib.backend_fingerprint()
        bumped = dict(base, jax="99.99.99")
        assert plan_lib.plan_cache_key("step", config=CFG, backend=base) != \
            plan_lib.plan_cache_key("step", config=CFG, backend=bumped)

    def test_label_and_plan_knobs_invalidate(self):
        p1 = plan_lib.Plan(MeshSpec(pp=2, tp=2, dp=2), microbatches=2)
        p2 = plan_lib.Plan(MeshSpec(pp=2, tp=2, dp=2), microbatches=4)
        assert plan_lib.plan_cache_key("a", plan=p1) != \
            plan_lib.plan_cache_key("b", plan=p1)
        assert plan_lib.plan_cache_key("a", plan=p1) != \
            plan_lib.plan_cache_key("a", plan=p2)


class TestCompileCache:
    def test_commit_then_seen(self, tmp_path):
        cache = plan_lib.CompileCache(str(tmp_path))
        key = "k" * 64
        assert not cache.seen(key)
        cache.commit(key, {"label": "step"})
        assert cache.seen(key)
        # A fresh instance over the same dir (≈ a new process) sees it.
        assert plan_lib.CompileCache(str(tmp_path)).seen(key)

    def test_corrupt_marker_degrades_to_miss(self, tmp_path):
        cache = plan_lib.CompileCache(str(tmp_path))
        key = "c" * 64
        cache.commit(key)
        marker = tmp_path / plan_lib._KEY_INDEX_DIR / f"{key}.json"
        marker.write_text("{torn json")
        assert not cache.seen(key)
        # mismatched content (wrong key recorded inside) is also a miss
        marker.write_text(json.dumps({"key": "someone-else"}))
        assert not cache.seen(key)

    def test_unwritable_index_never_crashes(self, tmp_path):
        # A FILE squatting the index path: commit and seen both degrade.
        (tmp_path / plan_lib._KEY_INDEX_DIR).write_text("not a dir")
        cache = plan_lib.CompileCache(str(tmp_path))
        cache.commit("x" * 64)  # must not raise
        assert not cache.seen("x" * 64)

    def test_disabled_cache(self):
        cache = plan_lib.CompileCache(None)
        assert not cache.enabled
        cache.commit("y" * 64)
        assert not cache.seen("y" * 64)

    def test_instrument_jit_counts_miss_then_hit(self, tmp_path):
        from tony_tpu import observability

        reg = observability.default_registry()
        cache = plan_lib.CompileCache(str(tmp_path))
        hits = reg.counter("tony_compile_cache_hits_total")
        misses = reg.counter("tony_compile_cache_misses_total")
        h0, m0 = hits.value, misses.value

        calls = []
        fn = plan_lib.instrument_jit(
            lambda x: calls.append(x) or x + 1, "base-key", cache=cache
        )
        assert fn(1) == 2 and fn(2) == 3
        assert (hits.value, misses.value) == (h0, m0 + 1)
        # "Second submit": a fresh wrapper over the same cache and the
        # same base key + argument signature classifies as a hit.
        fn2 = plan_lib.instrument_jit(
            lambda x: x + 1, "base-key", cache=cache
        )
        assert fn2(1) == 2  # same base key AND argument signature
        assert (hits.value, misses.value) == (h0 + 1, m0 + 1)
        # ... but a different argument SHAPE is a different executable.
        fn3 = plan_lib.instrument_jit(
            lambda x: x, "base-key", cache=cache
        )
        fn3(np.zeros((2, 3)))
        assert (hits.value, misses.value) == (h0 + 1, m0 + 2)


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------


class TestPlanner:
    def test_candidates_legal(self):
        plans = plan_lib.candidate_plans(CFG, 8, global_batch=16, seq=16)
        assert plans
        for p in plans:
            s = p.mesh_spec
            assert p.num_devices == 8
            assert CFG.n_heads % s.tp == 0 and CFG.n_kv_heads % s.tp == 0
            assert s.ep == 1  # no experts in CFG
            assert CFG.n_layers % s.pp == 0
            assert (p.microbatches is not None) == (s.pp > 1)
            if s.sp > 1:
                assert 16 % s.sp == 0

    def test_require_pins_axes(self):
        plans = plan_lib.candidate_plans(
            CFG, 8, require={"pp": 2, "tp": 2, "microbatches": 2}
        )
        assert plans
        for p in plans:
            assert p.mesh_spec.pp == 2 and p.mesh_spec.tp == 2
            assert p.microbatches == 2

    def test_ep_needs_experts(self):
        moe = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=4, n_heads=4, head_dim=8,
            d_ff=64, max_seq=64, dtype="float32", n_experts=4,
        )
        assert any(
            p.mesh_spec.ep > 1
            for p in plan_lib.candidate_plans(moe, 8, seq=16)
        )
        assert plan_lib.candidate_plans(CFG, 8, seq=16, require={"ep": 2}) \
            == []

    def test_plan_for_impossible_raises(self):
        with pytest.raises(ValueError):
            plan_lib.plan_for(CFG, 8, require={"tp": 3})

    def test_measured_refinement_overrides_estimate(self, tmp_path):
        d = str(tmp_path)
        cands = plan_lib.candidate_plans(CFG, 8, seq=16)
        analytic = plan_lib.plan_for(CFG, 8, seq=16, cache_dir=d)
        # Declare some OTHER candidate measured-fastest; the pick must
        # follow the measurement, not the estimate.
        other = next(p for p in cands if p.key() != analytic.key())
        plan_lib.record_step_time(analytic, CFG, 500.0, seq=16,
                                  cache_dir=d)
        plan_lib.record_step_time(other, CFG, 1.0, seq=16, cache_dir=d)
        assert plan_lib.plan_for(CFG, 8, seq=16, cache_dir=d).key() == \
            other.key()
        # best-of: a worse later observation does not overwrite
        plan_lib.record_step_time(other, CFG, 900.0, seq=16, cache_dir=d)
        table = plan_lib.load_measurements(cache_dir=d)
        bucket = plan_lib._model_bucket(CFG, 8, None, 16)
        assert table[bucket][other.key()] == 1.0
        # a different work bucket (other batch/seq) must not see these
        assert plan_lib._model_bucket(CFG, 8, 64, 16) != bucket

    def test_corrupt_measurements_degrade_to_analytic(self, tmp_path):
        d = str(tmp_path)
        (tmp_path / plan_lib._MEASUREMENTS_FILE).write_text("{nope")
        assert plan_lib.load_measurements(cache_dir=d) == {}
        assert plan_lib.plan_for(CFG, 8, seq=16, cache_dir=d)  # no crash

    def test_pipeline_cost_includes_bubble(self):
        gspmd = plan_lib.Plan(MeshSpec(dp=8))
        pp_few = plan_lib.Plan(MeshSpec(dp=1, pp=8), microbatches=8)
        pp_many = plan_lib.Plan(MeshSpec(dp=1, pp=8), microbatches=32)
        big = TransformerConfig(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
            head_dim=64, d_ff=4096, max_seq=2048,
        )
        c = lambda p: plan_lib.estimate_cost(p, big, global_batch=64,
                                             seq=2048)
        assert c(pp_many) < c(pp_few)   # more microbatches, less bubble
        assert c(gspmd) < c(pp_few)     # dp beats a bubbly pipeline here


# ---------------------------------------------------------------------------
# Plan → train step plumbing
# ---------------------------------------------------------------------------


class TestEstimatePhases:
    def test_decomposition_sums_to_estimate_cost(self):
        for spec in (MeshSpec(dp=8), MeshSpec(dp=2, tp=2, sp=2),
                     MeshSpec(dp=4, tp=2)):
            p = plan_lib.Plan(spec)
            est = plan_lib.estimate_phases(p, CFG, global_batch=16, seq=16)
            assert est["compute"] > 0 and est["collective"] >= 0
            assert plan_lib.estimate_cost(
                p, CFG, global_batch=16, seq=16
            ) == pytest.approx(est["compute"] + est["collective"])

    def test_comm_bytes_per_axis(self):
        p = plan_lib.Plan(MeshSpec(dp=2, tp=2, sp=2))
        est = plan_lib.estimate_phases(p, CFG, global_batch=16, seq=16)
        # every active axis > 1 moves bytes; inactive axes are absent
        assert set(est["comm_bytes"]) == {"dp", "tp", "sp"}
        assert all(v > 0 for v in est["comm_bytes"].values())
        single = plan_lib.estimate_phases(
            plan_lib.Plan(MeshSpec()), CFG, global_batch=16, seq=16
        )
        assert single["comm_bytes"] == {} and single["collective"] == 0.0

    def test_illegal_pipeline_reads_infinite_compute(self):
        p = plan_lib.Plan(MeshSpec(pp=2))  # no microbatches
        est = plan_lib.estimate_phases(p, CFG, global_batch=16, seq=16)
        assert est["compute"] == float("inf")
        assert plan_lib.estimate_cost(p, CFG) == float("inf")

    def test_plan_from_mesh_maps_axis_sizes(self):
        mesh = build_mesh(MeshSpec(dp=4, tp=2), devices=jax.devices())
        p = plan_lib.plan_from_mesh(mesh, num_slices=1)
        assert p.mesh_spec.dp == 4 and p.mesh_spec.tp == 2
        assert p.num_devices == 8

    def test_calibration_residuals_normalized(self, tmp_path):
        d = str(tmp_path)
        plans = plan_lib.candidate_plans(CFG, 8, global_batch=16, seq=16)
        # perfectly-calibrated measurements: measured == estimate × 2
        for p in plans[:3]:
            plan_lib.record_step_time(
                p, CFG,
                2.0 * plan_lib.estimate_cost(p, CFG, global_batch=16,
                                             seq=16),
                global_batch=16, seq=16, cache_dir=d,
            )
        res = plan_lib.calibration_residuals(
            CFG, 8, global_batch=16, seq=16, cache_dir=d
        )
        assert len(res) == 3
        # all ratios equal ⇒ every residual is exactly 1.0 after the
        # bucket-mean normalization (the shared ×2 scale divides out)
        for v in res.values():
            assert v == pytest.approx(1.0)
        # an empty bucket yields no residuals, never a crash
        assert plan_lib.calibration_residuals(
            CFG, 8, global_batch=99, seq=16, cache_dir=d
        ) == {}


class TestPlanTrainStep:
    def test_plan_supplies_mesh_and_trunk(self):
        import jax.numpy as jnp

        from tony_tpu.models import make_train_step

        plan = plan_lib.plan_for(CFG, len(jax.devices()),
                                 require={"pp": 1, "tp": 2}, seq=16)
        assert plan.trunk == "gspmd"
        init_fn, step_fn = make_train_step(CFG, plan=plan)
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, CFG.vocab_size, (8, 17)),
            jnp.int32,
        )
        with jax.sharding.set_mesh(plan.build_mesh()):
            state = init_fn(jax.random.key(0))
            state, metrics = step_fn(state, tokens)
            assert np.isfinite(float(metrics["loss"]))

    def test_mesh_or_plan_required(self):
        from tony_tpu.models import make_train_step

        with pytest.raises(ValueError):
            make_train_step(CFG)


# ---------------------------------------------------------------------------
# TONY-C010: compile cache on non-persistent scratch
# ---------------------------------------------------------------------------


class TestScratchCacheLint:
    def _findings(self, **overrides):
        from tony_tpu.analysis.config_check import check_config
        from tony_tpu.conf.configuration import TonyConfiguration

        conf = TonyConfiguration()
        for k, v in overrides.items():
            conf.set(k, v)
        return [f for f in check_config(conf) if f.rule_id == "TONY-C010"]

    def test_tmp_cache_dir_flagged(self):
        from tony_tpu.conf import keys

        found = self._findings(**{keys.K_COMPILE_CACHE_DIR: "/tmp/xla"})
        assert len(found) == 1
        assert "non-persistent scratch" in found[0].message

    def test_durable_dir_and_disabled_pass(self):
        from tony_tpu.conf import keys

        assert not self._findings(
            **{keys.K_COMPILE_CACHE_DIR: "/home/me/.cache/xla"}
        )
        assert not self._findings(**{
            keys.K_COMPILE_CACHE_DIR: "/tmp/xla",
            keys.K_COMPILE_CACHE_ENABLED: "false",
        })
        assert not self._findings()  # empty dir = durable default


# ---------------------------------------------------------------------------
# bench.py --check regression gate (fixture JSON, no benches run)
# ---------------------------------------------------------------------------


def _bench():
    sys.path.insert(0, str(REPO))
    try:
        import bench
    finally:
        sys.path.pop(0)
    return bench


class TestBenchGate:
    def test_collect_gates_metrics_not_parameters(self):
        bench = _bench()
        line = json.loads((FIXTURES / "line_ok.json").read_text())
        got = bench.collect_submetrics(line)
        assert got["mnist_train_steps_per_sec_per_chip"] == 2400.0
        assert got["transformer.mfu"] == 0.53
        assert got["flash_attention_2k.speedup"] == 2.1
        assert "transformer.batch" not in got       # parameter, ungated
        assert "transformer.seq" not in got
        # errored extras contribute nothing (→ "missing" downstream)
        assert not any(k.startswith("moe.") for k in got)

    def test_check_passes_on_baseline_itself(self):
        bench = _bench()
        base = bench.load_baselines(str(FIXTURES / "baseline.json"))
        metrics = base["TPU v5 lite"]
        assert bench.check_regressions(dict(metrics), metrics) == []

    def test_check_catches_drop_rise_and_missing(self):
        bench = _bench()
        base = {"a.tokens_per_sec_per_chip": 1000.0, "a.step_ms": 10.0,
                "b.mfu": 0.6}
        cur = {"a.tokens_per_sec_per_chip": 850.0, "a.step_ms": 11.5}
        problems = bench.check_regressions(cur, base)
        assert len(problems) == 3
        assert any("below baseline" in p for p in problems)
        assert any("above baseline" in p for p in problems)
        assert any("missing" in p for p in problems)
        # within tolerance: no findings
        ok = {"a.tokens_per_sec_per_chip": 950.0, "a.step_ms": 10.5,
              "b.mfu": 0.58}
        assert bench.check_regressions(ok, base) == []

    def test_pct_metrics_get_absolute_slack(self):
        bench = _bench()
        base = {"io.overhead_pct": 1.3}
        # 3x the baseline but only +2.6 points: noise, not a regression
        assert bench.check_regressions({"io.overhead_pct": 3.9}, base) == []
        assert bench.check_regressions({"io.overhead_pct": 9.0}, base)

    def test_zero_baseline_retrace_counter_gates_absolutely(self):
        """`retraces_total` is lower-is-better, and its zero baseline is
        absolute: ONE steady-state recompile fails --check (no threshold
        to scale against). Zero-baseline higher-direction metrics keep
        passing free — a drop from zero is unscalable noise."""
        bench = _bench()
        assert bench.metric_direction("transformer.retraces_total") == \
            "lower"
        base = {"transformer.retraces_total": 0.0}
        assert bench.check_regressions(
            {"transformer.retraces_total": 0.0}, base
        ) == []
        problems = bench.check_regressions(
            {"transformer.retraces_total": 2.0}, base
        )
        assert len(problems) == 1 and "zero baseline" in problems[0]
        assert bench.check_regressions({"x.mfu": 0.5}, {"x.mfu": 0.0}) == []

    def test_retrace_baselines_seeded_for_hot_paths(self):
        bench = _bench()
        table = bench.load_baselines().get("TPU v5 lite", {})
        for wl in ("transformer", "serving", "decode_gqa"):
            assert table.get(f"{wl}.retraces_total") == 0

    def test_main_check_exit_codes(self, tmp_path):
        bench = _bench()
        baseline = str(FIXTURES / "baseline.json")
        assert bench.main(["--check", "--baseline", baseline,
                           "--input", str(FIXTURES / "line_ok.json")]) == 0
        assert bench.main(["--check", "--baseline", baseline,
                           "--input",
                           str(FIXTURES / "line_regressed.json")]) == 1
        # Unknown platform: ungated, not a regression.
        other = tmp_path / "line_other.json"
        line = json.loads((FIXTURES / "line_ok.json").read_text())
        line["extras"]["device"] = "TPU v9"
        other.write_text(json.dumps(line))
        assert bench.main(["--check", "--baseline", baseline,
                           "--input", str(other)]) == 0

    @pytest.mark.parametrize("extras, want", [
        ({"moe": {"tokens_per_sec_per_chip": 1.0}}, 0),
        ({"moe": {"error": "RuntimeError: boom"}}, 1),
    ])
    def test_main_fails_when_a_phase_of_this_run_failed(
        self, monkeypatch, capsys, extras, want
    ):
        """`_safe` keeps one failed extra from losing the line, but the
        run that recorded it must not exit 0."""
        bench = _bench()
        monkeypatch.setattr(bench, "run_benches", lambda: {
            "platform": "cpu", "device_kind": "cpu", "device_count": 1,
            "metric": "m", "value": 1.0, "extras": extras,
        })
        monkeypatch.setattr(plan_lib, "configure_compile_cache",
                            lambda *a, **k: None)
        assert bench.main([]) == want
        assert json.loads(capsys.readouterr().out)["platform"] == "cpu"

    def test_update_baseline_roundtrip(self, tmp_path):
        bench = _bench()
        target = tmp_path / "BASELINE.json"
        target.write_text(json.dumps({"north_star": "keep-me"}))
        line_path = str(FIXTURES / "line_ok.json")
        assert bench.main(["--update-baseline", "--baseline", str(target),
                           "--input", line_path]) == 0
        doc = json.loads(target.read_text())
        assert doc["north_star"] == "keep-me"  # other keys untouched
        assert "TPU v5 lite" in doc[bench.BASELINE_KEY]
        assert bench.main(["--check", "--baseline", str(target),
                           "--input", line_path]) == 0

    def test_shipped_baseline_has_tpu_entries(self):
        bench = _bench()
        shipped = bench.load_baselines()
        assert "TPU v5 lite" in shipped
        assert shipped["TPU v5 lite"]["mnist_train_steps_per_sec_per_chip"] \
            > 0


# ---------------------------------------------------------------------------
# Persistent-cache e2e: a second identical run skips compilation
# ---------------------------------------------------------------------------

_PROBE = r"""
import json, os, sys, time
import jax
import jax.numpy as jnp
import numpy as np

from tony_tpu.parallel.plan import configure_compile_cache
cache_dir = configure_compile_cache()
assert cache_dir == os.environ["TONY_COMPILE_CACHE_DIR"], cache_dir

from tony_tpu.models import MnistConfig
from tony_tpu.models.train import make_classifier_step
from tony_tpu.parallel.mesh import MeshSpec, build_mesh

mesh = build_mesh(MeshSpec(), devices=jax.devices()[:1])
cfg = MnistConfig(arch="cnn", dtype="float32")
init_fn, step_fn = make_classifier_step(cfg, mesh)
rng = np.random.default_rng(0)
images = jnp.asarray(rng.normal(size=(16, 28, 28, 1)), jnp.float32)
labels = jnp.asarray(rng.integers(0, 10, (16,)), jnp.int32)
t0 = time.perf_counter()
with jax.sharding.set_mesh(mesh):
    state = init_fn(jax.random.key(0))
    state, m = step_fn(state, images, labels)
    assert np.isfinite(float(m["loss"]))
wall = time.perf_counter() - t0

from tony_tpu import observability
snap = observability.default_registry().snapshot()
print("PROBE" + json.dumps({
    "counters": snap["counters"],
    "compile_ms": snap["histograms"]["tony_compile_ms"]["sum"],
    "wall_s": wall,
}))
"""


def _clean_env() -> dict:
    return {k: v for k, v in os.environ.items()
            if not k.startswith(("TONY_", "XLA_"))
            and k != "JAX_COMPILATION_CACHE_DIR"}


def _run_probe(cache_dir: Path) -> dict:
    env = _clean_env()
    env.update({
        "JAX_PLATFORMS": "cpu",
        "TONY_COMPILE_CACHE_DIR": str(cache_dir),
        "PYTHONPATH": str(REPO),
    })
    out = subprocess.run(
        [sys.executable, "-c", _PROBE], env=env, capture_output=True,
        text=True, timeout=240,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("PROBE"))
    return json.loads(line[len("PROBE"):])


@pytest.mark.slow
def test_resubmitted_job_hits_compile_cache_through_cluster(tmp_path):
    """The full wiring, end to end: ``tony.compile.cache-dir`` in the job
    conf → client-style frozen conf → executor TONY_COMPILE_* env →
    ``runtime.initialize()`` configuring jax in the user process. A
    second submit of the IDENTICAL job records cache hits and zero
    misses for the step function."""
    from tony_tpu.conf import keys
    from tony_tpu.mini import MiniTonyCluster

    cluster = MiniTonyCluster(tmp_path)
    probe_out = tmp_path / "probe.jsonl"
    cache_dir = tmp_path / "xla-cache"

    def submit():
        conf = cluster.base_conf()
        conf.set(keys.K_FRAMEWORK, "jax")
        conf.set(keys.K_EXECUTES,
                 str(Path(__file__).resolve().parent / "fixtures" /
                     "compile_cache_probe.py"))
        conf.set(keys.K_PYTHON_BINARY, sys.executable)
        conf.set(keys.instances_key("worker"), 1)
        conf.set(keys.instances_key("ps"), 0)
        conf.set(keys.K_COMPILE_CACHE_DIR, str(cache_dir))
        conf.set(keys.K_SHELL_ENV, f"PROBE_OUT={probe_out}")
        status, coord = cluster.run_job(conf)
        assert status.name == "SUCCEEDED", coord.session.diagnostics

    submit()
    submit()
    lines = [json.loads(l) for l in probe_out.read_text().splitlines()]
    assert len(lines) == 2
    cold, warm = lines
    assert cold["tony_compile_cache_misses_total"] == 2  # init + step
    assert cold.get("tony_compile_cache_hits_total", 0) == 0
    assert warm["tony_compile_cache_hits_total"] == 2
    assert warm.get("tony_compile_cache_misses_total", 0) == 0


def test_second_identical_run_hits_compile_cache(tmp_path):
    """The retry/resume/re-submit acceptance path, minus the cluster: two
    fresh processes compile the identical program against one
    ``tony.compile.cache-dir``. The first is all misses; the second
    records cache hits and ZERO misses for the step function, and its
    measured compile+first-step wall drops (the XLA persistent cache
    serves the executable)."""
    cache = tmp_path / "xla-cache"
    cold = _run_probe(cache)
    warm = _run_probe(cache)

    assert cold["counters"]["tony_compile_cache_misses_total"] == 2
    assert cold["counters"].get("tony_compile_cache_hits_total", 0) == 0
    assert warm["counters"]["tony_compile_cache_hits_total"] == 2
    assert warm["counters"].get("tony_compile_cache_misses_total", 0) == 0
    # Wall-time reduction: generous margin (CPU boxes share the machine
    # with the suite), but a served cache must beat a cold XLA compile.
    assert warm["wall_s"] < cold["wall_s"]
    assert warm["compile_ms"] < cold["compile_ms"]


# ---------------------------------------------------------------------------
# Where the cache goes: placed from outside, else one fixed path
# ---------------------------------------------------------------------------

_PLACEMENT_PROBE = r"""
import json, os, sys
import jax

set_in_code = []
_update = jax.config.update
def recording_update(name, value):
    set_in_code.append(name)
    return _update(name, value)
jax.config.update = recording_update

from tony_tpu.parallel import plan as plan_lib
explicit = os.environ.get("PROBE_EXPLICIT_DIR") or None
resolved = plan_lib.configure_compile_cache(explicit)
print("PROBE" + json.dumps({
    "resolved": resolved,
    "active": plan_lib.active_cache_dir(),
    "index": plan_lib.CompileCache.active()._index,
    "default": plan_lib.default_cache_dir(),
    "set_in_code": set_in_code,
}))
"""


@pytest.mark.parametrize("case", ["variable_set", "unset", "conf_and_variable"])
def test_compile_cache_placement(tmp_path, case):
    """``JAX_COMPILATION_CACHE_DIR`` places the cache from outside: JAX's
    own reading of it stands, the code sets no directory, and it wins
    over the conf-exported variable and an explicit argument alike.
    Without it (and without conf) the cache sits at one fixed path inside
    the checkout, whatever $HOME or the working directory are."""
    outside = tmp_path / "placed-from-outside"
    conf_dir = tmp_path / "conf-dir"
    explicit = tmp_path / "explicit-arg"
    env = _clean_env()
    env.update({
        "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO),
        "HOME": str(tmp_path / "some-home"),
    })
    if case != "unset":
        env["JAX_COMPILATION_CACHE_DIR"] = str(outside)
    if case == "conf_and_variable":
        env["TONY_COMPILE_CACHE_DIR"] = str(conf_dir)
        env["PROBE_EXPLICIT_DIR"] = str(explicit)
    out = subprocess.run(
        [sys.executable, "-c", _PLACEMENT_PROBE], env=env, cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("PROBE"))
    got = json.loads(line[len("PROBE"):])

    fixed = str(REPO / ".tony_cache" / "xla-cache")
    assert got["default"] == fixed
    want = fixed if case == "unset" else str(outside)
    assert got["resolved"] == got["active"] == want
    assert got["index"].startswith(want + os.sep)
    if case == "unset":
        assert "jax_compilation_cache_dir" in got["set_in_code"]
    else:
        assert "jax_compilation_cache_dir" not in got["set_in_code"]
        # The thresholds are still lowered so every executable is kept.
        assert "jax_persistent_cache_min_compile_time_secs" in \
            got["set_in_code"]
        assert not conf_dir.exists() and not explicit.exists()
