"""Measured autotuner (parallel/autotune.py): record persistence
degrade-to-miss semantics (corrupt / torn / concurrent / version-bump),
the shared search loop (default-first convention, trial budget, warm
reuse with zero trials), knob consumption (`set_tuned_blocks`,
`make_train_step` lookup, stepstats live feedback), the `tony.tune.*`
config-check rules (TONY-C002 enum, min-one budget, TONY-C011 scratch),
the one value ``ServingEngine(kv_quant=)`` still takes, and the
`tony tune` CLI table."""

import dataclasses
import json
import os
from pathlib import Path

import jax
import numpy as np
import pytest

from tony_tpu.models import TransformerConfig
from tony_tpu.parallel import autotune
from tony_tpu.parallel import plan as plan_lib
from tony_tpu.parallel.mesh import MeshSpec, build_mesh

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=2, head_dim=16,
    d_ff=64, max_seq=96, dtype="float32", remat=False,
)


def _record_for(key: str, *, best=None, **extra) -> dict:
    rec = {
        "version": autotune._RECORD_VERSION,
        "key": key,
        "label": "t",
        "best": best if best is not None else {"block_q": 256},
        "best_ms": 1.0,
        "default_ms": 2.0,
        "trials": [{"knobs": {}, "ms": 2.0},
                   {"knobs": {"block_q": 256}, "ms": 1.0}],
    }
    rec.update(extra)
    return rec


# ---------------------------------------------------------------------------
# Record persistence: every failure mode degrades to a miss
# ---------------------------------------------------------------------------


class TestRecordPersistence:
    def test_round_trip(self, tmp_path):
        key = autotune.tune_key("t", config=CFG)
        autotune.save_record(_record_for(key), cache_dir=str(tmp_path))
        rec = autotune.load_record(key, cache_dir=str(tmp_path))
        assert rec is not None
        assert rec["best"] == {"block_q": 256}

    def test_absent_is_miss(self, tmp_path):
        key = autotune.tune_key("t", config=CFG)
        assert autotune.load_record(key, cache_dir=str(tmp_path)) is None

    def test_corrupt_json_is_miss(self, tmp_path):
        key = autotune.tune_key("t", config=CFG)
        autotune.save_record(_record_for(key), cache_dir=str(tmp_path))
        path = Path(autotune._record_path(key, str(tmp_path)))
        path.write_text("{ not json !!")
        assert autotune.load_record(key, cache_dir=str(tmp_path)) is None

    def test_torn_write_is_miss(self, tmp_path):
        key = autotune.tune_key("t", config=CFG)
        autotune.save_record(_record_for(key), cache_dir=str(tmp_path))
        path = Path(autotune._record_path(key, str(tmp_path)))
        path.write_text(path.read_text()[: len(path.read_text()) // 2])
        assert autotune.load_record(key, cache_dir=str(tmp_path)) is None

    def test_key_mismatch_is_miss(self, tmp_path):
        # A record dir moved wholesale across identities: the embedded
        # key disagrees with the filename's — never served.
        key = autotune.tune_key("t", config=CFG)
        autotune.save_record(
            _record_for("0" * len(key)), cache_dir=str(tmp_path)
        )
        os.replace(
            autotune._record_path("0" * len(key), str(tmp_path)),
            autotune._record_path(key, str(tmp_path)),
        )
        assert autotune.load_record(key, cache_dir=str(tmp_path)) is None

    def test_version_bump_is_miss(self, tmp_path):
        key = autotune.tune_key("t", config=CFG)
        autotune.save_record(
            _record_for(key, version=autotune._RECORD_VERSION + 1),
            cache_dir=str(tmp_path),
        )
        assert autotune.load_record(key, cache_dir=str(tmp_path)) is None

    def test_non_dict_best_is_miss(self, tmp_path):
        key = autotune.tune_key("t", config=CFG)
        autotune.save_record(
            _record_for(key, best="fast"), cache_dir=str(tmp_path)
        )
        assert autotune.load_record(key, cache_dir=str(tmp_path)) is None

    def test_jax_version_bump_changes_key(self):
        # The backend fingerprint rides the key, so a jax upgrade (or a
        # different device kind) is a MISS by construction — exactly how
        # plan-measurements.json invalidates.
        base = autotune.tune_key("t", config=CFG)
        bumped = autotune.tune_key(
            "t", config=CFG,
            backend=dict(plan_lib.backend_fingerprint(), jax="99.99.99"),
        )
        assert base != bumped

    def test_concurrent_writers_last_complete_record_wins(self, tmp_path):
        # Two searchers race: each lands a COMPLETE file via tmp+rename;
        # whatever survives is a valid record, and a dead writer's
        # leftover tmp never shadows it.
        key = autotune.tune_key("t", config=CFG)
        autotune.save_record(
            _record_for(key, best={"block_q": 256}), cache_dir=str(tmp_path)
        )
        autotune.save_record(
            _record_for(key, best={"block_q": 512}), cache_dir=str(tmp_path)
        )
        path = autotune._record_path(key, str(tmp_path))
        with open(f"{path}.tmp.99999", "w") as f:
            f.write('{"half": ')  # a crashed writer's torn tmp
        rec = autotune.load_record(key, cache_dir=str(tmp_path))
        assert rec is not None and rec["best"] == {"block_q": 512}
        assert all(
            r["best"] == {"block_q": 512}
            for r in autotune.list_records(str(tmp_path))
        )

    def test_unwritable_dir_degrades_silently(self, tmp_path, monkeypatch):
        blocked = tmp_path / "blocked"
        blocked.write_text("a file, not a dir")
        key = autotune.tune_key("t", config=CFG)
        autotune.save_record(_record_for(key), cache_dir=str(blocked))
        assert autotune.load_record(key, cache_dir=str(blocked)) is None


# ---------------------------------------------------------------------------
# The search loop
# ---------------------------------------------------------------------------


class TestSearch:
    def _measure(self, walls):
        calls = []

        def measure(knobs):
            calls.append(knobs)
            return walls[len(calls) - 1]

        return measure, calls

    def test_default_first_and_best_wins(self, tmp_path):
        cands = [autotune.Knobs(), autotune.Knobs(block_q=256),
                 autotune.Knobs(block_q=512)]
        measure, calls = self._measure([3.0, 1.0, 2.0])
        rec = autotune.search(
            "t", cands, measure, key="k1", cache_dir=str(tmp_path)
        )
        assert calls[0] == autotune.Knobs()
        assert rec["default_ms"] == 3.0
        assert rec["best_ms"] == 1.0
        assert rec["best"]["block_q"] == 256
        assert rec["trials_this_run"] == 3

    def test_trial_budget_caps_measurement(self, tmp_path):
        cands = [autotune.Knobs(block_q=b) for b in (128, 256, 512, 1024)]
        measure, calls = self._measure([4.0, 3.0, 2.0, 1.0])
        rec = autotune.search(
            "t", cands, measure, key="k2", trial_budget=2,
            cache_dir=str(tmp_path),
        )
        assert len(calls) == 2
        assert rec["best"]["block_q"] == 256

    def test_warm_reuse_zero_trials(self, tmp_path):
        cands = [autotune.Knobs(), autotune.Knobs(block_q=256)]
        measure, calls = self._measure([2.0, 1.0])
        autotune.search("t", cands, measure, key="k3",
                        cache_dir=str(tmp_path))
        rec = autotune.search(
            "t", cands, measure, key="k3", cache_dir=str(tmp_path)
        )
        assert rec["trials_this_run"] == 0
        assert len(calls) == 2  # nothing re-measured
        assert rec["best"]["block_q"] == 256

    def test_failed_and_nonfinite_trials_are_data(self, tmp_path):
        def measure(knobs):
            if knobs.block_q == 256:
                raise RuntimeError("pallas says no")
            if knobs.block_q == 512:
                return float("nan")
            return 5.0

        cands = [autotune.Knobs(), autotune.Knobs(block_q=256),
                 autotune.Knobs(block_q=512)]
        rec = autotune.search(
            "t", cands, measure, key="k4", cache_dir=str(tmp_path)
        )
        assert rec["best"] == dataclasses.asdict(autotune.Knobs()) | {
            "xla_flags": []
        }
        errors = [t for t in rec["trials"] if "error" in t]
        assert len(errors) == 2

    def test_all_failed_search_not_persisted(self, tmp_path):
        def measure(knobs):
            raise RuntimeError("no backend")

        rec = autotune.search(
            "t", [autotune.Knobs()], measure, key="k5",
            cache_dir=str(tmp_path),
        )
        assert rec["best_ms"] is None
        assert autotune.load_record("k5", cache_dir=str(tmp_path)) is None

    def test_note_step_time_improves_live_best(self, tmp_path):
        key = autotune.tune_key("lm_train_step", config=CFG)
        autotune.save_record(_record_for(key), cache_dir=str(tmp_path))
        autotune.note_step_time(
            "lm_train_step", config=CFG, step_ms=0.5,
            cache_dir=str(tmp_path),
        )
        rec = autotune.load_record(key, cache_dir=str(tmp_path))
        assert rec["live_best_ms"] == 0.5
        # A worse production step never regresses the record.
        autotune.note_step_time(
            "lm_train_step", config=CFG, step_ms=9.0,
            cache_dir=str(tmp_path),
        )
        rec = autotune.load_record(key, cache_dir=str(tmp_path))
        assert rec["live_best_ms"] == 0.5

    def test_flash_block_candidates_clamped_and_deduped(self):
        cands = autotune.flash_block_candidates(512)
        assert cands[0] == autotune.Knobs()
        sizes = {(k.block_q, k.block_k) for k in cands[1:]}
        assert all(q <= 512 and k <= 512 for q, k in sizes)
        assert len(sizes) == len(cands) - 1


# ---------------------------------------------------------------------------
# Consumption: tuned blocks, make_train_step, DecodeSession
# ---------------------------------------------------------------------------


class TestConsumption:
    def test_set_tuned_blocks_fills_defaults_only(self):
        from tony_tpu.ops import attention as attention_lib

        try:
            attention_lib.set_tuned_blocks(256, 128)
            bq, bk = attention_lib._default_blocks(2048, 2048, None, None)
            assert (bq, bk) == (256, 128)
            # Explicit arguments always win over the tuned pin.
            bq, bk = attention_lib._default_blocks(2048, 2048, 1024, None)
            assert (bq, bk) == (1024, 128)
            # The pin clamps to the sequence like the bucketed default.
            bq, bk = attention_lib._default_blocks(64, 64, None, None)
            assert (bq, bk) == (64, 64)
        finally:
            attention_lib.clear_tuned_blocks()
        assert attention_lib.tuned_blocks() == (None, None)

    def test_make_train_step_consumes_record(self, tmp_path, monkeypatch):
        from tony_tpu import constants
        from tony_tpu.models import make_train_step
        from tony_tpu.ops import attention as attention_lib

        monkeypatch.setenv(constants.TONY_TUNE_RECORD_DIR, str(tmp_path))
        mesh = build_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
        key = autotune.tune_key("lm_train_step", config=CFG, mesh=mesh)
        autotune.save_record(
            _record_for(key, best={"block_q": 256, "block_k": 128}),
            cache_dir=str(tmp_path),
        )
        try:
            make_train_step(CFG, mesh)
            assert attention_lib.tuned_blocks() == (256, 128)
        finally:
            attention_lib.clear_tuned_blocks()

    def test_make_train_step_disabled_ignores_record(
        self, tmp_path, monkeypatch
    ):
        from tony_tpu import constants
        from tony_tpu.models import make_train_step
        from tony_tpu.ops import attention as attention_lib

        monkeypatch.setenv(constants.TONY_TUNE_RECORD_DIR, str(tmp_path))
        monkeypatch.setenv(constants.TONY_TUNE_ENABLED, "false")
        mesh = build_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
        key = autotune.tune_key("lm_train_step", config=CFG, mesh=mesh)
        autotune.save_record(
            _record_for(key, best={"block_q": 256}), cache_dir=str(tmp_path)
        )
        try:
            make_train_step(CFG, mesh)
            assert attention_lib.tuned_blocks() == (None, None)
        finally:
            attention_lib.clear_tuned_blocks()

    def test_lookup_counts_hits_and_misses(self, tmp_path):
        from tony_tpu import observability

        reg = observability.default_registry()
        hits0 = reg.counter(autotune.TUNE_RECORD_HITS_COUNTER).value
        misses0 = reg.counter(autotune.TUNE_RECORD_MISSES_COUNTER).value
        assert autotune.lookup(
            "absent", config=CFG, cache_dir=str(tmp_path)
        ) is None
        key = autotune.tune_key("present", config=CFG)
        autotune.save_record(_record_for(key), cache_dir=str(tmp_path))
        knobs = autotune.lookup(
            "present", config=CFG, cache_dir=str(tmp_path)
        )
        assert knobs is not None and knobs.block_q == 256
        assert reg.counter(autotune.TUNE_RECORD_HITS_COUNTER).value \
            == hits0 + 1
        assert reg.counter(autotune.TUNE_RECORD_MISSES_COUNTER).value \
            == misses0 + 1

    def test_apply_xla_flags_appends_once(self, monkeypatch):
        monkeypatch.setenv("XLA_FLAGS", "--xla_existing=1")
        knobs = autotune.Knobs(xla_flags=("--xla_new_thing=true",))
        assert autotune.apply_xla_flags(knobs)
        assert os.environ["XLA_FLAGS"] == \
            "--xla_existing=1 --xla_new_thing=true"
        assert not autotune.apply_xla_flags(knobs)  # already present


# ---------------------------------------------------------------------------
# tony.tune.* config checks (TONY-C002 enum, min-one budget, TONY-C011)
# ---------------------------------------------------------------------------


class TestTuneConfigCheck:
    def _findings(self, rule_id, **overrides):
        from tony_tpu.analysis.config_check import check_config
        from tony_tpu.conf.configuration import TonyConfiguration

        conf = TonyConfiguration()
        for k, v in overrides.items():
            conf.set(k, v)
        return [f for f in check_config(conf) if f.rule_id == rule_id]

    def test_zero_trial_budget_rejected(self):
        from tony_tpu.conf import keys

        found = self._findings(
            "TONY-C002", **{keys.K_TUNE_TRIAL_BUDGET: "0"}
        )
        assert len(found) == 1

    def test_scratch_record_dir_flagged(self):
        from tony_tpu.conf import keys

        found = self._findings(
            "TONY-C011", **{keys.K_TUNE_RECORD_DIR: "/tmp/tune"}
        )
        assert len(found) == 1
        assert "scratch" in found[0].message

    def test_durable_dir_and_disabled_pass(self):
        from tony_tpu.conf import keys

        assert not self._findings(
            "TONY-C011", **{keys.K_TUNE_RECORD_DIR: "/srv/tony-tune"}
        )
        assert not self._findings("TONY-C011", **{
            keys.K_TUNE_RECORD_DIR: "/tmp/tune",
            keys.K_TUNE_ENABLED: "false",
        })
        assert not self._findings("TONY-C011")  # empty = beside the cache


# ---------------------------------------------------------------------------
# The KV cache has one storage form (the int8 fork went in PR 32)
# ---------------------------------------------------------------------------


class TestKVStorageArg:
    @pytest.mark.parametrize("mode", ["fp4", "int8"])
    def test_bad_mode_rejected(self, mode):
        from tony_tpu.models import init_params
        from tony_tpu.serving import ServingEngine

        params = init_params(jax.random.key(0), CFG)
        with pytest.raises(ValueError, match="kv_quant"):
            ServingEngine(params, CFG, slots=2, kv_quant=mode)

    def test_none_is_the_compute_dtype(self):
        """The keyword that ``perfbench/jobs/serve.py`` still passes takes
        "none" or nothing: the cache is the compute dtype's, and the
        stats name no storage form."""
        from tony_tpu.models import init_params
        from tony_tpu.serving import ServingEngine

        params = init_params(jax.random.key(0), CFG)
        for mode in (None, "none"):
            eng = ServingEngine(params, CFG, slots=2, max_len=32,
                                kv_quant=mode)
            assert eng._k.dtype == eng._v.dtype == CFG.compute_dtype
            assert "kv_quant" not in eng.stats()
            eng.close()


# ---------------------------------------------------------------------------
# `tony tune` CLI + history panel
# ---------------------------------------------------------------------------


class TestSurfaces:
    def test_tune_cli_table(self, tmp_path, capsys):
        from tony_tpu.client.cli import tune_cmd

        key = autotune.tune_key("lm_train_step", config=CFG)
        autotune.save_record(
            _record_for(key, label="lm_train_step", live_best_ms=0.9),
            cache_dir=str(tmp_path),
        )
        assert tune_cmd(["--record-dir", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "lm_train_step" in out
        assert "block_q" in out

    def test_tune_cli_json(self, tmp_path, capsys):
        from tony_tpu.client.cli import tune_cmd

        key = autotune.tune_key("t", config=CFG)
        autotune.save_record(_record_for(key), cache_dir=str(tmp_path))
        assert tune_cmd(["--record-dir", str(tmp_path), "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert len(doc["records"]) == 1
        assert doc["records"][0]["best"] == {"block_q": 256}

    def test_history_autotune_section(self):
        from tony_tpu.history.server import HistoryHandler

        final = {"metrics": {"tasks": {"worker:0": {
            autotune.TUNE_RECORD_HITS_COUNTER: 2,
            autotune.TUNE_RECORD_MISSES_COUNTER: 0,
            autotune.TUNE_SEARCH_TRIALS_COUNTER: 5,
        }, "worker:1": {}}}}
        parts = HistoryHandler._autotune_section(
            None, final, lambda s: str(s)
        )
        html = "".join(parts)
        assert "Autotuning" in html
        assert "worker:0" in html
        assert "worker:1" not in html  # no tune activity, no row
        assert HistoryHandler._autotune_section(
            None, {"metrics": {"tasks": {}}}, str
        ) == []


# ---------------------------------------------------------------------------
# End-to-end search on a real (tiny) train step — heavy, slow-marked
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestEndToEnd:
    def test_tune_train_step_cold_then_warm(self, tmp_path):
        mesh = build_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
        cold = autotune.tune_train_step(
            CFG, mesh, global_batch=2, seq=32, trial_budget=2,
            cache_dir=str(tmp_path),
        )
        assert cold["trials_this_run"] >= 1
        assert cold["best_ms"] is not None
        assert cold["default_ms"] >= cold["best_ms"]
        warm = autotune.tune_train_step(
            CFG, mesh, global_batch=2, seq=32, trial_budget=2,
            cache_dir=str(tmp_path),
        )
        assert warm["trials_this_run"] == 0
        assert warm["best"] == cold["best"]
