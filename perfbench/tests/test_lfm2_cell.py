"""The LFM2 cell's files (model module, reference, the two readers) through
the whole command at a toy size on the CPU, its look for a chip skipped: a
sound run is ``correct`` and every metric that reads a counter of the
program is in its line; the fp8 control and a wrong-mechanism control (a
conv state dropped at every chunk boundary) in the program's place are not
``correct``. Counts, never times."""

from __future__ import annotations

from pathlib import Path

import pytest

import tinyrepo

LFM2 = Path(__file__).resolve().parent / "data" / "lfm2"
CELL = "tinylfm2.serve"


@pytest.fixture(scope="module")
def repo(tmp_path_factory):
    return tinyrepo.make(tmp_path_factory.mktemp("lfm2") / "repo",
                         tinyrepo.TINY, LFM2)


def test_a_sound_run_is_correct_and_its_counters_reach_their_readers(repo):
    import run as harness

    done = harness.run_cell(repo, CELL, 2**31 + 5, 2.0, True,
                            require_tpu=False)
    result = done["result"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["widest_gap"]["ok"] is True
    assert result["compared"]["tokens_unaccounted"] == {
        "value": 0, "limit": 0, "ok": True}
    metrics = result["metrics"]
    # 4 slots x 2 of 8 experts: at most one token an expert an iteration
    assert 0.0 < metrics["expert_tokens_per_expert"]["value"] <= 1.0
    assert metrics["expert_load_max_over_mean"]["value"] >= 1.0
    for name in ("kv_live_pct", "slot_occupancy_pct", "compile_s",
                 "engine_host_share_pct.serve-saturated"):
        assert name in metrics
    # the readers of a device trace find no TPU plane on the CPU and
    # leave their metric out
    for name in ("conv_operator_ms", "expert_ffn_hbm_roofline",
                 "decode_hbm_roofline"):
        assert name not in metrics


@pytest.mark.parametrize("control", ["fp8", "state_reset"])
def test_a_control_in_the_programs_place_is_not_correct(repo, control):
    import run as harness

    result = harness.run_cell(repo, CELL, 2**31 + 4, 2.0, False,
                              require_tpu=False, control=control)["result"]
    assert result["correct"] is False
    assert result["compared"]["widest_gap"]["ok"] is False
    assert result["compared"]["requests_failed"]["ok"] is True
