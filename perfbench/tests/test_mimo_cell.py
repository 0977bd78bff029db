"""The MiMo-V2-Flash cell's files (model module, reference, readers) through
the whole command at a toy size on the CPU, its look for a chip skipped:
a sound run is ``correct``, the fp8 control in the program's place is not,
and the engine's expert counters reach their reader. Counts, never times."""

from __future__ import annotations

from pathlib import Path

import pytest

import tinyrepo

MIMO = Path(__file__).resolve().parent / "data" / "mimo"


@pytest.fixture(scope="module")
def repo(tmp_path_factory):
    return tinyrepo.make(tmp_path_factory.mktemp("mimo") / "repo",
                         tinyrepo.TINY, MIMO)


def test_a_sound_run_is_correct_and_counts_its_experts(repo):
    import run as harness

    done = harness.run_cell(repo, "tinymimo.serve", 2**31 + 5, 2.0, True,
                            require_tpu=False)
    result = done["result"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["widest_gap"]["ok"] is True
    assert result["compared"]["tokens_unaccounted"] == {
        "value": 0, "limit": 0, "ok": True}
    # the readers of the program's counters read; those of a device trace
    # find no TPU plane on the CPU and leave their metric out
    assert result["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
    assert "kv_live_pct" in result["metrics"]
    assert "expert_ffn_hbm_roofline" not in result["metrics"]


def test_the_fp8_control_in_the_programs_place_is_not_correct(repo):
    import run as harness

    result = harness.run_cell(repo, "tinymimo.serve", 2**31 + 3, 2.0, False,
                              require_tpu=False, control="fp8")["result"]
    assert result["correct"] is False
    assert result["compared"]["widest_gap"]["ok"] is False
    assert result["compared"]["requests_failed"]["ok"] is True
