"""The MiniCPM-SALA cell's files (model module, reference, traffic, the three
readers) through the whole command at a toy size on the CPU, its look for a
chip skipped: a sound run is ``correct``; the fp8 control and a
wrong-mechanism control (the lightning layers without their decay) in the
program's place are not; the engine's sparse counters reach their reader.
Counts, never times. (The reference's third control, ``dense``, is for the
chip: at a toy size the window and the first block are most of a prompt.)"""

from __future__ import annotations

from pathlib import Path

import pytest

import tinyrepo

SALA = Path(__file__).resolve().parent / "data" / "sala"
CELL = "tinysala.serve"


@pytest.fixture(scope="module")
def repo(tmp_path_factory):
    return tinyrepo.make(tmp_path_factory.mktemp("sala") / "repo",
                         tinyrepo.TINY, SALA)


def test_a_sound_run_is_correct_and_counts_its_selection(repo):
    import run as harness

    done = harness.run_cell(repo, CELL, 2**31 + 5, 2.0, True,
                            require_tpu=False)
    result = done["result"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["widest_gap"]["ok"] is True
    assert result["compared"]["tokens_unaccounted"] == {
        "value": 0, "limit": 0, "ok": True}
    # the reader of the program's counter reads; those of a device trace
    # find no TPU plane on the CPU and leave their metric out
    assert 0.0 < result["metrics"]["sparse_keys_read_pct"]["value"] < 100.0
    assert "kv_live_pct" in result["metrics"]
    assert "lightning_roofline" not in result["metrics"]
    assert "sparse_attention_roofline" not in result["metrics"]


@pytest.mark.parametrize("control", ["fp8", "no_decay"])
def test_a_control_in_the_programs_place_is_not_correct(repo, control):
    import run as harness

    result = harness.run_cell(repo, CELL, 2**31 + 4, 2.0, False,
                              require_tpu=False, control=control)["result"]
    assert result["correct"] is False
    assert result["compared"]["widest_gap"]["ok"] is False
    assert result["compared"]["requests_failed"]["ok"] is True
