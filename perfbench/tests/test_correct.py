"""``correct`` has to be able to fail. Whole runs of the harness at a size
a test run can hold, its look for a chip skipped: with the control (the
plain reference computed in fp8) put in the program's place, and with the
timed path broken underneath, the run's own comparison comes out not
correct. The same controls go through the same comparison on the chip at
the cells' own sizes and limits (``onchip.py --control fp8``; PERF.md)."""

from __future__ import annotations

import pytest

import tinyrepo


@pytest.mark.parametrize("workload,broken,number", [
    ("tiny.train", {"fault": "half_batch"}, "loss1_rel"),
    ("tiny.serve", {"fault": "alter_token"}, "widest_gap"),
    ("tiny.serve", {"fault": "truncate_answer"}, "requests_failed"),
    ("tiny.serve", {"fault": "inflate_counter"}, "tokens_unaccounted"),
    ("tiny.train", {"control": "fp8"}, "grad_norm_gap"),
    ("tiny.serve", {"control": "fp8"}, "widest_gap"),
    # another architecture, added as files: the toy mixture of experts
    ("toymoe.train", {"fault": "half_batch"}, "loss1_rel"),
    ("toymoe.serve", {"fault": "alter_token"}, "widest_gap"),
    ("toymoe.train", {"control": "fp8"}, "grad_norm_gap"),
    ("toymoe.serve", {"control": "fp8"}, "widest_gap"),
])
def test_a_run_with_the_timed_path_broken_or_the_control_in_its_place_is_not_correct(
        tmp_path, workload, broken, number):
    """A whole run of the harness, its look for a chip skipped. A fault
    breaks the timed path underneath; a control puts the reference in fp8
    in the program's place. The run's own comparison has to say so."""
    import run as harness

    repo = tinyrepo.make(tmp_path / "repo")
    done = harness.run_cell(repo, workload, 2**31 + 3, 2.0, False,
                            require_tpu=False, **broken)
    result = done["result"]
    assert result["correct"] is False
    assert result["compared"][number]["ok"] is False
    assert list(result)[-1] == "compared"
    if "control" in broken:      # only the precision's number is missed
        missed = [k for k, v in result["compared"].items() if not v["ok"]]
        assert number in missed and "requests_failed" not in missed
        assert "steps_failed" not in missed


@pytest.mark.parametrize("workload,numbers", [
    ("toymoe.train", ("loss1_rel", "grad_norm_gap", "change_norm_gap")),
    ("toymoe.serve", ("widest_gap",)),
])
def test_a_sound_run_of_an_added_architecture_is_correct(tmp_path, workload,
                                                         numbers):
    """The toy mixture of experts through the whole command: the program's
    expert trunk against a plain reference with no capacity."""
    import run as harness

    repo = tinyrepo.make(tmp_path / "repo")
    result = harness.run_cell(repo, workload, 2**31 + 5, 2.0, False,
                              require_tpu=False)["result"]
    assert result["correct"] is True and result["failed"] == 0
    for name in numbers:
        assert result["compared"][name]["ok"] is True
    if workload == "toymoe.train":    # the router is a leaf like any other
        job_numbers = result["compared"]
        assert job_numbers["grad_norm_gap"]["value"] < 1e-3


def test_a_sound_run_at_toy_size_is_correct_and_needs_a_tpu(tmp_path):
    import run as harness

    repo = tinyrepo.make(tmp_path / "repo")
    done = harness.run_cell(repo, "tiny.serve", 12345, 2.0, False,
                            require_tpu=False)
    assert done["result"]["correct"] is True
    assert done["result"]["failed"] == 0
    names = [name for _, _, name in sorted(done["stages"].rows)]
    for stage in ("process_start", "application_staged", "executor_launched",
                  "script_main", "devices", "weights_on_device",
                  "server_answering", "warmup_done", "window_start",
                  "window_end", "job_gone"):
        assert stage in names
    with pytest.raises(harness.BenchFailure, match="not on a TPU"):
        harness.run_cell(repo, "tiny.serve", 1, 1.0, False)
