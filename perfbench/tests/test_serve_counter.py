"""``serve_tokens_per_s`` through the whole command at a toy size on the
CPU, its look for a chip skipped: the rate is the engine's counter read at
the window's two edges, and the counter is held to the tokens that clients
received: exactly where every request is followed to its end, within what
the cut requests may hold where the shutdown cuts them. Counts, never
times."""

from __future__ import annotations

import json

import pytest

import tinyrepo
from yardstick import stats

SECONDS = 3.0


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One run of the toy cell that drains and one of the toy cell that is
    cut, with the job's own records kept."""
    import run as harness

    tmp = tmp_path_factory.mktemp("counter")
    repo = tinyrepo.make(tmp / "repo", tinyrepo.TINY)
    out = {}
    for cell in ("tiny.serve", "tiny.serve-cut"):
        done = harness.run_cell(repo, cell, 2**31 + 17, SECONDS, False,
                                require_tpu=False, keep_work=tmp / cell)
        out[cell] = (done["result"], done["stages"],
                     json.loads((tmp / cell / "window.json").read_text()))
    return out


@pytest.mark.parametrize("cell", ["tiny.serve", "tiny.serve-cut"])
def test_the_rate_is_the_counters_difference_over_the_window(runs, cell):
    result, stages, job = runs[cell]
    rows = job["occupancy"]
    assert all(len(row) == 5 for row in rows)
    counts = [row[-1] for row in rows]
    assert counts == sorted(counts) and counts[-1] <= job["tokens_generated"]
    t0, t1 = stages.at("window_start"), stages.at("window_end")
    assert t1 - t0 == pytest.approx(SECONDS)
    made = stats.counter_at(rows, t1) - stats.counter_at(rows, t0)
    rate = result["metrics"]["serve_tokens_per_s"]["value"]
    assert rate * SECONDS == pytest.approx(made, rel=1e-9) and made > 0
    # the answers-arrived figure is information, not the metric
    assert "tokens_answered_per_s" in result["info"]
    assert "tokens_answered_per_s" not in result["metrics"]


def test_a_drained_cell_accounts_for_every_token(runs):
    result, _, job = runs["tiny.serve"]
    info = result["info"]
    assert result["correct"] is True and info["cut_at_shutdown"] == 0
    assert result["compared"]["tokens_unaccounted"] == {
        "value": 0, "limit": 0, "ok": True}
    assert info["tokens_cut_allowance"] == 0
    assert info["tokens_generated"] == info["tokens_answered"] \
        == job["tokens_generated"] > 0


def test_a_cut_cell_stays_inside_what_the_cut_requests_may_hold(runs):
    result, _, _ = runs["tiny.serve-cut"]
    info = result["info"]
    assert result["correct"] is True and info["cut_at_shutdown"] > 0
    assert result["compared"]["tokens_unaccounted"]["value"] == 0
    adrift = info["tokens_generated"] - info["tokens_answered"]
    assert 0 <= adrift <= info["tokens_cut_allowance"]
    assert info["tokens_cut_allowance"] > 0
