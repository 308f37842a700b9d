"""Percentiles and rates on a request log made by hand."""

import pytest

from benchmark import stats


def row(t_done, latency, tokens=10, ok=True):
    return {"t_submit": t_done - latency / 1000.0, "t_done": t_done,
            "latency_ms": latency, "ok": ok, "completion_tokens": tokens}


def test_percentile_interpolates_between_ranks():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 50) == 30
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile(xs, 0) == 10 and stats.percentile(xs, 100) == 50
    assert stats.percentile([7], 95) == 7
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_highest_supported_percentile_keeps_ten_samples_beyond():
    assert stats.highest_supported_percentile(200) == 95.0
    assert stats.highest_supported_percentile(100) == 90.0
    assert stats.highest_supported_percentile(10) == 0.0


def test_end_to_end_counts_the_window_only_and_failures_have_no_sample():
    log = [row(0.5, 999),                       # finished before it opened
           row(1.0, 100), row(2.0, 300), row(3.0, 200, tokens=30),
           row(3.5, 0, tokens=0, ok=False),     # failed: counted, no sample
           row(5.5, 999)]                       # finished after it closed
    e = stats.end_to_end(log, 1.0, 5.0)
    assert (e["attempted"], e["failed"], e["samples"]) == (4, 1, 3)
    assert e["turn_latency_p50_ms"] == 200
    assert e["turn_latency_p95_ms"] == pytest.approx(290.0)
    # whole turns by where they finished, over the WHOLE window (printed)
    assert e["finished_turns_tokens_per_s"] == pytest.approx(50 / 4.0)
    # the rate: a turn counts by the share of its time inside the window;
    # the one done at 1.0 s lies before it, the one done at 5.5 s ran
    # 4.501 to 5.5 s, so 0.499 of its 0.999 s are the window's
    assert e["output_tokens_per_s"] == pytest.approx(
        (10 + 30 + 10 * 0.499 / 0.999) / 4.0)


@pytest.mark.parametrize("submit,done,want", [
    (1.0, 3.0, 64.0),     # inside
    (-1.0, 1.0, 32.0),    # half before the window opened
    (9.0, 13.0, 16.0),    # a quarter before it closed
    (-5.0, 15.0, 32.0),   # longer than the window
    (10.0, 12.0, 0.0),    # after it
    (-3.0, 0.0, 0.0),     # before it
])
def test_a_turn_counts_by_the_share_of_its_time_inside(submit, done, want):
    log = [{"t_submit": submit, "t_done": done, "ok": True,
            "completion_tokens": 64}]
    assert stats.tokens_inside(log, 0.0, 10.0) == pytest.approx(want)


def test_failed_and_unsent_turns_hold_no_tokens():
    log = [{"t_submit": 1.0, "t_done": 2.0, "ok": False,
            "completion_tokens": 5},
           {"t_done": 2.0, "ok": False, "completion_tokens": 0}]
    assert stats.tokens_inside(log, 0.0, 10.0) == 0.0
