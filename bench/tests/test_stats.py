"""Percentile and rate arithmetic of the window."""

import pytest

from bench import stats


def test_percentile_matches_linear_interpolation():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 90) == pytest.approx(4.6)
    assert stats.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def _window(stall: float):
    """Two bursts of four requests, 10 tokens each at 10 ms a token; the
    second burst starts ``stall`` seconds late and its tokens stall."""
    recs = []
    for b, due in enumerate((0.0, 1.0 + stall)):
        for i in range(4):
            first = due + 0.1 * (i + 1)
            gap = 0.01 + (stall / 9 if b else 0.0)
            recs.append(stats.Record(due=due if b == 0 else 1.0, first=first,
                                     last=first + 9 * gap, n_out=10, ok=True))
    end = max(r.last for r in recs)
    return stats.end_to_end(recs, 0.0, end)


def test_stall_lowers_rate_and_raises_tails():
    calm, stalled = _window(0.0), _window(2.0)
    assert calm["tokens_per_s"] == pytest.approx(80 / calm["window_s"])
    assert stalled["tokens_per_s"] < calm["tokens_per_s"]
    assert stalled["ttft_p90_s"] > calm["ttft_p90_s"]
    assert stalled["tpot_p90_ms"] > calm["tpot_p90_ms"]
    assert calm["tpot_p90_ms"] == pytest.approx(10.0)
    assert calm["n_requests"] == 8 and calm["n_tpot"] == 8


def test_failed_request_waits_the_whole_window():
    recs = [stats.Record(0.0, 0.1, 0.2, 5, True) for _ in range(9)]
    recs.append(stats.Record(0.0, None, None, 0, False))
    out = stats.end_to_end(recs, 0.0, 4.0)
    assert stats.ttfts(recs, 4.0)[-1] == 4.0
    assert out["ttft_p90_s"] > 0.1
    assert out["n_tpot"] == 9
