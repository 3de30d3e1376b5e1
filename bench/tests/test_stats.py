"""Tail arithmetic: every request's gaps are pooled, so a stall that every
request sees moves the tail."""

import math

import pytest

from bench import stats


def records(stall_at=(), stall_s=0.5, n=20, tokens=50, gap=0.01):
    out = []
    for i in range(n):
        t, ts = 0.0, []
        for k in range(tokens):
            t += gap + (stall_s if k in stall_at else 0.0)
            ts.append(t)
        out.append(stats.Record(i, 0.0, (1, 2), tokens, admit_s=0.0,
                                token_s=ts, tokens=[1] * tokens))
    return out


def test_percentile_is_linear_interpolation():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([1, 2], 95) == pytest.approx(1.95)
    assert math.isnan(stats.percentile([], 95))
    assert stats.percentile([1.0, math.inf], 95) == math.inf


def test_an_injected_stall_moves_the_pooled_itl_tail():
    calm = stats.itl_gaps(records(), 100.0)
    assert len(calm) == 20 * 49
    assert stats.percentile(calm, 95) == pytest.approx(0.01)
    # A stall on 4 of 49 gaps of every request: 8% of all gaps.
    stalled = stats.itl_gaps(records(stall_at=(5, 15, 25, 35)), 100.0)
    assert stats.percentile(stalled, 95) == pytest.approx(0.51)


def test_only_gaps_and_tokens_inside_the_window_count():
    recs = records(tokens=10)
    assert stats.window_tokens(recs, 0.05) == 20 * 5
    assert len(stats.itl_gaps(recs, 0.05)) == 20 * 4


def test_ttft_counts_from_due_time_and_missing_is_infinite():
    recs = [stats.Record(0, 1.0, (1,), 4, admit_s=1.5, token_s=[2.0]),
            stats.Record(1, 2.0, (1,), 4, admit_s=None),
            stats.Record(2, 9.0, (1,), 4, admit_s=9.1, token_s=[9.5])]
    assert stats.ttfts(recs, 5.0) == [1.0, math.inf]
    assert stats.queue_waits(recs, 5.0) == [0.5, math.inf]
