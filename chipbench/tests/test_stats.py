"""Percentiles and the window's arithmetic, on hand-made records."""

import pytest

import stats
from client import Record


def rec(due, first=None, last=None, tokens=0, pieces=(), ok=True, sent=None):
    r = Record("r", -1, 0, due, due if sent is None else sent, 10, tokens)
    if ok:
        r.status, r.prompt_tokens, r.completion_tokens = 200, 10, tokens
    r.first_s, r.last_s, r.done_s = first, last, last
    r.pieces = list(pieces)
    return r


def test_percentile_interpolates_like_numpy():
    xs = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 95) == pytest.approx(4.8)
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_times_run_from_due_not_from_send():
    r = rec(due=1.0, sent=1.4, first=1.5, last=2.5, tokens=11)
    assert r.ttft_s == pytest.approx(0.5)          # not 0.1
    assert r.tpot_s == pytest.approx(0.1)          # (2.5 - 1.5) / 10


def test_window_takes_requests_due_in_it_and_tokens_that_arrived_in_it():
    ramp = rec(due=-1.0, first=-0.5, last=0.5, tokens=4,
               pieces=[(-0.5, 2), (0.5, 2)])
    inside = rec(due=2.0, first=2.1, last=3.1, tokens=5,
                 pieces=[(2.1, 1), (3.1, 4)])
    drain = rec(due=9.9, first=10.2, last=11.0, tokens=3,
                pieces=[(10.2, 1), (11.0, 2)])
    failed = rec(due=5.0, ok=False)
    records = [ramp, inside, drain, failed]
    assert stats.measured(records, 10.0) == [inside, drain, failed]
    # ramp's second piece, inside's both, none of drain's (after the end).
    assert stats.tokens_in_window(records, 10.0) == 2 + 5
    e2e = stats.end_to_end(records, 10.0, chips=1)
    assert e2e["out_tokens_per_s"] == pytest.approx(0.7)
    assert e2e["ttft_p50_ms"] == pytest.approx((100 + 300) / 2)
    assert stats.end_to_end(records, 10.0, chips=4)["out_tokens_per_s"] == \
        pytest.approx(0.175)                        # per chip


def test_generator_report_counts_lateness_and_samples():
    records = [rec(due=i * 0.1, sent=i * 0.1 + 0.002 * i, first=i * 0.1 + 0.05,
                   last=i * 0.1 + 0.1, tokens=2) for i in range(100)]
    g = stats.generator_report(records, 20.0)
    assert g["requests_in_window"] == 100 and g["samples_beyond_p95"] == 5
    assert g["late_max_ms"] == pytest.approx(198.0)
    assert g["late_p50_ms"] == pytest.approx(99.0)


def test_a_request_short_of_its_tokens_or_with_another_prompt_is_failed():
    r = rec(due=0.0, first=0.1, last=0.2, tokens=5)
    assert r.ok
    r.completion_tokens = 4
    assert not r.ok
    r.completion_tokens, r.prompt_tokens = 5, 11
    assert not r.ok
