"""Timing from the due time, lateness, pooled-gap percentiles, throughput."""

import pytest

import metrics


def rec(due, sent, tokens_at, ok=True, prompt=10, ended=None):
    return {"due": due, "sent": sent, "tokens_at": tokens_at, "ok": ok,
            "prompt_tokens": prompt,
            "ended": ended if ended is not None else
            (tokens_at[-1] if tokens_at else sent)}


def test_percentile_interpolates_like_numpy():
    xs = [1.0, 2.0, 3.0, 4.0]
    assert metrics.percentile(xs, 50) == 2.5
    assert metrics.percentile(xs, 99) == pytest.approx(3.97)
    assert metrics.percentile([7.0], 99) == 7.0
    assert metrics.percentile([], 50) is None


def test_ttft_counts_from_the_due_instant_not_from_the_send():
    # due at 10.0, the generator was 0.25 s late, first token at 10.75
    r = rec(10.0, 10.25, [10.75, 10.85])
    assert metrics.ttfts_ms([r]) == [pytest.approx(750.0)]
    assert metrics.ttfts_ms([r], origin="sent") == [pytest.approx(500.0)]
    late = metrics.lateness_ms([r, rec(0.0, 0.001, [1.0])])
    assert late["worst"] == pytest.approx(250.0)
    assert late["median"] == pytest.approx(125.5)


def test_gaps_are_pooled_over_requests_not_averaged_per_request():
    a = rec(0, 0, [1.0, 1.1, 1.2, 1.3])          # three gaps of 100 ms
    b = rec(0, 0, [2.0, 2.5])                    # one gap of 500 ms
    c = rec(0, 0, [3.0])                         # one token: no gap
    gaps = metrics.pooled_gaps_ms([a, b, c])
    assert sorted(round(g) for g in gaps) == [100, 100, 100, 500]
    e = metrics.end_to_end([a, b, c])
    assert e["itl_p50_ms"] == pytest.approx(100.0)
    assert e["itl_p99_ms"] == pytest.approx(100 + 0.97 * 400)


def test_failed_requests_give_no_latency_and_no_tokens():
    good = rec(0.0, 0.0, [1.0, 2.0], prompt=100, ended=2.0)
    bad = rec(0.5, 0.5, [0.9], ok=False, prompt=1000, ended=4.0)
    e = metrics.end_to_end([good, bad])
    assert e["ttft_p50_ms"] == pytest.approx(1000.0)
    # 102 tokens of the completed request over first due -> last completion
    assert e["tokens_per_s"] == pytest.approx(102 / 2.0)
