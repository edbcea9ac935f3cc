"""Percentile and rate arithmetic; a stall must move p99 and delivered_pps."""

import numpy as np
import pytest

from benchmarks import stats

NS = 1_000_000_000


def test_percentile_is_numpys():
    rng = np.random.default_rng(0)
    v = np.sort(rng.exponential(20.0, 1001))
    for q in (0, 50, 95, 99, 100):
        assert stats.percentile(v, q) == pytest.approx(float(np.percentile(v, q)))
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def _window(stall_at_s=None, stall_s=0.0):
    """1,000 packets a second for 10 s, each 30 ms on the way; a stall holds
    whatever would arrive inside it until it ends."""
    t0 = 5 * NS
    due = t0 + np.arange(-1000, 10_000) * (NS // 1000)       # a second of lead-in
    arrival = due + 30_000_000
    if stall_at_s is not None:
        a, b = t0 + int(stall_at_s * NS), t0 + int((stall_at_s + stall_s) * NS)
        arrival = np.where((arrival >= a) & (arrival < b), b, arrival)
    return stats.end_to_end(due, arrival, t0, 10 * NS)


def test_steady_window():
    m = _window()
    assert m["samples"] == 10_000
    assert m["fwd_latency_p50_ms"] == pytest.approx(30.0)
    assert m["fwd_latency_p99_ms"] == pytest.approx(30.0)
    assert m["delivered_pps"] == pytest.approx(1000.0)


def test_a_stall_in_the_window_moves_p99_but_not_the_rate():
    m = _window(stall_at_s=4.0, stall_s=0.5)
    assert m["fwd_latency_p50_ms"] == pytest.approx(30.0)
    assert m["fwd_latency_p99_ms"] > 400.0          # 5 % of the packets waited
    assert m["fwd_latency_p90_ms"] == pytest.approx(30.0)
    assert m["delivered_pps"] == pytest.approx(1000.0)
    assert _window(stall_at_s=4.0, stall_s=1.5)["fwd_latency_p90_ms"] > 400.0     # 15 % did


def test_a_stall_over_the_close_moves_p99_and_the_rate():
    m = _window(stall_at_s=9.7, stall_s=0.5)
    assert m["samples"] == 10_000                    # late is late, not lost
    assert m["fwd_latency_p99_ms"] > 150.0
    assert m["delivered_pps"] < 980.0


def test_stat_names():
    assert stats.stat([1.0, 2.0, 3.0, 4.0], "p50") == pytest.approx(2.5)
    assert stats.stat([1.0, 5.0], "max") == 5.0
    assert stats.stat([], "p50") is None
    with pytest.raises(ValueError):
        stats.stat([1.0], "mode")
