"""Percentile, goodput, lateness and backlog arithmetic on hand-made numbers."""

import math

from benchmark import stats


def req(due, sent, done, records=1, status=200, ok=True):
    return {"due": due, "sent": sent, "done": done, "records": records, "status": status,
            "scores_ok": ok}


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 95) == 95
    assert stats.percentile(v, 50) == 50
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile([1, 2, 3, 4], 95) == 4
    assert math.isnan(stats.percentile([], 95))


def test_latency_counts_from_due_and_failures_as_the_window():
    reqs = [req(1.0, 1.2, 1.25), req(2.0, 2.0, 2.01, status=429, ok=False),
            req(3.0, 3.0, 3.1, ok=False)]
    lat = stats.request_latencies_ms(reqs, window_s=10.0)
    assert lat[0] == 250.0 and lat[1] == 10000.0 and lat[2] == 10000.0


def test_goodput_counts_records_inside_the_limit():
    reqs = [req(0.0, 0.0, 0.05, records=10), req(1.0, 1.0, 1.5, records=100),
            req(2.0, 2.0, 2.01, records=7, status=504, ok=False)]
    assert stats.goodput(reqs, window_s=10.0, limit_ms=100.0) == 1.0
    assert stats.goodput(reqs, window_s=10.0, limit_ms=1000.0) == 11.0


def test_lateness_and_backlog():
    reqs = [req(0.0, 0.001, 0.5), req(0.1, 0.4, 0.9), req(0.2, 0.2, 0.25)]
    late = stats.lateness_ms(reqs)
    assert [round(x, 6) for x in late] == [1.0, 300.0, 0.0]
    assert stats.backlog(reqs, 0.3) == 2
    assert stats.backlog(reqs, 1.0) == 0
