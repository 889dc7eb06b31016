"""Percentiles and window arithmetic, the censored first-token wait
included."""
import math

import pytest

from chipbench import stats


def rec(due, submit, done, n=5, ttft_wall=0.5):
    return {"due": due, "submit": submit, "done": done, "n": n,
            "ttft_wall": ttft_wall}


def test_percentile_linear():
    assert stats.percentile(range(1, 11), 90) == pytest.approx(9.1)
    assert stats.percentile([3.0], 90) == 3.0
    assert math.isnan(stats.percentile([], 90))


def test_latency_over_every_request_due_in_window():
    """Requests due in the window count, delivered inside it or after its
    end; one still undelivered when serving stops counts at (stop - due);
    one due before the window does not count."""
    recs = [rec(0.0, 0.2, 3.0), rec(1.0, 1.0, 12.0), rec(2.0, 2.0, None),
            rec(-1.0, -1.0, 4.0)]
    assert stats.latencies(recs, 0.0, 10.0, 20.0) == \
        pytest.approx([3.0, 11.0, 18.0])


def test_first_token_wait_censored_where_serving_stops():
    recs = [
        rec(1.0, 1.5, 4.0, ttft_wall=1.0),          # delivered: 1.5 s
        rec(2.0, 2.0, 13.0, ttft_wall=9.0),         # after the end: 9 s
        rec(8.0, 8.1, None),                        # stalled: 20 - 8
        rec(-1.0, -1.0, 3.0),                       # due before window
    ]
    assert stats.first_token_waits(recs, 0.0, 10.0, 20.0) == \
        pytest.approx([1.5, 9.0, 12.0])


def test_per_token_after_first():
    r = rec(0.0, 0.0, 2.5, n=6, ttft_wall=0.5)
    assert stats.per_token([r], 0.0, 10.0) == [pytest.approx(0.4)]
    assert stats.per_token([rec(0, 0, 1.0, n=1)], 0.0, 10.0) == []
    assert stats.per_token([rec(-1.0, 0, 2.5, n=6)], 0.0, 10.0) == []
