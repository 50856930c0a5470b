import pytest

from harness import stats


class _Req:
    def __init__(self, n):
        self.sets = [None] * n


class _Rec:
    def __init__(self, n, due, done, error=None):
        self.request = _Req(n)
        self.due = due
        self.done = done
        self.error = error

    @property
    def answered(self):
        return self.done is not None and self.error is None


def test_verdict_rate_counts_sets_after_the_first_verdict():
    recs = [_Rec(256, 0, 10.0), _Rec(256, 10, 20.0), _Rec(256, 20, 30.0),
            _Rec(256, 30, 45.0)]
    v = stats.window_verdicts(recs, 0.0, 40.0)
    assert [t for t, _ in v] == [10.0, 20.0, 30.0]
    assert stats.verdict_rate(v) == pytest.approx(512 / 20.0)
    assert stats.verdict_interval(v) == pytest.approx(10.0)
    assert stats.verdict_rate(v[:1]) is None
    assert stats.verdict_interval([]) is None


def test_window_verdicts_skip_failed_and_missing_requests():
    recs = [_Rec(1, 0, 1.0), _Rec(1, 0, 2.0, error="x"), _Rec(1, 0, None)]
    assert stats.window_verdicts(recs, 0.0, 5.0) == [(1.0, 1)]
