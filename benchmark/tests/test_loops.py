import threading
import time

from harness import cells, loops

from conftest import BENCH_DIR

closed = cells.module(BENCH_DIR, "traffic/loops", "closed")


class _Fut:
    def __init__(self):
        self.ev = threading.Event()
        self.value = None

    def result(self, timeout=None):
        if not self.ev.wait(timeout):
            raise TimeoutError("not done")
        return self.value


class _Service:
    """Answers every request `delay` seconds after it is submitted."""

    def __init__(self, delay, hang_after=None):
        self.delay = delay
        self.sent = []
        self.hang_after = hang_after

    def submit(self, sets, priority=None, want_per_set=False):
        fut = _Fut()
        self.sent.append((time.monotonic(), priority, want_per_set))
        if self.hang_after is not None and len(self.sent) > self.hang_after:
            return fut                     # never answered

        def answer():
            fut.value = [True] * len(sets) if want_per_set else True
            fut.ev.set()

        threading.Timer(self.delay, answer).start()
        return fut


class _Req:
    def __init__(self, n=2):
        self.sets = [object()] * n


def test_closed_loop_keeps_one_request_outstanding_within_the_window():
    svc = _Service(0.05)
    reqs = [_Req() for _ in range(3)]
    t0 = time.monotonic()
    recs = closed.drive(svc, reqs, t0, 0.5, "attestation", False, 5.0)
    assert 6 <= len(recs) <= 11
    assert all(r.answered and r.verdict is True for r in recs)
    assert all(r.sent < t0 + 0.5 for r in recs)
    # the next request goes out only after the last verdict
    for a, b in zip(recs, recs[1:]):
        assert b.sent >= a.done
    # wrapped around the three requests
    assert recs[3].request is reqs[0]


def test_closed_loop_stops_on_a_request_past_the_drain_limit():
    svc = _Service(0.01, hang_after=2)
    t0 = time.monotonic()
    recs = closed.drive(svc, [_Req()], t0, 0.3, "block", False, 0.2)
    assert recs[-1].done is None and not recs[-1].answered
    assert time.monotonic() - t0 < 1.5


def test_closed_loop_sends_no_more_than_its_limit():
    svc = _Service(0.01)
    recs = closed.drive(svc, [_Req()], time.monotonic(), 5.0, "block",
                        True, 1.0, limit=1)
    assert len(recs) == 1 and recs[0].verdict == [True, True]
    assert svc.sent[0][1:] == ("block", True)


def test_warmup_answers_each_request_before_the_next():
    svc = _Service(0.05)
    recs = loops.warmup(svc, [_Req(1), _Req(1)], "attestation", False,
                        timeout=5)
    assert all(r.answered for r in recs)
    assert recs[1].sent >= recs[0].done
