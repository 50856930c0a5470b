"""What every load loop (traffic/loops/<kind>.py) shares: a Record per
request of the window, submission to VerificationService.submit, and
the wait for a verdict.  A loop waits up to `drain_s` past the window's
close for the verdicts still due: one that never comes is missing, not
late.
"""

import time


class Record:
    """One request of the window: when it was due, sent and answered."""

    __slots__ = ("request", "due", "sent", "done", "verdict", "error")

    def __init__(self, request, due):
        self.request = request
        self.due = due
        self.sent = None
        self.done = None          # monotonic time of the verdict
        self.verdict = None       # bool, or [bool] per set
        self.error = None         # exception text when the request failed

    @property
    def answered(self):
        return self.done is not None and self.error is None


def settle(rec, fut, timeout):
    """Wait up to `timeout` seconds for the request's verdict."""
    try:
        rec.verdict = fut.result(max(timeout, 0.0))
    except TimeoutError:
        return                    # missing: no verdict by the drain limit
    except Exception as e:  # noqa: BLE001 — the request failed; count it
        rec.error = f"{type(e).__name__}: {e}"[:300]
    rec.done = time.monotonic()


def submit(service, rec, priority, want_per_set):
    rec.sent = time.monotonic()
    try:
        return service.submit(rec.request.sets, priority=priority,
                              want_per_set=want_per_set)
    except Exception as e:  # noqa: BLE001 — refused at admission
        rec.error = f"{type(e).__name__}: {e}"[:300]
        rec.done = rec.sent
        return None


def warmup(service, requests, priority, want_per_set, timeout):
    """Send the untimed warm-up requests one after another and return
    their records."""
    recs = [Record(r, None) for r in requests]
    for rec in recs:
        fut = submit(service, rec, priority, want_per_set)
        if fut is not None:
            settle(rec, fut, timeout)
    return recs
