"""Loop kind "closed": one request in flight; the next is sent the
moment its verdict returns, until the window closes.  The window's
`pool_requests` requests are sent in turn, from the first again once
all have gone.  The traced slice is one request."""

import time

from harness import loops

KEYS = {"pool_requests"}
SLICE_REQUESTS = 1


def window_requests(params):
    return int(params["pool_requests"])


def drive(service, requests, t0, seconds, priority, want_per_set, drain_s,
          limit=None):
    """Records of the requests sent from t0 until t0 + seconds; `limit`
    caps how many are sent."""
    t_end = t0 + seconds
    recs = []
    while time.monotonic() < t_end and (limit is None or len(recs) < limit):
        rec = loops.Record(requests[len(recs) % len(requests)],
                           time.monotonic())
        recs.append(rec)
        fut = loops.submit(service, rec, priority, want_per_set)
        if fut is not None:
            loops.settle(rec, fut, t_end + drain_s - time.monotonic())
            if rec.done is None:
                break             # stuck past the drain limit: stop
    return recs
