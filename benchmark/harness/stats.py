"""Window arithmetic: verdict-to-verdict rates and intervals."""


def window_verdicts(records, t0, t_end):
    """(time, sets) of every verdict that arrived inside [t0, t_end],
    in arrival order."""
    out = [(r.done, len(r.request.sets)) for r in records
           if r.answered and t0 <= r.done <= t_end]
    out.sort()
    return out


def verdict_rate(verdicts):
    """Sets per second from the window's first verdict to its last: the
    sets whose verdicts came after the first, over the time between the
    first and the last.  None with fewer than two verdicts."""
    if len(verdicts) < 2:
        return None
    span = verdicts[-1][0] - verdicts[0][0]
    if span <= 0:
        return None
    return sum(n for _, n in verdicts[1:]) / span


def verdict_interval(verdicts):
    """Mean seconds between consecutive verdicts, first to last."""
    if len(verdicts) < 2:
        return None
    return (verdicts[-1][0] - verdicts[0][0]) / (len(verdicts) - 1)
