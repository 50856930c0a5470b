"""What the per-layer readers (metrics/<name>.py) share.

A reader is `read(window) -> number | dict | None`: a dict carries the
number under "value" and notes beside it; None means the window held
nothing to read, and the metric is left out of the line.
"""


class Window:
    """Everything a run measured, handed to each reader."""

    def __init__(self, **kw):
        self.cell = kw["cell"]
        self.t0 = kw["t0"]                  # window start (monotonic)
        self.t_end = kw["t_end"]            # window end
        self.records = kw["records"]        # loops.Record of the window
        self.before = kw["before"]          # program counters at t0
        self.after = kw["after"]            # ... after the drain
        self.spans = kw["spans"]            # (name, start, end, attrs)
        self.device = kw["device"]          # trace.Summary or None
        self.programs = kw["programs"]      # compile-cache records

    def delta(self, key):
        return self.after[key] - self.before[key]


def spans_named(w, name, t0=None, t1=None):
    """Spans called `name` that start inside [t0, t1] (the window by
    default)."""
    t0 = w.t0 if t0 is None else t0
    t1 = w.t_end if t1 is None else t1
    return [s for s in w.spans if s[0] == name and t0 <= s[1] <= t1]


def mean_host_prep_ms(w, per_set=False):
    vals = [s[3]["host_prep_ms"] for s in spans_named(w, "device_chunk")
            if bool(s[3].get("per_set")) == per_set]
    return sum(vals) / len(vals) if vals else None
