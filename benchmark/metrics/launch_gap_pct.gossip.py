"""Dispatcher (verify_service/service.py): the share of the window, on
the host clock, in which no verify launch was in flight,
100 x (1 - |union of the `launch` spans, clipped to the window| /
window).  Launches on one chip run one at a time, so this bounds the
device's idle time between launches from above; it is not the device's
idle share, which only a device trace gives.  Notes: the longest gap
and the names of the spans that cover its middle (none: no request was
in the service).  None where the window holds no `launch` span."""


def read(w):
    window = w.t_end - w.t0
    segs = sorted((max(s[1], w.t0), min(s[2], w.t_end)) for s in w.spans
                  if s[0] == "launch" and s[2] > w.t0 and s[1] < w.t_end)
    if not segs or window <= 0:
        return None
    covered = 0.0
    gaps = []
    edge = w.t0
    for a, b in segs:
        if a > edge:
            gaps.append((edge, a))
        if b > edge:
            covered += b - max(a, edge)
            edge = b
    if w.t_end > edge:
        gaps.append((edge, w.t_end))
    out = {"value": 100.0 * (1.0 - covered / window), "longest_gap_ms": 0.0}
    if gaps:
        a, b = max(gaps, key=lambda g: g[1] - g[0])
        mid = (a + b) / 2
        out["longest_gap_ms"] = 1e3 * (b - a)
        out["longest_gap_in"] = sorted({s[0] for s in w.spans
                                        if s[1] <= mid <= s[2]})
    return out
