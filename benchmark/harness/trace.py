"""The device trace of a `--trace 1` run, reduced to what the readers use.

The profiler writes an `.xplane.pb`; `jax.profiler.ProfileData` reads it.
A host annotation `bench.clock`, opened at a known `time.monotonic()`,
puts the trace's clock onto the program's span clock.  On each device
plane (`/device:TPU:<n>`):

  * busy time is the union of the intervals of the events on the op line
    (`XLA Ops`, or every line of the plane where it has none), clipped to
    the traced window; idle is the rest of the window;
  * the longest idle gaps are kept with their times, so that the session
    can name what the host was doing in each.
"""

import glob
import os
import shutil
import time
from array import array

import numpy as np

CLOCK_MARK = "bench.clock"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
TRACEME_LINE = "XLA TraceMe"
DROPPED = "Trace Buffers Dropped"


class Tracer:
    """Start and stop the profiler in `log_dir`.  Python-function
    tracing is off and host tracing is at its first level (the
    annotations), so the trace holds the device's events and little
    else.  A verify launch records some 2.7 million op events on a v5e
    chip: the device's trace buffers hold about two launches, and
    writing one launch out takes minutes, so a run traces a fraction of
    a second."""

    def __init__(self, log_dir):
        self.log_dir = log_dir
        self.mark = self.t_stop = None

    def start(self):
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        os.makedirs(self.log_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.mark = time.monotonic()
        with jax.profiler.TraceAnnotation(CLOCK_MARK):
            pass

    def stop(self):
        import jax

        self.t_stop = time.monotonic()
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        return found[0] if found else None


class Summary:
    """Reduced device trace, on the monotonic clock."""

    def __init__(self, t0, t1):
        self.t0, self.t1 = t0, t1      # traced window
        self.devices = 0
        self.busy_s = 0.0              # mean over the device planes
        self.ops = {}                  # op name -> device seconds
        self.gaps = []                 # (start, end) idle, longest first

    @property
    def window_s(self):
        return self.t1 - self.t0


def _union(starts, ends):
    """Disjoint [start, end] segments covering the given intervals."""
    if not len(starts):
        return []
    order = np.argsort(starts, kind="stable")
    a = np.asarray(starts)[order]
    b = np.maximum.accumulate(np.asarray(ends)[order])
    new = np.empty(len(a), bool)
    new[0] = True
    new[1:] = a[1:] > b[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(a) - 1)
    return list(zip(a[first].tolist(), b[last].tolist()))


def _events(line):
    """(name, start_ns, duration_ns) of a profiler line, or of a list
    of such tuples (the recorded fixture)."""
    if hasattr(line, "events"):
        for e in line.events:
            yield e.name, e.start_ns, e.duration_ns
    else:
        for e in line:
            yield tuple(e)


MARK_SEARCH_NS = 10e9     # the mark opens right after the trace starts


def mark_time(profile):
    """Trace time (ns) at which the `bench.clock` annotation opened;
    None without one."""
    for plane in profile.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for name, start, _ in _events(line):
                if name == CLOCK_MARK:
                    return start
                if start > MARK_SEARCH_NS:
                    break
    return None


def reduce_planes(planes, t0, t1, mark, keep_gaps=10):
    """Summary of device planes given as
    [(plane name, {line name: line})] (a line is a profiler line or a
    list of (event, start_ns, duration_ns)), on a clock that puts the
    `bench.clock` mark at `mark` ns; the annotation opened at monotonic
    time t0 and the trace stopped at t1.  Where the device dropped trace
    buffers, the window ends where the drop began.  Busy time comes from
    the op line where it has events, else from the program line."""
    planes = [(n, lines) for n, lines in planes
              if n.startswith("/device:TPU:")]
    shift = t0 - mark / 1e9
    for _, lines in planes:
        for ev, start, _ in _events(lines.get(TRACEME_LINE, ())):
            if ev == DROPPED:
                t1 = min(t1, start / 1e9 + shift)
    s = Summary(t0, t1)
    gaps = []
    busy = []
    for name, lines in planes:
        if OP_LINE in lines or MODULE_LINE in lines:
            op_lines = [lines.get(OP_LINE, ()), lines.get(MODULE_LINE, ())]
        else:
            op_lines = list(lines.values())
        starts, ends = array("d"), array("d")
        ops = {}
        for line in op_lines:
            for ev, start, dur in _events(line):
                a = max(start / 1e9 + shift, t0)
                b = min((start + dur) / 1e9 + shift, t1)
                if b > a:
                    starts.append(a)
                    ends.append(b)
                    ops[ev] = ops.get(ev, 0.0) + dur / 1e9
            if starts:
                break
        for ev, secs in ops.items():
            # an op's name is its HLO text; keep the instruction name
            key = ev.split(" = ")[0]
            s.ops[key] = s.ops.get(key, 0.0) + secs
        if not starts:
            continue
        s.devices += 1
        merged = _union(starts, ends)
        busy.append(sum(b - a for a, b in merged))
        edge = t0
        for a, b in merged:
            if a > edge:
                gaps.append((edge, a))
            edge = b
        if t1 > edge:
            gaps.append((edge, t1))
    s.busy_s = sum(busy) / len(busy) if busy else 0.0
    s.gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:keep_gaps]
    return s


def planes_of(profile):
    """[(plane name, {line name: profiler line})] of the device planes
    of a ProfileData."""
    return [(plane.name, {line.name: line for line in plane.lines})
            for plane in profile.planes
            if plane.name.startswith("/device:")]


def reduce(path, tracer):
    """Summary of the trace file of a Tracer's window."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    mark = mark_time(profile)
    if mark is None:
        raise RuntimeError(f"no {CLOCK_MARK} annotation in {path}")
    return reduce_planes(planes_of(profile), tracer.mark, tracer.t_stop,
                         mark)
