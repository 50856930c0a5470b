"""Lightweight pipeline tracing: spans threaded router -> beacon_processor
-> verify_service -> crypto backend.

Not OpenTelemetry — a process-local ring buffer of recent traces served
at the `/lighthouse/tracing` debug endpoint, answering the delay-
attribution question Prometheus histograms can't: for THIS block (or
THIS verification batch), how long was queue wait vs. batch assembly vs.
kernel time, and what pad ratio / occupancy did the device see.

Usage contract:

  * a pipeline entry point creates a trace (`start_trace(kind, **attrs)`)
    and makes it current for its thread with `use(trace)`; code running
    underneath reads `current_trace()` and attaches spans
  * traces cross thread boundaries EXPLICITLY: verify_service requests
    capture the submitter's current trace at submit() and the dispatcher
    thread appends the stage spans before resolving the future
  * `finish()` publishes the trace into the ring buffer (idempotent)

The verify path's spans, by thread (`verify_batch` traces):

  dispatcher   queue_wait, batch, kernel, attribution, prep_wait, launch,
               device_chunk, and prep on the serial (one-chunk) path
  prep thread  prep, one per chunk staged by a pipelined batch, drained
               chunks included

Span timestamps are time.monotonic() seconds; each trace additionally
records one wall-clock timestamp at creation for display.  Spans may
start before the trace was created (a queued request's submit time) —
their relative start_ms is simply negative.

Live spans: `Trace.span(...)` and the module-level `span(...)` time the
block they wrap.  While the block runs, the span is the innermost
*region* of its thread.  A span records the enclosing region's name in
a `parent` attribute (None at the top of a thread), so its self time is
its duration less its children's.  Every region also opens a
`jax.profiler.TraceAnnotation` of the same name, carrying the trace id:
while a profiler session runs, the spans lie on its host plane, on the
device trace's own clock.  Outside a session the annotation costs a few
hundred nanoseconds.  `region(...)` alone marks a block whose ring span
its caller records itself (the dispatcher's `kernel`).

Trace ids are NODE-UNIQUE strings ``<node>-<seq>``: the counter alone
is process-local and collides the moment two nodes' traces meet (the
remote verification fabric stitches server spans into client traces,
and an ambiguous id would join the wrong pair).  The node component
defaults to a random token and can be pinned to an operator-meaningful
name with `set_node_id` (the wire node does this with its peer id).
`/lighthouse/logs` joins are by-equality on the full string, so they
keep working unchanged.
"""

import itertools
import os
import threading
import time
from collections import deque
from contextlib import contextmanager

CAPACITY = 256

_BUFFER = deque(maxlen=CAPACITY)
_BUF_LOCK = threading.Lock()
_NEXT_ID = itertools.count(1)
_TLS = threading.local()

_NODE_LOCK = threading.Lock()
_NODE_ID = None


def node_id():
    """This process's trace-id prefix (lazily drawn random token until
    `set_node_id` pins something meaningful)."""
    global _NODE_ID
    with _NODE_LOCK:
        if _NODE_ID is None:
            _NODE_ID = os.urandom(4).hex()
        return _NODE_ID


def set_node_id(nid):
    """Pin the node component of new trace ids (idempotent overwrite;
    already-issued ids keep their old prefix).  Sanitized to keep ids
    join- and URL-friendly."""
    global _NODE_ID
    nid = "".join(
        c for c in str(nid) if c.isalnum() or c in "._"
    )[:32] or None
    with _NODE_LOCK:
        if nid is not None:
            _NODE_ID = nid
    return _NODE_ID


class Trace:
    __slots__ = (
        "trace_id", "kind", "attrs", "spans", "wall_start", "t_start",
        "_finished", "_lock",
    )

    def __init__(self, kind, **attrs):
        self.trace_id = f"{node_id()}-{next(_NEXT_ID)}"
        self.kind = kind
        self.attrs = dict(attrs)
        self.spans = []          # (name, start, end, attrs)
        self.wall_start = time.time()
        self.t_start = time.monotonic()
        self._finished = False
        self._lock = threading.Lock()

    def add_span(self, name, start=None, end=None, **attrs):
        end = time.monotonic() if end is None else float(end)
        start = end if start is None else float(start)
        with self._lock:
            self.spans.append((name, start, end, attrs))
        return self

    @contextmanager
    def span(self, name, **attrs):
        with region(name, self) as parent:
            t0 = time.monotonic()
            try:
                yield self
            finally:
                self.add_span(name, t0, time.monotonic(), parent=parent,
                              **attrs)

    def finish(self, **attrs):
        with self._lock:
            if attrs:
                self.attrs.update(attrs)
            if self._finished:
                return self
            self._finished = True
        with _BUF_LOCK:
            _BUFFER.append(self)
        return self

    def span_names(self):
        with self._lock:
            return [s[0] for s in self.spans]

    def snapshot_spans(self):
        """Consistent (name, start, end, attrs) snapshot — the wire
        serve path reads this to ship span timings back to the caller."""
        with self._lock:
            return list(self.spans)

    def to_dict(self):
        with self._lock:
            spans = list(self.spans)
            attrs = dict(self.attrs)
        t_end = max((e for _, _, e, _ in spans), default=self.t_start)
        return {
            "trace_id": self.trace_id,
            "kind": self.kind,
            "wall_start": round(self.wall_start, 6),
            "mono_start": round(self.t_start, 6),
            "duration_ms": round((t_end - self.t_start) * 1e3, 3),
            "attrs": attrs,
            "spans": [
                {
                    "name": name,
                    "start_ms": round((s - self.t_start) * 1e3, 3),
                    "duration_ms": round((e - s) * 1e3, 3),
                    **({"attrs": a} if a else {}),
                }
                for name, s, e, a in spans
            ],
        }


def start_trace(kind, **attrs):
    return Trace(kind, **attrs)


def current_trace():
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else None


@contextmanager
def use(trace):
    """Make `trace` the calling thread's current trace for the block.
    `use(None)` is a no-op, so call sites don't branch on optionality."""
    if trace is None:
        yield None
        return
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    stack.append(trace)
    try:
        yield trace
    finally:
        stack.pop()


_ANNOTATION = None      # jax.profiler.TraceAnnotation, imported on first use


def _annotation(name, trace):
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    if trace is None:
        return _ANNOTATION(name)
    return _ANNOTATION(name, trace_id=trace.trace_id)


@contextmanager
def region(name, trace=None):
    """Make `name` the calling thread's innermost live region for the
    block, under a profiler annotation of the same name that carries
    `trace`'s id.  Yields the enclosing region's name (None at the top
    of the thread).  Records no span: `span` does, or the caller."""
    live = getattr(_TLS, "live", None)
    if live is None:
        live = _TLS.live = []
    parent = live[-1] if live else None
    live.append(name)
    try:
        with _annotation(name, trace):
            yield parent
    finally:
        live.pop()


@contextmanager
def span(name, trace=None, **attrs):
    """Live span `name` on `trace` (default: the thread's current
    trace).  Without a trace the block still opens its region and
    annotation, and nothing is recorded."""
    trace = current_trace() if trace is None else trace
    if trace is None:
        with region(name):
            yield None
        return
    with trace.span(name, **attrs):
        yield trace


def depth():
    """Finished traces currently buffered (monitoring snapshot reads
    this instead of materializing every trace dict via recent())."""
    with _BUF_LOCK:
        return len(_BUFFER)


def recent(limit=None):
    """Most-recent-first dicts of the finished traces in the ring."""
    with _BUF_LOCK:
        traces = list(_BUFFER)
    traces.reverse()
    if limit is not None:
        traces = traces[: max(int(limit), 0)]
    return [t.to_dict() for t in traces]


def clear():
    with _BUF_LOCK:
        _BUFFER.clear()
