"""One run of one cell: set-up, the measured window, the reference
check, the result line.

Set-up (counted in setup_s, from process start to window start): the
device check, the deployment's data from the seed, the cell's verify
programs loaded (deserialized; compiled only on a cold cache), the
service built, and the mix's untimed warm-up requests.  A traced run
then traces the device for a fraction of a second inside a verify
launch of the same traffic, before the window.  The window runs the
mix's loop for `seconds`; verdicts still due are awaited up to DRAIN_S
past its close.  After the window: the device's peak memory,
the compiles since warm-up (there must be none), the reference verdict
of every window request, and the metrics: the cell's end-to-end metrics
(`--trace 0`) or its per-layer metrics (`--trace 1`), read over the
window; a traced run's `device` busy and window seconds and its
breakdown come from the traced slice.
"""

import json
import os
import sys
import threading
import time

from reference import pool as message_pool
from reference.verdicts import Reference

from . import check, loops, program, readers, stats, traffic
from .trace import Tracer, reduce as reduce_trace

DRAIN_S = 60.0
DEADLINE_S = 350.0         # a run's whole life, warm programs
COLD_DEADLINE_S = 1150.0   # ... when this run compiled its programs


class RunError(Exception):
    """The run cannot report a result."""


def say(**fields):
    print(json.dumps(fields), flush=True)


def _deadline(seconds, t_process):
    def out_of_time():
        print(f"benchmark: still running {seconds:.0f} s after start",
              file=sys.stderr, flush=True)
        os._exit(3)

    t = threading.Timer(max(seconds - (time.monotonic() - t_process), 0.0),
                        out_of_time)
    t.daemon = True
    t.start()
    return t


def _ms(seconds):
    return None if seconds is None else 1e3 * seconds


def end_to_end(cell, records, t0, t_end, setup_s):
    verdicts = stats.window_verdicts(records, t0, t_end)
    values = {
        "setup_s": setup_s,
        "sets_per_s": stats.verdict_rate(verdicts),
        "block_verify_ms": _ms(stats.verdict_interval(verdicts)),
    }
    out = {}
    for m in cell.end_to_end:
        v = values.get(m["name"])
        if v is None:
            raise RunError(f"{m['name']}: nothing to measure in the window "
                           f"({len(verdicts)} verdicts)")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def per_layer(cell, window):
    out = {}
    for m in cell.per_layer:
        v = cell.reader(m["name"])(window)
        if v is None:
            continue
        entry = dict(v) if isinstance(v, dict) else {"value": v}
        entry["unit"] = m["unit"]
        out[m["name"]] = entry
    return out


def _host_label(spans, a, b):
    """What the host was doing during a device gap [a, b]: the most
    specific service span that covers its middle."""
    mid = (a + b) / 2
    covering = {s[0] for s in spans if s[1] <= mid <= s[2]}
    for name, label in (("device_chunk", "launch and transfer"),
                        ("attribution", "attribution host prep"),
                        ("kernel", "host prep between chunks"),
                        ("batch", "batch assembly"),
                        ("queue_wait", "coalescing wait")):
        if name in covering:
            return label
    return "no request in the service"


def breakdown(summary, spans):
    ops = sorted(summary.ops.items(), key=lambda kv: -kv[1])[:10]
    gaps = [[_host_label(spans, a, b), b - a] for a, b in summary.gaps]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": gaps}


def warm_up(svc, plan, tr):
    """The mix's untimed warm-up requests; all must be answered."""
    warm = loops.warmup(svc, plan.warmup, tr["priority"],
                        bool(tr["want_per_set"]), timeout=COLD_DEADLINE_S)
    bad = [r.error or "no verdict" for r in warm if not r.answered]
    if bad:
        raise RunError(f"warm-up request failed: {bad[0]}")
    return warm


def drive(cell, svc, requests, t0, seconds, limit=None):
    """The mix's loop over `requests` from t0 for `seconds`; `limit`
    caps the requests sent."""
    tr = cell.traffic
    return cell.loop.drive(svc, requests, t0, seconds, tr["priority"],
                           bool(tr["want_per_set"]), DRAIN_S, limit=limit)


def traced_slice(cell, svc, plan, tracer):
    """Trace the device for `trace_seconds` inside a verify launch of
    the plan's slice requests, before the window: the trace opens once
    the first launch has completed, so it falls in the next one.  A
    whole launch records millions of device events and takes minutes to
    write out, more than a run may last; a fraction of one writes in
    seconds.  Returns the trace file."""
    secs = float(cell.traffic["trace_seconds"])
    path = []
    first = program.launches()
    served = threading.Event()       # the slice's requests are answered

    def trace_inside_a_launch():
        while program.launches() == first and not served.is_set():
            time.sleep(0.001)
        tracer.start()
        time.sleep(secs)
        path.append(tracer.stop())

    tracer_thread = threading.Thread(target=trace_inside_a_launch,
                                     name="bench_trace", daemon=True)
    tracer_thread.start()
    t = time.monotonic()
    recs = drive(cell, svc, plan.trace, t, DEADLINE_S,
                 limit=len(plan.trace))
    served.set()
    tracer_thread.join()
    bad = [r.error or "no verdict" for r in recs if not r.answered]
    if bad:
        raise RunError(f"traced request failed: {bad[0]}")
    if not path or path[0] is None:
        raise RunError("the profiler wrote no trace")
    say(phase="trace", slice_s=tracer.t_stop - tracer.mark,
        written_s=time.monotonic() - t)
    return path[0]


def run(cell, args, t_process):
    """One run of `cell` (cells.Cell); returns the exit code."""
    devs = program.devices()
    if devs[0].platform != "tpu":
        print(f"benchmark: JAX's default device is {devs[0].platform!r}, "
              "not a TPU", file=sys.stderr)
        return 1
    if len(devs) < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(devs)}", file=sys.stderr)
        return 1
    devs = devs[:cell.chips]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    try:
        return _run(cell, args, t_process, devs, device)
    except RunError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1


def _run(cell, args, t_process, devs, device):
    from lighthouse_tpu.utils import xla_cache

    tr = cell.traffic
    program.set_program_env(cell.config, os.environ)
    xla_cache.configure()
    compiles = program.Compiles()
    deadline = _deadline(COLD_DEADLINE_S, t_process)

    t = time.monotonic()
    pool = message_pool.load()
    plan = traffic.build(cell, args.seed, pool, program.bucket(),
                         program.signature_set, traced=bool(args.trace))
    data_s = time.monotonic() - t
    width = program.pk_width(plan.window[0].sets)
    t = time.monotonic()
    loaded = program.load_programs(compiles, width,
                                   per_set="per_set" in tr["programs"])
    load_s = time.monotonic() - t
    if all(p["source"] == "deserialized" for p in loaded.values()):
        deadline.cancel()
        deadline = _deadline(DEADLINE_S, t_process)
    svc = program.service()
    per_set = bool(tr["want_per_set"])
    warm = warm_up(svc, plan, tr)
    say(phase="setup", cell=cell.name, seed=args.seed, data_s=data_s,
        program_load_s=load_s, width=width,
        programs={k: v["source"] for k, v in loaded.items()},
        warmup_s=[r.done - r.sent for r in warm])

    program.clear_spans()
    mark = compiles.count()
    tracer = None
    if args.trace:
        tracer = Tracer(os.path.join(cell.bench_dir, ".cache", "trace"))
        trace_path = traced_slice(cell, svc, plan, tracer)
    before = program.counters()
    t0 = time.monotonic()
    t_end = t0 + args.seconds
    setup_s = t0 - t_process
    records = drive(cell, svc, plan.window, t0, args.seconds)
    after = program.counters()
    in_window = compiles.since(mark)
    peak = program.memory_peak_bytes(devs)
    svc.stop()
    if in_window:
        raise RunError(f"compiled inside the window: {in_window}")

    t = time.monotonic()
    ok, numbers, compared = check.compare(records, Reference(pool), per_set)
    ref_s = time.monotonic() - t
    errors = sum(r.error is not None for r in records)
    failed = errors + numbers["missing_verdicts"]
    device["memory_peak_bytes"] = peak
    result = {"correct": ok, "attempted": len(records), "failed": failed}
    if tracer:
        t = time.monotonic()
        summary = reduce_trace(trace_path, tracer)
        say(phase="trace_read", read_s=time.monotonic() - t,
            dropped=summary.t1 < tracer.t_stop)
        spans = program.spans(tracer.mark, t_end)
        window = readers.Window(
            cell=cell, t0=t0, t_end=t_end, records=records, before=before,
            after=after, spans=spans, device=summary, programs=loaded)
        result["metrics"] = per_layer(cell, window)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["device"] = device
        result["breakdown"] = breakdown(summary, spans)
        print(f"benchmark: trace of {summary.devices} device plane(s), "
              f"{len(summary.ops)} op names", file=sys.stderr)
    else:
        result["metrics"] = end_to_end(cell, records, t0, t_end, setup_s)
        result["device"] = device
    result["checks"] = check.as_json(numbers)
    say(phase="check", compared=compared, reference_s=ref_s,
        invalid_compared=sum(bool(r.request.invalid) for r in records
                             if r.answered),
        errors=errors, counters={k: after[k] - before[k] for k in after})
    for line in check.lines(numbers):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    deadline.cancel()
    return 0
