"""The system under test, as the benchmark sees it: the device, its
programs, the verification service, and the spans and counters it keeps.

Only this module and the load loops touch the program.  The service is
the node's own dispatcher, `VerificationService`, over
`SignatureVerifier("tpu", fallback=False)`; its host verifier refuses
every call, so a batch that would have gone to the host path fails its
requests and counts in `failed`.
"""

import threading


class NoHost:
    """host_verifier of the benchmark's service: the device path is the
    only path."""

    backend = "host"

    def verify_signature_sets(self, sets, priority=None):
        raise RuntimeError("verification was routed to the host path")

    verify_signature_sets_per_set = verify_signature_sets


class Compiles:
    """Every XLA backend compile in this process, named by the jitted
    function (JAX's compile event: a scalar when it starts, a duration
    when it ends).  `start_in_order` starts program loads in threads so
    that each one's lowering (which holds the interpreter lock) ends
    before the next begins, and their backend compiles run side by
    side."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.events = []
        self.verify_started = 0
        self._lock = threading.Condition()
        monitoring.register_event_duration_secs_listener(self._on_event)
        monitoring.register_scalar_listener(self._on_start)

    def _on_event(self, event, secs, **kw):
        if event == self.EVENT:
            with self._lock:
                self.events.append((kw.get("fun_name", "?"), secs))

    def _on_start(self, event, value, **kw):
        if event == self.EVENT and "verify_kernel" in kw.get("fun_name", ""):
            with self._lock:
                self.verify_started += 1
                self._lock.notify_all()

    def start_in_order(self, jobs):
        threads = []
        for name, target, kwargs in jobs:
            with self._lock:
                seen = self.verify_started
            t = threading.Thread(target=target, kwargs=kwargs,
                                 name=f"load_{name}", daemon=True)
            t.start()
            with self._lock:
                while self.verify_started == seen and t.is_alive():
                    self._lock.wait(0.5)
            threads.append(t)
        return threads

    def count(self):
        with self._lock:
            return len(self.events)

    def since(self, mark):
        with self._lock:
            return list(self.events[mark:])


def devices():
    import jax

    return jax.devices()


def set_program_env(config, environ):
    """The deployment's program environment.  The value
    `planner_bucket` stands for the shape planner's own default set
    bucket, read from the program, not written as a number."""
    from lighthouse_tpu.crypto.tpu import compile_cache as cc

    for key, value in config.get("program_env", {}).items():
        if value == "planner_bucket":
            value = str(cc.ShapePlanner().bucket)
        environ[key] = str(value)


def bucket():
    from lighthouse_tpu.crypto.tpu import compile_cache as cc

    return cc.get_planner().bucket


def pk_width(sets):
    from lighthouse_tpu.crypto.tpu import compile_cache as cc

    return cc.get_planner().plan_pks(max(len(s.pubkeys) for s in sets))


def signature_set(sig, pubkeys, message):
    from lighthouse_tpu.crypto.ref.bls import SignatureSet

    return SignatureSet(sig, pubkeys, message)


def load_programs(compiles, width, per_set):
    """Load (deserialize, or compile on a cold cache) the cell's verify
    programs at (bucket, width), side by side; returns their
    compile-cache records."""
    from lighthouse_tpu.crypto.tpu import bls
    from lighthouse_tpu.crypto.tpu import compile_cache as cc

    specs = bls.kernel_specs(bucket(), width, per_set=per_set)
    errors = []

    def load(spec):
        try:
            cc.load_programs([spec])
        except Exception as e:  # noqa: BLE001 — raised below
            errors.append(e)

    jobs = [(s[0], load, {"spec": s}) for s in specs]
    cache = cc.get_cache()
    if all(cache.entry_on_disk(s[0], s[2]) for s in specs):
        # nothing to lower: deserialize them all at once
        threads = [threading.Thread(target=load, args=(s,), daemon=True)
                   for s in specs]
        for t in threads:
            t.start()
    else:
        threads = compiles.start_in_order(jobs)
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    names = {s[0] for s in specs}
    loaded = cc.get_cache().stats()["loaded"]
    return {k: v for k, v in loaded.items() if v["kernel"] in names}


def service():
    from lighthouse_tpu.crypto.backend import SignatureVerifier
    from lighthouse_tpu.verify_service import VerificationService

    return VerificationService(
        SignatureVerifier("tpu", fallback=False), host_verifier=NoHost())


def counters():
    """The program's counters the readers use, now."""
    from lighthouse_tpu.crypto.tpu import bls
    from lighthouse_tpu.utils import metrics as M
    from lighthouse_tpu.verify_service import metrics as VM

    out = {
        "pk_cache_hits": bls._PK_HITS.value,
        "pk_cache_misses": bls._PK_MISSES.value,
        "poisoned_batches": VM.POISONED_BATCHES.value,
        "device_fallbacks": M.DEVICE_FALLBACKS.value,
        "host_fallbacks": M.HOST_BACKEND_FALLBACKS.value,
    }
    for cls in ("attestation", "block"):
        h = VM.QUEUE_WAIT.with_labels(cls)
        out[f"queue_wait_sum.{cls}"] = h.sum
        out[f"queue_wait_count.{cls}"] = h.count
    return out


def launches():
    """Verify-kernel launches completed in this process so far (the
    kernel profile's `kernel_profile_launches_total`)."""
    from lighthouse_tpu.crypto.tpu import profile

    return sum(float(line.rsplit(" ", 1)[1])
               for line in profile.LAUNCHES.samples())


def spans(t0, t1):
    """Spans of the service's verify_batch traces that overlap [t0, t1],
    as (name, start, end, attrs) on the monotonic clock."""
    import time

    from lighthouse_tpu.utils import tracing

    offset = time.time() - time.monotonic()
    out = []
    for tr in tracing.recent():
        if tr["kind"] != "verify_batch":
            continue
        base = tr["wall_start"] - offset
        for s in tr["spans"]:
            start = base + s["start_ms"] / 1e3
            end = start + s["duration_ms"] / 1e3
            if end >= t0 and start <= t1:
                out.append((s["name"], start, end, s.get("attrs", {})))
    out.sort(key=lambda s: s[1])
    return out


def clear_spans():
    from lighthouse_tpu.utils import tracing

    tracing.clear()


def memory_peak_bytes(devs):
    peaks = []
    for d in devs:
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except (AttributeError, KeyError, TypeError):
            continue
    return max(peaks) if peaks else None
