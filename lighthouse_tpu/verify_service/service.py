"""VerificationService: cross-caller continuous batching for BLS work.

The device kernel amortizes its fixed cost only at large batch sizes
(BENCH: the gossip-batch curve knees at the compile bucket), but each
call path on its own offers small batches — a single proposer signature,
a page of discovery records, one sync aggregate.  This service is the
missing coalescing layer: all callers submit; one dispatcher forms
deadline-aware micro-batches across them and runs the existing
`SignatureVerifier` backend seam once per batch.

Request lifecycle:

    submit(sets, priority, deadline) -> VerifyFuture
        bounded per-class queue (admission control raises QueueFullError)
    dispatcher: dispatch when total queued sets >= target_batch
        OR the oldest queued request's deadline arrives
    one backend call per batch; on a failed batch, ONE extra per-set
        pass (crypto/tpu/bls.py:329 / backend.py:130) attributes the
        poison to individual submitters — innocent futures still succeed

The blocking `verify_signature_sets` / `verify_signature_sets_per_set`
wrappers (and the `backend` property) make the service a drop-in
`SignatureVerifier`, so every existing call site routes through it
unchanged apart from a priority tag.
"""

import heapq
import itertools
import threading
import time
from collections import deque
from queue import Empty, Queue

from ..crypto.backend import SignatureVerifier
from ..utils import failpoints, locks, tracing
from ..utils.logging import get_logger
from . import metrics as M
from .circuit import OPEN, CircuitBreaker

log = get_logger("verify_service")

# priority classes, highest first (ISSUE: block > aggregate > attestation
# > discovery/light-client).  Index IS the drain order.
PRIORITY_CLASSES = ("block", "aggregate", "attestation", "discovery")
_CLASS_INDEX = {name: i for i, name in enumerate(PRIORITY_CLASSES)}
_PRIORITY_ALIASES = {"light_client": "discovery"}

# shed-by-class policy: the overload level at which each class is
# REJECTED before queueing (blocks and aggregates are never shed — an
# aggregate stands in for a whole committee's attestations).  Level 1 =
# device circuit open or queues past the shed watermark; level 2 =
# queues saturated well past it.
SHED_LEVEL = {"discovery": 1, "attestation": 2}

DEFAULT_TARGET_BATCH = 128          # dispatch immediately at this many sets
DEFAULT_MAX_BATCH = 512             # never exceed (device chunk ceiling)
DEFAULT_MIN_TARGET = 16             # adaptive controller's lower bound
DEFAULT_MAX_DELAY = {               # per-class coalescing window (seconds)
    "block": 0.002,                 # blocks are latency-critical
    "aggregate": 0.010,
    "attestation": 0.025,
    "discovery": 0.050,             # discovery/light-client can wait
}
DEFAULT_QUEUE_CAPS = {              # requests, mirroring beacon_processor caps
    "block": 1024,
    "aggregate": 4096,
    "attestation": 16384,
    "discovery": 4096,
}


def verify_with_verdicts(verifier, sets, priority="attestation"):
    """(ok, verdicts) for the batch-then-attribute call pattern; on a
    failed batch `verdicts` is ALWAYS the per-set vector (None only when
    ok).

    Against a VerificationService this is ONE want_per_set submission: a
    clean batch costs one backend pass ([True]*n is free) and a poisoned
    batch exactly one attribution pass — asking for a bool would discard
    the verdicts the service already computed and force the caller to
    re-submit the same sets for a third pass.  Against a bare
    SignatureVerifier it runs the pre-service two-call pattern (batch,
    then per-set on failure) so every call site reduces to
    `if not ok: use verdicts`.
    """
    sets = list(sets)
    if sets and hasattr(verifier, "submit"):
        verdicts = verifier.verify_signature_sets_per_set(
            sets, priority=priority
        )
        return all(verdicts), verdicts
    ok = verifier.verify_signature_sets(sets, priority=priority)
    if ok:
        return True, None
    return False, verifier.verify_signature_sets_per_set(
        sets, priority=priority
    )


def _next_staged(out_q, producer):
    """The next (t0, t1, item) the prep thread staged; None once it has
    exited without staging one.  Empty alone does NOT mean the producer
    died — a slow prep can exceed any fixed timeout — so only a dead
    thread ends the wait."""
    while True:
        try:
            return out_q.get(timeout=0.25)
        except Empty:
            if not producer.is_alive():
                return None


class QueueFullError(RuntimeError):
    """Admission control: the request's class queue is at capacity."""


class LoadShedError(QueueFullError):
    """Overload policy rejected the request before queueing: low-value
    work (discovery/light-client, then attestations) is dropped so the
    degraded path spends its budget on blocks and aggregates.  Subclass
    of QueueFullError so pre-shed call sites that caught overflow keep
    working; the blocking compat wrappers distinguish the two — overflow
    degrades to an inline verify, shed fails closed."""


class ShedVerdicts(list):
    """Per-set verdict vector for SHED work: all False (fail-closed),
    but distinguishable from real invalid-signature verdicts via
    `.shed` — callers that cache verdicts by immutable input bytes
    (network/discovery.py's record cache) must NOT persist these, or
    valid records would stay rejected long after the overload clears."""

    shed = True


class ServiceStopped(RuntimeError):
    """The service stopped while the request was queued."""


def normalize_priority(priority):
    if priority is None:
        return "attestation"
    priority = _PRIORITY_ALIASES.get(priority, priority)
    return priority if priority in _CLASS_INDEX else "attestation"


class VerifyFuture:
    """Completion handle for one submitted request."""

    __slots__ = ("_event", "_result", "_error")

    def __init__(self):
        self._event = threading.Event()
        self._result = None
        self._error = None

    def done(self):
        return self._event.is_set()

    def set_result(self, value):
        self._result = value
        self._event.set()

    def set_error(self, exc):
        self._error = exc
        self._event.set()

    def result(self, timeout=None):
        if not self._event.wait(timeout):
            raise TimeoutError("verification not complete")
        if self._error is not None:
            raise self._error
        return self._result


class _Request:
    __slots__ = ("sets", "future", "cls", "deadline", "submitted", "per_set",
                 "trace", "dispatched")

    def __init__(self, sets, future, cls, deadline, submitted, per_set,
                 trace=None):
        self.sets = sets
        self.future = future
        self.cls = cls
        self.deadline = deadline
        self.submitted = submitted
        self.per_set = per_set
        # the submitter thread's current pipeline trace: the dispatcher
        # appends queue-wait/batch/kernel spans to it before resolving
        self.trace = trace
        # marks this request's deadline-heap entry stale once popped
        # from its class queue (lazy heap deletion)
        self.dispatched = False


class AdaptiveBatchController:
    """EWMA knee controller for the dispatch threshold.

    Every dispatched batch contributes one (sets, kernel_seconds) sample.
    EWMA first/second moments give a running least-squares fit
    ``t ≈ fixed + per_set·n``; the knee ``n* = fixed / per_set`` is the
    batch size at which the per-batch fixed cost (launch, padding, batch
    bookkeeping) has been amortized down to the marginal per-set cost —
    the measured operating point the continuous-batching literature
    (Orca-style iteration scheduling) picks instead of a static 128.
    `update` walks the target a quarter of the way toward the knee per
    batch (jumping would thrash the coalescing window) and clamps to
    [lo, hi], so a nonsense fit can never push the dispatcher outside
    its bounds."""

    def __init__(self, initial, lo, hi, alpha=0.15):
        self.lo = float(lo)
        self.hi = float(max(hi, lo))
        self.alpha = float(alpha)
        self.target = min(max(float(initial), self.lo), self.hi)
        self._m_n = None          # EWMA moments of (n, t) samples
        self._m_t = self._m_nn = self._m_nt = 0.0
        self.fixed_s = None       # last fitted per-batch fixed cost
        self.per_set_s = None     # last fitted marginal per-set cost

    def update(self, n, t):
        """Feed one (batch sets, kernel seconds) sample; returns the new
        integer target."""
        if n <= 0 or t < 0.0:
            return int(round(self.target))
        n, t = float(n), float(t)
        a = self.alpha
        if self._m_n is None:
            self._m_n, self._m_t = n, t
            self._m_nn, self._m_nt = n * n, n * t
            return int(round(self.target))
        self._m_n += a * (n - self._m_n)
        self._m_t += a * (t - self._m_t)
        self._m_nn += a * (n * n - self._m_nn)
        self._m_nt += a * (n * t - self._m_nt)
        var = self._m_nn - self._m_n * self._m_n
        if var <= 1e-9:
            return int(round(self.target))    # no size diversity yet
        per_set = (self._m_nt - self._m_n * self._m_t) / var
        fixed = self._m_t - per_set * self._m_n
        self.fixed_s = max(fixed, 0.0)
        self.per_set_s = max(per_set, 0.0)
        if per_set <= 0.0:
            knee = self.hi        # flat marginal cost: batch as large as allowed
        elif fixed <= 0.0:
            knee = self.lo        # no fixed cost to amortize
        else:
            knee = fixed / per_set
        knee = min(max(knee, self.lo), self.hi)
        self.target = min(max(self.target + 0.25 * (knee - self.target),
                              self.lo), self.hi)
        return int(round(self.target))


class VerificationService:
    """Process-wide asynchronous verification dispatcher.

    `verifier` is the backend seam (crypto/backend.SignatureVerifier or
    any duck-typed equivalent).  `host_verifier` overrides the path the
    circuit breaker pins to; by default a device-backed primary degrades
    to `SignatureVerifier("native")` (which itself falls through to the
    oracle).  The dispatcher runs under a supervised TaskExecutor thread
    when `start(executor)` is called (node wiring), or under a lazily
    spawned daemon thread on first submit (tests, CLI tools).
    """

    def __init__(self, verifier=None, host_verifier=None,
                 target_batch=DEFAULT_TARGET_BATCH,
                 max_batch=DEFAULT_MAX_BATCH,
                 max_delay=None, queue_caps=None,
                 breaker_threshold=3, breaker_cooldown=30.0,
                 breaker_probe_max=None,
                 shed_watermark=None,
                 adaptive_batch=False, target_bounds=None,
                 remote_pool=None, mesh_devices=None):
        self.verifier = verifier or SignatureVerifier("oracle")
        # remote verification fabric (remote.py): when attached, the
        # FIRST backend tier — remote pool, then local device, then
        # local host.  verify_batch returning None (no admissible
        # target / budget exhausted / failed audit) falls through to
        # the local tiers, so the remote fabric can only ever ADD
        # capacity, never block the chain.
        self.remote_pool = remote_pool
        # mesh scaling: the dispatch knee is PER-DEVICE, so an N-device
        # verification mesh should coalesce ~N× the sets before a
        # launch.  Auto-discovered from the backend's mesh plan unless
        # pinned by the caller; 1 everywhere the backend is unsharded.
        if mesh_devices is None:
            try:
                mesh_devices = getattr(self.verifier, "mesh_devices", 1)
            except Exception:  # noqa: BLE001 — duck-typed backends
                mesh_devices = 1
        self.mesh_devices = max(1, int(mesh_devices or 1))
        self.target_batch = int(target_batch) * self.mesh_devices
        self.max_batch = max(
            int(max_batch) * self.mesh_devices, self.target_batch
        )
        # adaptive dispatch threshold: walk target_batch toward the
        # measured fixed-cost/marginal-cost knee instead of pinning the
        # constructor constant.  Opt-in: latency-sensitive tests (and
        # custom targets) keep exact dispatch semantics by default.
        self._controller = None
        if adaptive_batch:
            if target_bounds is not None:
                lo, hi = (
                    target_bounds[0] * self.mesh_devices,
                    target_bounds[1] * self.mesh_devices,
                )
            else:
                lo, hi = (
                    min(DEFAULT_MIN_TARGET * self.mesh_devices,
                        self.target_batch),
                    self.max_batch,
                )
            self._controller = AdaptiveBatchController(
                self.target_batch, lo, hi
            )
        M.TARGET_BATCH.set(self.target_batch)
        M.MESH_DEVICES.set(self.mesh_devices)
        # queued-set depth at which sheddable classes start being
        # rejected (level 1); 4x this is level 2.  Default: several
        # device passes' worth of backlog.
        self.shed_watermark = (
            4 * self.max_batch if shed_watermark is None
            else int(shed_watermark)
        )
        self.max_delay = dict(DEFAULT_MAX_DELAY)
        if max_delay:
            self.max_delay.update(max_delay)
        self.queue_caps = dict(DEFAULT_QUEUE_CAPS)
        if queue_caps:
            self.queue_caps.update(queue_caps)

        self._queues = [deque() for _ in PRIORITY_CLASSES]
        self._queued_sets = 0
        # min-heap of (deadline, seq, request) maintained at submit;
        # entries whose request already dispatched are dropped lazily —
        # the nearest-deadline peek is O(log n), not a full-queue scan
        self._deadline_heap = []
        self._req_seq = itertools.count()
        self._cv = threading.Condition(locks.lock("verify_service.cv"))
        self._thread = None
        self._executor = None
        self._stopped = False
        # watchdog surface: the dispatcher stamps `heartbeat` every loop
        # pass; `restart_dispatcher` bumps the generation so a wedged
        # thread is superseded with the queues intact
        self.heartbeat = None
        # monotonic stamp while a dispatch pass is in flight (None when
        # idle): the watchdog judges an in-pass dispatcher against its
        # larger busy budget — a first-time XLA compile inside a device
        # batch can legitimately run for minutes
        self.pass_started = None
        self._gen = 0
        self.restarts = 0
        # work-section mutex: a restarted dispatcher must not run
        # _dispatch concurrently with a superseded thread wedged inside
        # one (the breaker, _device_event and the adaptive controller
        # are single-dispatcher state by contract) — the replacement
        # blocks until the old thread's in-flight batch resolves
        self._work_lock = locks.lock("verify_service.work")
        # lockset checker (LTPU_RACE_WITNESS=1; no-op otherwise): every
        # queue-state mutation must hold the cv lock.  `heartbeat` is
        # deliberately NOT registered — it is a single-writer monotonic
        # stamp read racily by the watchdog on purpose.
        for field in ("_queues", "_queued_sets", "_deadline_heap"):
            locks.guarded(self, field, "verify_service.cv")

        # admission warm gate: while a compile prewarm is in flight
        # (BeaconNode.start kicks one before the dispatcher may touch
        # the device), device work serves on the host path — a fresh
        # host must never pay a cold XLA compile against live deadlines.
        # Set by default: standalone services (tests, tools) admit
        # device work immediately, exactly as before.
        self._device_ready = threading.Event()
        self._device_ready.set()
        M.WARMTH.set(1.0)

        breaker_kw = (
            {} if breaker_probe_max is None
            else {"probe_max_sets": breaker_probe_max}
        )
        self.breaker = CircuitBreaker(
            breaker_threshold, breaker_cooldown, **breaker_kw
        )
        self._host_verifier = host_verifier
        self._device_event = False
        # hook into the backend seam: a device failure inside a verify
        # call (already degraded to host by the seam) feeds the breaker
        if hasattr(self.verifier, "on_device_fallback"):
            self.verifier.on_device_fallback = self._note_device_failure

        # bounded observability windows (tests and the service's own
        # stats read these; Prometheus carries the unbounded series)
        self.dispatched_batches = deque(maxlen=4096)   # sets per batch
        self.recent_waits = deque(maxlen=8192)         # queue wait seconds
        self.recent_overlaps = deque(maxlen=4096)      # pipelined prep overlap

    # ------------------------------------------------------------ compat

    @property
    def backend(self):
        return getattr(self.verifier, "backend", "host")

    def verify_signature_sets(self, sets, priority="attestation") -> bool:
        """Blocking drop-in for SignatureVerifier.verify_signature_sets:
        submit + wait.  Admission rejection or service shutdown degrade
        to a direct synchronous backend call — the compat path must never
        fail work that the bare seam would have verified.  The direct
        call still honors the circuit breaker: a dead device must not be
        re-probed per call exactly when the queues are overloaded."""
        sets = list(sets)
        if not sets or self._stopped:
            return self._degraded_verifier().verify_signature_sets(sets)
        try:
            fut = self.submit(sets, priority=priority)
        except LoadShedError:
            # shed means DROPPED, not "verify inline anyway" — fail
            # closed so the caller treats the work as unverified
            return False
        except QueueFullError:
            return self._degraded_verifier().verify_signature_sets(sets)
        try:
            return fut.result()
        except ServiceStopped:
            return self._degraded_verifier().verify_signature_sets(sets)

    # the ISSUE's `verify(...)` compat spelling
    verify = verify_signature_sets

    def verify_signature_sets_per_set(self, sets, priority="attestation") -> list:
        sets = list(sets)
        if not sets or self._stopped:
            return self._degraded_per_set(sets)
        try:
            fut = self.submit(sets, priority=priority, want_per_set=True)
        except LoadShedError:
            return ShedVerdicts([False] * len(sets))   # dropped, fail-closed
        except QueueFullError:
            return self._degraded_per_set(sets)
        try:
            return fut.result()
        except ServiceStopped:
            return self._degraded_per_set(sets)

    def _degraded_per_set(self, sets):
        """Overload/shutdown degrade for the per-set wrapper: batch-verify
        FIRST and only attribute per set on failure (the two-call pattern
        verify_with_verdicts uses against a bare seam).  Running N
        individual host verifications for a clean batch would multiply
        CPU cost exactly when the queues are already saturated."""
        v = self._degraded_verifier()
        if sets and v.verify_signature_sets(sets):
            return [True] * len(sets)
        return v.verify_signature_sets_per_set(sets)

    # ------------------------------------------------------------ submit

    def submit(self, sets, priority="attestation", deadline=None,
               want_per_set=False) -> VerifyFuture:
        """Queue `sets` for batched verification.

        `priority`: one of PRIORITY_CLASSES (or "light_client", an alias
        for the discovery class).  `deadline`: maximum seconds this
        request may wait for coalescing (default: the class window).
        Returns a VerifyFuture resolving to a bool (or a per-set verdict
        list when `want_per_set`).  Raises QueueFullError when the class
        queue is at capacity — callers either shed load or verify inline.
        """
        sets = list(sets)
        fut = VerifyFuture()
        if not sets:
            fut.set_result([] if want_per_set else
                           self.verifier.verify_signature_sets([]))
            return fut
        cls = normalize_priority(priority)
        idx = _CLASS_INDEX[cls]
        now = time.monotonic()
        window = self.max_delay[cls] if deadline is None else float(deadline)
        req = _Request(sets, fut, cls, now + window, now, want_per_set,
                       trace=tracing.current_trace())
        shed_at = SHED_LEVEL.get(cls)
        if shed_at is not None:
            with self._cv:
                shed_level, shed_queued = (
                    self._overload_level_locked(), self._queued_sets
                )
            # decided under the lock, reported OUTSIDE it: the log
            # handler does console/file I/O that must never stall the
            # lock every submitter and the dispatcher share
            if shed_level >= shed_at:
                M.SHED.with_labels(cls).inc()
                log.warning_rate_limited(
                    f"shed:{cls}", 1.0,
                    "shedding %s verification work under overload",
                    cls, overload_level=shed_level,
                    breaker_state=self.breaker.state,
                    queued_sets=shed_queued,
                )
                raise LoadShedError(
                    f"{cls} work shed under overload (level {shed_level})"
                )
        with self._cv:
            if self._stopping():
                fut.set_error(ServiceStopped("verification service stopped"))
                return fut
            if len(self._queues[idx]) >= self.queue_caps[cls]:
                M.ADMISSION_REJECTED.inc()
                raise QueueFullError(f"{cls} queue at capacity")
            locks.access(self, "_queues", "write")
            locks.access(self, "_deadline_heap", "write")
            locks.access(self, "_queued_sets", "write")
            self._queues[idx].append(req)
            heapq.heappush(
                self._deadline_heap,
                (req.deadline, next(self._req_seq), req),
            )
            self._queued_sets += len(sets)
            M.SETS_SUBMITTED.inc(len(sets))
            M.queue_depth_gauge(cls).set(len(self._queues[idx]))
            self._ensure_running_locked()
            self._cv.notify_all()
        return fut

    def _overload_level_locked(self):
        """Shed policy input (read-only breaker peek, caller thread —
        same contract as _degraded_verifier): 0 = healthy; 1 = the
        device circuit is OPEN (host path is paying for everything) or
        the backlog crossed the shed watermark; 2 = backlog far past
        the watermark (shed attestations too; blocks/aggregates never)."""
        level = 0
        if self.breaker.state == OPEN:
            level = 1
        if self._queued_sets >= self.shed_watermark:
            level = max(level, 1)
        if self._queued_sets >= 4 * self.shed_watermark:
            level = 2
        return level

    # --------------------------------------------------------- lifecycle

    def start(self, executor):
        """Run the dispatcher under a supervised TaskExecutor (node
        wiring).  Idempotent; a lazily-started daemon thread keeps
        running if one already exists."""
        with self._cv:
            if self._thread is not None or self._executor is not None:
                return self
            self._executor = executor
        executor.spawn(self._run_supervised, "verify_service")
        return self

    def stop(self):
        with self._cv:
            self._stopped = True
            # the dispatcher may already be gone (executor shutdown
            # exits the loop without setting _stopped) — fail whatever
            # is queued HERE so no submitter blocks forever; running
            # this twice is harmless
            self._fail_pending_locked()
            self._cv.notify_all()

    def _ensure_running_locked(self):
        if self._thread is None and self._executor is None:
            t = threading.Thread(
                target=self._loop, name="verify_service", daemon=True
            )
            self._thread = t
            t.start()

    def _run_supervised(self, executor):
        self._loop()

    def _stopping(self):
        return self._stopped or (
            self._executor is not None and self._executor.shutting_down
        )

    # -------------------------------------------------------- dispatcher

    def _loop(self):
        with self._cv:
            gen = self._gen
        while True:
            self.heartbeat = time.monotonic()
            try:
                # chaos seam: `delay` wedges the dispatcher HERE — before
                # any batch is popped — so a watchdog restart loses
                # nothing; `error` just retries the loop
                failpoints.hit("verify.dispatch")
            except failpoints.FailpointError:
                # retry the loop; the pause keeps an error(1.0)
                # injection from busy-spinning the dispatcher, and the
                # generation check keeps a superseded thread from
                # spinning forever (and stamping the shared heartbeat)
                # without ever reaching the in-lock check
                time.sleep(0.005)
                if self._gen != gen:
                    return
                if not self._stopping():
                    continue
                # stopping while the fault is armed: fall through to
                # the cv block, which fails pending work and exits —
                # otherwise stop() could never terminate this loop
            with self._cv:
                while True:
                    if self._gen != gen:
                        # superseded by a watchdog restart: a fresh
                        # dispatcher owns the queues now — exit without
                        # failing pending work
                        return
                    if self._stopping():
                        # mark stopped so post-shutdown submits take the
                        # compat degrade path instead of queueing onto a
                        # dispatcher that no longer exists
                        self._stopped = True
                        self._fail_pending_locked()
                        return
                    self.heartbeat = time.monotonic()
                    wait = self._dispatch_wait_locked()
                    if wait is not None and wait <= 0:
                        break
                    # cap the wait so executor shutdown (no cv notify) is
                    # noticed promptly
                    self._cv.wait(0.25 if wait is None else min(wait, 0.25))
            # work is ready: take the work section BEFORE popping the
            # batch, so a replacement dispatcher blocked behind a
            # wedged-in-dispatch predecessor leaves the work QUEUED
            # (blocking after the pop would strand popped futures).
            # The wait does NOT stamp the heartbeat: while a predecessor
            # is mid-pass, `pass_started` keeps the watchdog on the busy
            # budget — a pass hung PAST that budget must go visibly
            # stale and draw another dump/restart, not read as healthy.
            while not self._work_lock.acquire(timeout=0.25):
                if self._gen != gen:
                    return
                if self._stopping():
                    # the canonical exit, sans work lock: fail pending
                    # under the cv so no submitter blocks forever
                    with self._cv:
                        self._stopped = True
                        self._fail_pending_locked()
                    return
            self.pass_started = time.monotonic()
            try:
                with self._cv:
                    if self._gen != gen or self._stopping():
                        continue   # the loop-top cv block exits canonically
                    batch = self._form_batch_locked()
                if batch:
                    self._dispatch(batch)
            finally:
                self.pass_started = None
                self._work_lock.release()

    def restart_dispatcher(self):
        """Watchdog recovery hook: supersede a wedged dispatcher with a
        fresh thread, QUEUES INTACT.  The old thread observes the
        generation bump at its next lock acquisition and exits without
        failing pending work; queued requests drain under the new one.
        The replacement runs under the SAME supervision as the original
        — executor.spawn when the service was started(executor), so a
        later crash still trips the panic-catcher instead of silently
        hanging every caller.  Returns False when the service (or its
        executor) is stopped: nothing to recover."""
        with self._cv:
            if self._stopped:
                return False
            executor = self._executor
            if executor is not None and executor.shutting_down:
                return False
            self._gen += 1
            self.restarts += 1
            gen, queued = self._gen, self._queued_sets
            if executor is None:
                t = threading.Thread(
                    target=self._loop, name="verify_service", daemon=True
                )
                self._thread = t
                t.start()
            self._cv.notify_all()
        if executor is not None:
            executor.spawn(self._run_supervised, "verify_service")
        log.warning(
            "verification dispatcher restarted (generation %d)", gen,
            queued_sets=queued,
        )
        return True

    def _dispatch_wait_locked(self):
        """None = no work; <=0 = dispatch now; >0 = seconds until the
        nearest queued deadline.  The nearest deadline comes from a
        min-heap maintained at submit time (an explicit short `deadline`
        can sit behind a default-window request in the same class, so
        queue heads alone are not enough) — an O(log n) peek with lazy
        deletion of dispatched entries, where the old full scan was
        O(total queued requests) per dispatcher tick."""
        locks.access(self, "_queued_sets", "read")
        if self._queued_sets == 0:
            # every heap entry is necessarily stale now — drop them so an
            # idle service doesn't retain resolved requests (and their
            # signature sets) until the next submit
            locks.access(self, "_deadline_heap", "write")
            self._deadline_heap.clear()
            return None
        # prune BEFORE the target-batch early return: under sustained
        # saturating load that branch fires every tick, and skipping the
        # pops here would let dispatched entries accumulate unboundedly
        self._prune_deadline_heap_locked()
        if self._queued_sets >= self.target_batch:
            return 0.0
        heap = self._deadline_heap
        if not heap:                       # defensive; queued_sets > 0
            return 0.0                     # implies a live entry exists
        return heap[0][0] - time.monotonic()

    def _prune_deadline_heap_locked(self):
        """Lazy deletion: pop dispatched entries off the top; compact the
        whole heap when stale entries buried behind a live minimum come
        to dominate (requests dispatch in priority order, not deadline
        order, so burial is possible)."""
        locks.access(self, "_deadline_heap", "write")
        heap = self._deadline_heap
        while heap and heap[0][2].dispatched:
            heapq.heappop(heap)
        live = sum(len(q) for q in self._queues)
        if len(heap) > 64 and len(heap) > 2 * live:
            heap = [e for e in heap if not e[2].dispatched]
            heapq.heapify(heap)
            self._deadline_heap = heap

    def _form_batch_locked(self):
        """Pop requests in priority order up to max_batch sets.  Requests
        are atomic (never split); an oversized request dispatches alone."""
        locks.access(self, "_queues", "write")
        locks.access(self, "_queued_sets", "write")
        reqs = []
        n = 0
        for idx, cls in enumerate(PRIORITY_CLASSES):
            q = self._queues[idx]
            while q:
                k = len(q[0].sets)
                if reqs and n + k > self.max_batch:
                    break
                req = q.popleft()
                req.dispatched = True      # stale-marks its heap entry
                reqs.append(req)
                n += k
            M.queue_depth_gauge(cls).set(len(q))
            if reqs and n >= self.max_batch:
                break
        self._queued_sets -= n
        return reqs

    def _fail_pending_locked(self):
        locks.access(self, "_queues", "write")
        locks.access(self, "_deadline_heap", "write")
        locks.access(self, "_queued_sets", "write")
        err = ServiceStopped("verification service stopped")
        for idx, cls in enumerate(PRIORITY_CLASSES):
            q = self._queues[idx]
            while q:
                req = q.popleft()
                req.dispatched = True
                req.future.set_error(err)
            M.queue_depth_gauge(cls).set(0)
        self._deadline_heap.clear()
        self._queued_sets = 0

    def _note_device_failure(self, exc=None):
        # called from inside the backend seam on a device→host fallback
        self._device_event = True

    def _host(self):
        if self._host_verifier is None:
            self._host_verifier = SignatureVerifier("native")
        return self._host_verifier

    # ------------------------------------------------- compile warm gate

    @property
    def device_ready(self):
        """False while a compile prewarm gates device admission."""
        return self._device_ready.is_set()

    def begin_warmup(self):
        """Close the device admission gate: until `mark_device_ready`,
        every dispatched batch runs on the host fallback path (the same
        degrade seam the circuit breaker pins), so prewarm compiles and
        live traffic never contend for the device."""
        self._device_ready.clear()
        M.WARMTH.set(0.0)

    def set_warmth(self, frac):
        """Prewarm progress callback (0..1) — drives the
        `verify_service_warmth` gauge; does NOT open the gate."""
        M.WARMTH.set(round(min(max(float(frac), 0.0), 1.0), 4))

    def mark_device_ready(self):
        """Open the admission gate (idempotent): the canonical kernel
        menu is loaded — or prewarm failed and the first real batch pays
        the compile under the watchdog's busy budget."""
        self._device_ready.set()
        M.WARMTH.set(1.0)
        with self._cv:
            self._cv.notify_all()

    def _active_verifier(self):
        """Dispatcher-side: the warm gate, then the breaker, decide
        whether this batch tries the device (allow_device may transition
        OPEN -> HALF_OPEN; only the dispatcher thread calls it —
        circuit.py's contract)."""
        if self.backend != "tpu":
            return self.verifier
        if not self._device_ready.is_set():
            return self._host()
        if self.breaker.allow_device():
            return self.verifier
        return self._host()

    def _degraded_verifier(self):
        """Caller-thread-side (compat wrappers on overflow/shutdown): a
        READ-ONLY breaker/gate check — a non-CLOSED breaker or a cold
        warm gate means the host path, without racing the dispatcher's
        probe state machine."""
        if self.backend != "tpu":
            return self.verifier
        if not self._device_ready.is_set():
            return self._host()
        if self.breaker.state == 0:  # CLOSED
            return self.verifier
        return self._host()

    def _resolve(self, req, value=None, error=None):
        """Complete one request's future, observing the per-class
        submit->resolve delay (the attestation/aggregate analogue of the
        BlockTimesCache's per-stage block delays)."""
        M.SUBMIT_RESOLVE.with_labels(req.cls).observe(
            time.monotonic() - req.submitted
        )
        if error is not None:
            req.future.set_error(error)
        else:
            req.future.set_result(value)

    def _attach_spans(self, reqs, t_dispatch, t_k0, t_k1, attrs):
        """Append the dispatcher's stage spans to each submitter trace
        (the cross-thread handoff: the request captured its submitter's
        current trace; the dispatcher reports where the time went)."""
        for r in reqs:
            tr = r.trace
            if tr is None:
                continue
            tr.add_span("queue_wait", r.submitted, t_dispatch, cls=r.cls)
            tr.add_span("batch", t_dispatch, t_k0, **attrs)
            tr.add_span("kernel", t_k0, t_k1, backend=attrs.get("backend"))

    # ------------------------------------------- host-prep/device pipeline

    def _run_pipeline(self, chunks, prepare, execute):
        """Two-deep software pipeline: a batch-scoped prep thread stages
        chunk N+1 while this (dispatcher) thread executes chunk N on the
        device — a multi-chunk batch's wall time approaches
        max(prep, device) instead of their sum.  The depth-1 handoff
        queue is the backpressure: at most one staged chunk waits while
        one preps and one executes.

        The prep thread is BATCH-SCOPED by design: it exits after its
        last chunk (or its first error), so there is no worker lifecycle
        to coordinate with service shutdown — stop() during a pipelined
        dispatch lets this method finish normally (draining every staged
        chunk in the finally) and the running batch's futures resolve;
        only still-queued requests fail with ServiceStopped.

        Spans on the batch trace: `prep` per chunk staged (prep thread,
        drained chunks included) and `prep_wait` around each wait for a
        staged chunk (dispatcher; `drain` when that chunk will not be
        launched)."""
        out_q = Queue(maxsize=1)
        # thread-local current traces do not cross into the prep thread:
        # it is handed the batch trace here
        trace = tracing.current_trace()

        def produce():
            with tracing.use(trace):
                for i, chunk in enumerate(chunks):
                    t0 = time.monotonic()
                    try:
                        with tracing.span("prep", chunk=i, sets=len(chunk)):
                            # chaos seam: an injected prep fault aborts
                            # the pipeline; _verify_batch falls back to
                            # the plain path, so the batch still
                            # verifies correctly
                            failpoints.hit("verify.prep")
                            item = prepare(chunk)
                    except BaseException as e:   # delivered, not raised:
                        out_q.put((t0, time.monotonic(), e))
                        return       # the dispatcher owns error handling
                    out_q.put((t0, time.monotonic(), item))

        t = threading.Thread(
            target=produce, name="verify_service_prep", daemon=True
        )
        t.start()
        ok = True
        consumed = 0
        overlaps = []
        prev_exec = None
        try:
            for _ in range(len(chunks)):
                with tracing.span("prep_wait", trace, chunk=consumed,
                                  drain=not ok):
                    p0, p1, prepared = out_q.get()
                consumed += 1
                if isinstance(prepared, BaseException):
                    raise prepared
                if not ok:
                    # verdict already settled False: drain the remaining
                    # preps without launching kernels (the serial chunk
                    # loop's early-exit cost profile)
                    continue
                # how much of THIS chunk's prep ran during the previous
                # chunk's device window
                ratio = 0.0
                if prev_exec is not None and p1 > p0:
                    shared = min(p1, prev_exec[1]) - max(p0, prev_exec[0])
                    ratio = max(0.0, shared) / (p1 - p0)
                    overlaps.append(ratio)
                e0 = time.monotonic()
                ok = execute(prepared, overlap_ratio=ratio) and ok
                prev_exec = (e0, time.monotonic())
        finally:
            # if execute raised, the producer may be blocked on the full
            # handoff queue: drain until it has delivered every chunk (or
            # exited early on its own error)
            while consumed < len(chunks):
                with tracing.span("prep_wait", trace, chunk=consumed,
                                  drain=True):
                    staged = _next_staged(out_q, t)
                if staged is None:
                    break
                consumed += 1
                if isinstance(staged[2], BaseException):
                    break       # producer stopped after delivering this
        if overlaps:
            mean = sum(overlaps) / len(overlaps)
            self.recent_overlaps.extend(overlaps)
            M.OVERLAP_RATIO.set(round(mean, 4))
        return ok

    def _verify_batch(self, v, all_sets):
        """One backend pass for a formed batch: the two-stage pipeline
        when the backend exposes a prep/execute split AND the batch spans
        multiple chunks; the plain call otherwise.  A pipeline failure
        falls back to the plain call, whose internal degrade chain owns
        device-failure semantics (breaker events included)."""
        plan_fn = getattr(v, "plan_pipeline", None)
        plan = None
        if plan_fn is not None:
            try:
                plan = plan_fn(all_sets)
            except Exception:
                plan = None
        if plan:
            try:
                return self._run_pipeline(*plan)
            except Exception as e:
                log.warning(
                    "pipelined dispatch failed (%s); plain path",
                    str(e)[:200],
                )
        return v.verify_signature_sets(all_sets)

    def _verify_probe_split(self, all_sets, cap):
        """HALF_OPEN dispatch for a batch larger than the probe budget:
        only the first `cap` sets risk the device (the bounded probe);
        the remainder runs on the host path in the same pass.  The
        breaker judges the probe alone (`_device_event` is only set by
        the device verifier's fallback hook), and the batch verdict is
        the AND of both halves — verdict semantics are unchanged."""
        probe, rest = all_sets[:cap], all_sets[cap:]
        ok = self.verifier.verify_signature_sets(probe)
        if ok and rest:
            # a settled-False probe skips the host pass: the verdict
            # cannot change, and a failing batch pays the per-set
            # attribution pass over every set right after anyway
            ok = self._host().verify_signature_sets(rest)
        return ok

    def attach_remote(self, pool):
        """Attach a RemoteVerifierPool as the first backend tier (node
        wiring; also usable live — the dispatcher reads the attribute
        fresh each batch)."""
        self.remote_pool = pool
        return self

    def _try_remote(self, reqs, all_sets, now):
        """Offer one formed batch to the remote tier.  True = the pool
        returned (audited) verdicts and every request is resolved; False
        = the local tiers take the batch — the pool's bounded budget
        guarantees this returns promptly either way."""
        pool = self.remote_pool
        # the most urgent class present rides the whole coalesced batch
        cls = min(reqs, key=lambda r: _CLASS_INDEX[r.cls]).cls
        attrs = {
            "sets": len(all_sets),
            "requests": len(reqs),
            "coalesced": len(reqs) > 1,
            "classes": sorted({r.cls for r in reqs}),
            "backend": "remote",
        }
        # the batch trace is created BEFORE the pool call so its id can
        # ride the VERIFY_REQ frames: serving nodes open child traces
        # under it and ship their span timings back for stitching.  On a
        # remote miss the unfinished trace is simply dropped (finish()
        # publishes; we never call it) — the local path starts its own.
        bt = tracing.start_trace("verify_batch", **attrs)
        report = {}
        t0 = time.monotonic()
        try:
            verdicts = pool.verify_batch(
                all_sets, priority=cls,
                trace_ctx=(bt.trace_id, tracing.node_id()),
                report=report,
            )
        except Exception:
            log.exception(
                "remote verify tier failed hard; local tiers take the batch"
            )
            return False
        if verdicts is None:
            return False
        t1 = time.monotonic()
        M.REMOTE_TIER.set(0)
        bt.add_span("queue_wait", min(r.submitted for r in reqs), now)
        bt.add_span("kernel", t0, t1, backend="remote")
        self._stitch_remote_spans(bt, reqs, report)
        bt.finish(
            ok=all(verdicts),
            winner=report.get("winner"),
            hedged_duplicates=report.get("duplicates", 0),
        )
        self._attach_spans(reqs, now, t0, t1, attrs)
        pos = 0
        for r in reqs:
            mine = list(verdicts[pos:pos + len(r.sets)])
            pos += len(r.sets)
            self._resolve(r, mine if r.per_set else all(mine))
        return True

    def _stitch_remote_spans(self, bt, reqs, report):
        """Merge the pool's per-call records — the winning call AND its
        hedged duplicates, each tagged with its target and hedge index —
        into the batch trace, rebasing each server span at that call's
        local send time (cross-node clock skew rides on the assumption
        that the RPC round trip bounds it; good enough for attribution).
        Submitter traces get the same spans, so one /lighthouse/tracing
        row reads end-to-end: client queue_wait -> rpc -> server
        serve_decode/queue_wait/batch/kernel -> audit."""
        calls = report.get("calls") or []
        stitched_any = False
        for call in calls:
            tag = {
                "target": call.get("target"),
                "hedge": call.get("hedge", 0),
                "duplicate": bool(call.get("duplicate")),
            }
            if call.get("error"):
                bt.add_span(
                    "remote.rpc", call["t0"], call["t1"],
                    error=call["error"], **tag,
                )
                continue
            bt.add_span("remote.rpc", call["t0"], call["t1"], **tag)
            server = call.get("server")
            if not server:
                continue
            stitched_any = True
            base = call["t0"]
            for name, start_us, dur_us in server.get("spans", ()):
                s = base + start_us / 1e6
                bt.add_span(
                    f"remote.{name}", s, s + dur_us / 1e6,
                    server_trace=server.get("trace_id"), **tag,
                )
                M.TRACE_REMOTE_SPANS.with_labels(
                    str(call.get("target"))
                ).inc()
        audit = report.get("audit")
        if audit is not None:
            bt.add_span("audit", audit[0], audit[1], backend="host")
        if stitched_any:
            M.TRACE_STITCHED.inc()
        # the same stitched view lands on each submitter's trace, so a
        # request-level trace also reads end-to-end
        for r in reqs:
            if r.trace is None:
                continue
            for name, s, e, a in bt.snapshot_spans():
                if name.startswith("remote.") or name == "audit":
                    r.trace.add_span(name, s, e, **a)

    def _dispatch(self, reqs):
        now = time.monotonic()
        all_sets = []
        for r in reqs:
            wait = now - r.submitted
            M.QUEUE_WAIT.with_labels(r.cls).observe(wait)
            self.recent_waits.append(wait)
            all_sets.extend(r.sets)
        M.BATCH_SETS.observe(len(all_sets))
        M.BATCHES_DISPATCHED.inc()
        if len(reqs) > 1:
            M.COALESCED_BATCHES.inc()
        self.dispatched_batches.append(len(all_sets))

        # remote tier first: a healthy verifier pool takes the batch off
        # this host entirely (verdicts already audited by the pool)
        if self.remote_pool is not None and self._try_remote(
            reqs, all_sets, now
        ):
            return

        v = self._active_verifier()
        device_attempt = v is self.verifier and self.backend == "tpu"
        if self.remote_pool is not None:
            M.REMOTE_TIER.set(1 if device_attempt else 2)
        # bounded half-open probe (circuit.py): when the breaker is
        # probing, cap the device's exposure to probe_max_sets and run
        # the rest of the batch on the host
        probe_cap = self.breaker.probe_cap() if device_attempt else None
        batch_attrs = {
            "sets": len(all_sets),
            "requests": len(reqs),
            "coalesced": len(reqs) > 1,
            "classes": sorted({r.cls for r in reqs}),
            "backend": getattr(v, "backend", "host"),
        }
        # the service's own trace of this batch: queue wait (oldest
        # submit), batch bookkeeping, and the kernel call — with any
        # device-level spans (pad ratio, chunking) the crypto backend
        # attaches while this trace is current
        bt = tracing.start_trace("verify_batch", **batch_attrs)
        bt.add_span("queue_wait", min(r.submitted for r in reqs), now)
        self._device_event = False
        t_k0 = time.monotonic()
        bt.add_span("batch", now, t_k0, **batch_attrs)
        try:
            # `kernel` is recorded below from t_k0/t_k1; the region makes
            # it the parent of the spans the backend opens meanwhile
            with tracing.use(bt), tracing.region("kernel", bt):
                if probe_cap is not None and len(all_sets) > probe_cap:
                    ok = self._verify_probe_split(all_sets, probe_cap)
                else:
                    ok = self._verify_batch(v, all_sets)
        except Exception as e:
            # the seam's internal fallback chain should make this
            # unreachable; fail the batch's futures rather than hang them
            log.exception("verification batch failed hard")
            t_k1 = time.monotonic()
            bt.add_span("kernel", t_k0, t_k1, error=str(e)[:200])
            bt.finish(ok=False)
            if device_attempt:
                self.breaker.record_failure()
            self._attach_spans(reqs, now, t_k0, t_k1, batch_attrs)
            for r in reqs:
                self._resolve(r, error=e)
            return
        t_k1 = time.monotonic()
        bt.add_span("kernel", t_k0, t_k1, backend=batch_attrs["backend"])
        if self._controller is not None:
            # feed the knee controller the measured (sets, kernel time)
            # sample; target_batch is a plain int write — the dispatcher
            # is the only writer, readers see old-or-new (both valid)
            self.target_batch = self._controller.update(
                len(all_sets), t_k1 - t_k0
            )
            M.TARGET_BATCH.set(self.target_batch)
        if device_attempt:
            if self._device_event:
                self.breaker.record_failure()
            else:
                self.breaker.record_success()

        if ok:
            bt.finish(ok=True)
            self._attach_spans(reqs, now, t_k0, t_k1, batch_attrs)
            for r in reqs:
                self._resolve(r, [True] * len(r.sets) if r.per_set else True)
            return

        if len(reqs) == 1 and not reqs[0].per_set:
            # single submitter wanting a bool: the batch verdict IS its
            # verdict — no attribution pass needed (the caller runs its
            # own per-set fallback, same as against the bare seam)
            bt.finish(ok=False)
            self._attach_spans(reqs, now, t_k0, t_k1, batch_attrs)
            self._resolve(reqs[0], False)
            return

        # poisoned multi-caller batch: ONE per-set pass attributes the
        # failure; innocent submitters still succeed
        M.POISONED_BATCHES.inc()
        try:
            with tracing.use(bt):
                # emitted while the batch trace is current: the record's
                # trace_id joins this WARN to the /lighthouse/tracing
                # verify_batch entry that carries the stage spans
                log.warning(
                    "poisoned verification batch: %d sets from %d "
                    "submitter(s); running attribution pass",
                    len(all_sets), len(reqs),
                    classes=batch_attrs["classes"],
                    backend=batch_attrs["backend"],
                )
                # if the device failed this very batch (breaker now
                # OPEN), attribute on the host path instead of paying a
                # second hang against a dead device
                av = (
                    self._host()
                    if device_attempt and self.breaker.state == OPEN
                    else v
                )
                with bt.span("attribution"):
                    verdicts = av.verify_signature_sets_per_set(all_sets)
        except Exception as e:
            log.exception("per-set attribution pass failed hard")
            bt.finish(ok=False)
            self._attach_spans(reqs, now, t_k0, t_k1, batch_attrs)
            for r in reqs:
                self._resolve(r, error=e)
            return
        bt.finish(ok=False, poisoned=True)
        self._attach_spans(reqs, now, t_k0, t_k1, batch_attrs)
        pos = 0
        for r in reqs:
            mine = list(verdicts[pos:pos + len(r.sets)])
            pos += len(r.sets)
            self._resolve(r, mine if r.per_set else all(mine))

    # ----------------------------------------------------------- insight

    def stats(self):
        """Aggregates over the recent observability windows."""
        batches = list(self.dispatched_batches)
        waits = sorted(self.recent_waits)

        def pct(p):
            return waits[min(int(p * len(waits)), len(waits) - 1)] if waits else 0.0

        overlaps = list(self.recent_overlaps)
        remote = {}
        if self.remote_pool is not None:
            snap = self.remote_pool.snapshot()
            remote = {
                "remote_jobs_remote": snap["jobs_remote"],
                "remote_jobs_local": snap["jobs_local"],
                "remote_hedges": snap["hedges"],
                "remote_audit_catches": snap["audit_catches"],
            }
        return {
            **remote,
            "batches": len(batches),
            "sets": sum(batches),
            "mean_batch_sets": (sum(batches) / len(batches)) if batches else 0.0,
            "max_batch_sets": max(batches) if batches else 0,
            "queue_wait_p50_ms": pct(0.50) * 1e3,
            "queue_wait_p99_ms": pct(0.99) * 1e3,
            "circuit_state": self.breaker.state,
            "device_ready": self.device_ready,
            "target_batch": self.target_batch,
            "mesh_devices": self.mesh_devices,
            "dispatcher_restarts": self.restarts,
            "overlap_ratio_mean": (
                round(sum(overlaps) / len(overlaps), 4) if overlaps else 0.0
            ),
        }
