"""Device circuit breaker: consecutive-failure trip, cooldown, and a
BOUNDED half-open probe.

Extends the per-call device→native→oracle fallback chain in
crypto/backend.py with process-level health memory: one failed device call
already degrades that single call, but every subsequent call would still
pay the device attempt (a hang-then-timeout each time).  The breaker
counts consecutive device failures and pins the service to the host path
for a cooldown.  When the cooldown elapses the breaker goes HALF_OPEN —
and instead of blindly re-opening the device to whatever batch happens
to be queued (a 512-set batch against a still-dead device pays the whole
hang again), it exposes `probe_cap()`: the dispatcher sends at most
`probe_max_sets` sets to the device as the probe and routes the
remainder to the host.  Only a SUCCESSFUL probe restores CLOSED; a
failed probe re-opens immediately for another cooldown.

State transitions are observable: the `verify_service_breaker_state`
gauge (0=closed 1=open 2=half_open; `verify_service_circuit_state` is
the pre-PR-5 alias) plus a WARN on trip and an INFO on probe/restore
through the component logger.
"""

import time

from ..utils.logging import get_logger
from . import metrics as M

log = get_logger("verify_service")

CLOSED = 0      # device healthy, dispatch normally
OPEN = 1        # pinned to host path until cooldown elapses
HALF_OPEN = 2   # cooldown over: one bounded probe batch decides

_STATE_NAMES = {CLOSED: "closed", OPEN: "open", HALF_OPEN: "half_open"}

DEFAULT_PROBE_MAX_SETS = 64


class CircuitBreaker:
    """Single-dispatcher-thread breaker (no internal locking: only the
    service's dispatcher loop drives transitions; callers may READ
    `state`)."""

    def __init__(self, threshold=3, cooldown=30.0, clock=time.monotonic,
                 probe_max_sets=DEFAULT_PROBE_MAX_SETS,
                 state_gauge=None, name="device"):
        self.threshold = max(1, int(threshold))
        self.cooldown = float(cooldown)
        # force_open(cooldown=...) may lengthen `cooldown` for one exile;
        # a success restores this base so a once-quarantined but now-
        # honest target doesn't pay the long sit-out on every later trip
        self._base_cooldown = self.cooldown
        self.probe_max_sets = max(1, int(probe_max_sets))
        self._clock = clock
        self.state = CLOSED
        self.consecutive_failures = 0
        self.opened_at = None
        self.trips = 0
        self.name = name
        # a per-instance gauge (e.g. a verify_remote_breaker_state{target}
        # child) replaces the process-wide device-breaker families — a
        # remote target's breaker must not clobber the device gauges or
        # inflate the device trip counter
        self._state_gauge = state_gauge
        self._device_metrics = state_gauge is None
        # fleet incident hook: called with this breaker on each CLOSED/
        # HALF_OPEN -> OPEN transition (never on open-to-open refreshes)
        self.on_trip = None
        self._write_state_metric(CLOSED)

    def _write_state_metric(self, state):
        if self._device_metrics:
            M.CIRCUIT_STATE.set(state)
            M.BREAKER_STATE.set(state)
        else:
            self._state_gauge.set(state)

    def _set_state(self, state):
        prev, self.state = self.state, state
        self._write_state_metric(state)
        if state == prev:
            return
        if state == OPEN:
            log.warning(
                "%s circuit breaker tripped %s -> open; pinning "
                "verification to the fallback path",
                self.name, _STATE_NAMES[prev],
                consecutive_failures=self.consecutive_failures,
                cooldown_s=self.cooldown,
            )
            hook = self.on_trip
            if hook is not None:
                try:
                    hook(self)
                except Exception:  # noqa: BLE001 — a trip hook must not
                    log.exception(  # break the dispatcher loop
                        "%s breaker on_trip hook failed", self.name)
        elif state == HALF_OPEN:
            log.info(
                "%s circuit breaker half-open: probing with one "
                "bounded batch",
                self.name,
                probe_max_sets=self.probe_max_sets,
            )
        else:
            log.info(
                "%s circuit breaker restored %s -> closed after a "
                "successful probe batch",
                self.name, _STATE_NAMES[prev],
            )

    def allow_device(self) -> bool:
        """Should the next batch try the device path?"""
        if self.state == CLOSED:
            return True
        if self.state == OPEN:
            if self._clock() - self.opened_at >= self.cooldown:
                self._set_state(HALF_OPEN)
                return True
            return False
        return True  # HALF_OPEN: the probe batch is in flight

    def probe_cap(self):
        """Bounded half-open probe: when HALF_OPEN, at most this many
        sets may ride the device attempt (the dispatcher routes the
        rest of the batch to the host); None in every other state."""
        return self.probe_max_sets if self.state == HALF_OPEN else None

    def record_failure(self):
        self.consecutive_failures += 1
        if self.state == HALF_OPEN or self.consecutive_failures >= self.threshold:
            if self.state != OPEN:
                self.trips += 1
                if self._device_metrics:
                    M.CIRCUIT_TRIPS.inc()
            self.opened_at = self._clock()
            self._set_state(OPEN)

    def force_open(self, cooldown=None):
        """Administrative trip: pin OPEN immediately, regardless of the
        failure count — the audit-quarantine path for a remote target
        caught returning wrong verdicts.  An optional `cooldown`
        override lengthens the sit-out before any half-open re-probe
        (a byzantine verifier earns a longer exile than a flaky one);
        it lasts until the next successful probe, which restores the
        constructor's base cooldown for ordinary trips."""
        if cooldown is not None:
            self.cooldown = float(cooldown)
        if self.state != OPEN:
            self.trips += 1
            if self._device_metrics:
                M.CIRCUIT_TRIPS.inc()
        self.opened_at = self._clock()
        self.consecutive_failures = max(
            self.consecutive_failures, self.threshold
        )
        self._set_state(OPEN)

    def record_success(self):
        self.consecutive_failures = 0
        self.cooldown = self._base_cooldown
        if self.state != CLOSED:
            self._set_state(CLOSED)
