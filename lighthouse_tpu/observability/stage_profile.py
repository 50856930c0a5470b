"""Epoch-stage profiler: per-stage wall attribution for the
state-transition tail, zero-cost when disabled.

With ``LTPU_STATE_PROFILE`` unset (production default) ``timer()``
returns one shared null singleton whose ``stage()`` hands back a
reusable no-op context manager — no registry lookup, no clock read, no
allocation on the hot path (the ``utils/locks.py`` witness idiom: the
mode is decided once, an unarmed process pays a cached module-global
check and nothing else).  With ``LTPU_STATE_PROFILE=1`` every
instrumented site in ``state_processing`` records into a process-wide
``StageProfileRegistry`` keyed (fork, stage, validator-count bucket)
with the same EWMA + log-bucket histogram accumulation as the PR-12
kernel-profile registry (``crypto/tpu/profile.py``), persisted beside
it as ``state_profile.json``.

Stages covered (the ROADMAP epoch-on-device work plans over exactly
these rows): justification/finalization, rewards/penalties, registry
updates, slashings, final updates, participation-flag updates,
inactivity updates, sync-committee updates, historical summaries, the
per-slot SSZ hashing in ``process_slot``, per-block processing in the
replayer, and committee-cache builds — plus an ``epoch_total`` parent
row so stage totality (stages sum ~= epoch wall) is checkable from the
registry alone.

Served at ``GET /lighthouse/state-profile``; summarized by
``tools/profile_report.py --state``.
"""

import json
import os
import threading
import time

from ..utils import locks, metrics
from ..utils.logging import get_logger

log = get_logger("observability.stage_profile")

# stage walls span ~10us minimal-preset stages to multi-second
# 1M-validator rewards passes: log-spaced ms edges like BUCKETS_MS in
# crypto/tpu/profile.py, shifted two decades down
BUCKETS_MS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
              25.0, 50.0, 100.0, 250.0, 500.0, 1000.0, 2500.0)
EWMA_ALPHA = 0.2
_SAVE_INTERVAL_S = 5.0
_SCHEMA = 1

STAGE_CALLS = metrics.counter(
    "state_profile_stage_calls_total",
    "Instrumented state-transition stage executions recorded by the "
    "epoch-stage profiler, by fork and stage",
    labels=("fork", "stage"),
)
STAGE_EWMA = metrics.gauge(
    "state_profile_stage_ms",
    "EWMA wall time (ms) of recent executions of each state-transition "
    "stage, by fork and stage",
    labels=("fork", "stage"),
)

_ENABLED = None


def enabled():
    """Profiler armed?  Cached after the first read so the disabled hot
    path is one module-global check (the ``race_enabled()`` idiom);
    tests that flip the env call ``reset()``."""
    global _ENABLED
    if _ENABLED is None:
        _ENABLED = os.environ.get(
            "LTPU_STATE_PROFILE", "") not in ("", "0")
    return _ENABLED


def reset():
    """Re-read the env gate (tests flip LTPU_STATE_PROFILE around a
    monkeypatch and need the cached mode to follow)."""
    global _ENABLED
    _ENABLED = None


def fork_name(state):
    """The profile key's fork component, from the same structural
    hasattr probes as ``process_epoch_for_fork``."""
    if hasattr(state, "next_withdrawal_index"):
        return "capella"
    if hasattr(state, "latest_execution_payload_header"):
        return "bellatrix"
    if hasattr(state, "previous_epoch_participation"):
        return "altair"
    return "phase0"


_VBUCKETS = ((256, "<=256"), (1024, "<=1k"), (4096, "<=4k"),
             (16384, "<=16k"), (65536, "<=64k"), (262144, "<=256k"),
             (1048576, "<=1M"))


def vbucket(n):
    """Validator-count log bucket: stage cost scales with the registry,
    so rows from a 64-validator test must not dilute the 1M-validator
    EWMA the epoch-on-device work will plan against."""
    for edge, label in _VBUCKETS:
        if n <= edge:
            return label
    return ">1M"


def _bucket_index(ms):
    for i, edge in enumerate(BUCKETS_MS):
        if ms <= edge:
            return i
    return len(BUCKETS_MS)          # +Inf bucket


class _NullStage:
    """No-op context manager, one shared instance."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class _NullTimer:
    """The disabled-path singleton: ``stage()`` returns the shared
    no-op context regardless of arguments."""

    __slots__ = ()

    def stage(self, name, ops=0):
        return NULL_STAGE


NULL_STAGE = _NullStage()
NULL_TIMER = _NullTimer()


class _Stage:
    """One timed stage execution (context manager)."""

    __slots__ = ("_timer", "_name", "_ops", "_t0")

    def __init__(self, timer, name, ops):
        self._timer = timer
        self._name = name
        self._ops = ops
        self._t0 = time.perf_counter()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self._t0
        t = self._timer
        t.registry.record_stage(
            t.fork, self._name, t.n_validators, wall, ops=self._ops
        )
        return False


class StageTimer:
    """Armed-path timer bound to one (fork, validator count) context —
    constructed per instrumented call site by ``timer(state)``."""

    __slots__ = ("registry", "fork", "n_validators")

    def __init__(self, registry, fork, n_validators):
        self.registry = registry
        self.fork = fork
        self.n_validators = n_validators

    def stage(self, name, ops=0):
        return _Stage(self, name, ops)


def timer(state):
    """The instrumentation entry point.  Disabled: the shared null
    singleton (one cached-bool check, nothing touched on `state`).
    Armed: a StageTimer keyed to the state's fork and registry size."""
    if not enabled():
        return NULL_TIMER
    return StageTimer(
        get_registry(), fork_name(state), len(state.validators)
    )


class StageProfileRegistry:
    """Thread-safe accumulation of per-(fork, stage, vbucket) stage
    statistics with throttled JSON persistence — the state-transition
    sibling of ``crypto/tpu/profile.ProfileRegistry``."""

    def __init__(self, path=None):
        self.path = path
        self._lock = locks.lock("observability.stage_profile")
        self._entries = {}           # (fork, stage, vbucket) -> dict
        self._dirty = False
        self._last_save = 0.0
        locks.guarded(self, "_entries", self._lock)
        if path:
            self._load()

    # -- recording ----------------------------------------------------

    def _entry(self, fork, stage, vb):
        key = (fork, stage, vb)
        e = self._entries.get(key)
        if e is None:
            e = self._entries[key] = {
                "fork": fork, "stage": stage, "vbucket": vb,
                "calls": 0, "total_ms": 0.0, "ewma_ms": None,
                "min_ms": None, "max_ms": None,
                "hist": [0] * (len(BUCKETS_MS) + 1),
                "ops": 0,            # validator-ops accumulated
            }
        return e

    def record_stage(self, fork, stage, n_validators, wall_s, ops=0):
        """One stage execution: wall seconds around the stage body."""
        ms = max(float(wall_s), 0.0) * 1e3
        vb = vbucket(int(n_validators))
        with self._lock:
            locks.access(self, "_entries", "write")
            e = self._entry(fork, stage, vb)
            e["calls"] += 1
            e["total_ms"] += ms
            e["ewma_ms"] = (
                ms if e["ewma_ms"] is None
                else EWMA_ALPHA * ms + (1 - EWMA_ALPHA) * e["ewma_ms"]
            )
            e["min_ms"] = ms if e["min_ms"] is None else min(e["min_ms"], ms)
            e["max_ms"] = ms if e["max_ms"] is None else max(e["max_ms"], ms)
            e["hist"][_bucket_index(ms)] += 1
            e["ops"] += int(ops)
            ewma = e["ewma_ms"]
            self._dirty = True
        STAGE_CALLS.with_labels(fork, stage).inc()
        STAGE_EWMA.with_labels(fork, stage).set(round(ewma, 4))
        self._maybe_save()

    # -- reading ------------------------------------------------------

    def key_count(self):
        """Distinct (fork, stage, vbucket) keys held — the
        ``structure_depths`` leak-watch surface."""
        with self._lock:
            locks.access(self, "_entries", "read")
            return len(self._entries)

    def rows(self):
        """Per-key stat dicts, most total time first — the
        /lighthouse/state-profile payload."""
        with self._lock:
            locks.access(self, "_entries", "read")
            entries = [dict(e) for e in self._entries.values()]
        for e in entries:
            if e["calls"] > 0:
                e["mean_ms"] = round(e["total_ms"] / e["calls"], 4)
            for k in ("total_ms", "ewma_ms", "min_ms", "max_ms"):
                if isinstance(e.get(k), float):
                    e[k] = round(e[k], 4)
        entries.sort(key=lambda e: -e["total_ms"])
        return entries

    def snapshot(self):
        return {
            "schema": _SCHEMA,
            "path": self.path,
            "rows": self.rows(),
        }

    def stage_totals(self):
        """{stage: {total_ms, calls, ops}} aggregated over fork and
        vbucket — the per-stage table and the totality check's
        numerator."""
        out = {}
        for e in self.rows():
            s = out.setdefault(e["stage"], {
                "total_ms": 0.0, "calls": 0, "ops": 0,
            })
            s["total_ms"] = round(s["total_ms"] + e["total_ms"], 4)
            s["calls"] += e["calls"]
            s["ops"] += e["ops"]
        return out

    def summary(self, top_n=5):
        rows = self.rows()
        return {
            "schema": _SCHEMA,
            "stages": self.stage_totals(),
            "top_sinks": [
                {"fork": e["fork"], "stage": e["stage"],
                 "vbucket": e["vbucket"], "total_ms": e["total_ms"],
                 "calls": e["calls"], "ewma_ms": e["ewma_ms"]}
                for e in rows[:top_n]
            ],
        }

    def reset(self):
        with self._lock:
            locks.access(self, "_entries", "write")
            self._entries.clear()
            self._dirty = False

    # -- persistence --------------------------------------------------

    def _load(self):
        try:
            with open(self.path, "r", encoding="utf-8") as f:
                data = json.load(f)
            if data.get("schema") != _SCHEMA:
                return
            for row in data.get("rows", []):
                key = (row["fork"], row["stage"], row["vbucket"])
                e = {
                    "fork": row["fork"], "stage": row["stage"],
                    "vbucket": row["vbucket"],
                    "calls": int(row.get("calls", 0)),
                    "total_ms": float(row.get("total_ms", 0.0)),
                    "ewma_ms": row.get("ewma_ms"),
                    "min_ms": row.get("min_ms"),
                    "max_ms": row.get("max_ms"),
                    "hist": list(row.get("hist") or
                                 [0] * (len(BUCKETS_MS) + 1)),
                    "ops": int(row.get("ops", 0)),
                }
                if len(e["hist"]) != len(BUCKETS_MS) + 1:
                    e["hist"] = [0] * (len(BUCKETS_MS) + 1)
                self._entries[key] = e
        except FileNotFoundError:
            pass
        except Exception as exc:
            # a corrupt profile never blocks the transition — start fresh
            log.warning("state profile %s unreadable (%s); starting "
                        "empty", self.path, str(exc)[:120])

    def save(self, force=False):
        """Persist beside kernel_profile.json.  Throttled unless forced
        — stage recording sits inside the state transition and must
        never wait on repeated disk writes."""
        if not self.path:
            return False
        with self._lock:
            locks.access(self, "_entries", "read")
            if not self._dirty and not force:
                return False
            now = time.monotonic()
            if not force and now - self._last_save < _SAVE_INTERVAL_S:
                return False
            self._dirty = False
            self._last_save = now
        payload = {
            "schema": _SCHEMA,
            "buckets_ms": list(BUCKETS_MS),
            "rows": self.rows(),
        }
        try:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            tmp = f"{self.path}.tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
            return True
        except OSError as exc:
            log.warning("state profile save failed: %s", str(exc)[:120])
            return False

    def _maybe_save(self):
        self.save(force=False)


_REGISTRY = None
_REG_LOCK = threading.Lock()


def _default_path():
    from ..crypto.tpu.compile_cache import _default_cache_dir

    return os.path.join(_default_cache_dir(), "state_profile.json")


def get_registry() -> StageProfileRegistry:
    global _REGISTRY
    with _REG_LOCK:
        if _REGISTRY is None:
            _REGISTRY = StageProfileRegistry(_default_path())
        return _REGISTRY


def set_registry(registry):
    """Swap the process registry (tests point it at a tmp path)."""
    global _REGISTRY
    with _REG_LOCK:
        _REGISTRY = registry
