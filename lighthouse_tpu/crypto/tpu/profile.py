"""Per-kernel performance profile registry.

Every `CachedKernel` launch lands here: wall-time EWMA + log-bucket
histogram keyed by (kernel, canonical shape label, mesh topology),
joined with the XLA `cost_analysis()` numbers (flops, bytes accessed)
captured once at compile/load time, plus the pad-waste ratio the
lane planner imposed on each launch.  The key includes the topology
fingerprint because a sharded SPMD program is a DIFFERENT program with
different cost — mixing its samples with the single-device variant
would hide exactly the regression this registry exists to surface.

The registry persists beside the AOT compile cache
(`<cache_dir>/kernel_profile.json`, atomic tmp+replace, throttled) so
cold-start wall/cost baselines survive process restarts the way the
executables themselves do.  Served at `GET /lighthouse/profile`;
summarized by `tools/profile_report.py`.

Measurement notes: wall times include `block_until_ready`, so they are
device wall, not dispatch wall.  cost_analysis is XLA's static model —
the report tool's "cost fit" column (measured wall vs. flops) is how
you spot a kernel whose runtime stopped tracking its arithmetic (e.g.
a layout change made it bandwidth-bound).
"""

import json
import math
import os
import threading
import time

from ...utils import metrics
from ...utils.logging import get_logger

log = get_logger("crypto.tpu.profile")

# wall-time histogram bucket edges, milliseconds (log-spaced: kernel
# walls span ~0.1ms host no-ops to multi-second cold device launches)
BUCKETS_MS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
              100.0, 250.0, 500.0, 1000.0, 2500.0)
EWMA_ALPHA = 0.2
_SAVE_INTERVAL_S = 5.0
_SCHEMA = 1

LAUNCHES = metrics.counter(
    "kernel_profile_launches_total",
    "Kernel launches recorded by the per-kernel profile registry, by "
    "kernel and canonical shape label",
    labels=("kernel", "shape"),
)
WALL_EWMA = metrics.gauge(
    "kernel_profile_wall_ms",
    "EWMA device wall time (ms, includes block_until_ready) of the "
    "most recent launches, by kernel and canonical shape label",
    labels=("kernel", "shape"),
)
PAD_WASTE = metrics.gauge(
    "kernel_profile_pad_waste_ratio",
    "Fraction of padded lanes carrying no real work in recent launches "
    "(1 - sets/lanes), by kernel and canonical shape label",
    labels=("kernel", "shape"),
)


def _bucket_index(ms):
    for i, edge in enumerate(BUCKETS_MS):
        if ms <= edge:
            return i
    return len(BUCKETS_MS)          # +Inf bucket


def _topology():
    try:
        from . import sharding

        return sharding.topology_fingerprint()
    except Exception:
        return "unknown"


def extract_cost(exe):
    """Pull {flops, bytes_accessed, transcendentals} out of an XLA
    executable's cost_analysis(), tolerating the dict-vs-[dict] shape
    difference across jax versions.  None when the backend offers no
    cost model (the registry row simply has no cost join)."""
    try:
        ca = exe.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    out = {}
    for key, field in (("flops", "flops"),
                       ("bytes accessed", "bytes_accessed"),
                       ("transcendentals", "transcendentals")):
        v = ca.get(key)
        if isinstance(v, (int, float)) and math.isfinite(v) and v >= 0:
            out[field] = float(v)
    return out or None


class ProfileRegistry:
    """Thread-safe accumulation of per-(kernel, shape, topology) launch
    statistics with throttled JSON persistence."""

    def __init__(self, path=None):
        self.path = path
        self._lock = threading.Lock()
        self._entries = {}           # (kernel, shape, topology) -> dict
        self._dirty = False
        self._last_save = 0.0
        if path:
            self._load()

    # -- recording ----------------------------------------------------

    def _entry(self, kernel, shape, topology):
        key = (kernel, shape, topology)
        e = self._entries.get(key)
        if e is None:
            e = self._entries[key] = {
                "kernel": kernel, "shape": shape, "topology": topology,
                "launches": 0, "total_ms": 0.0, "ewma_ms": None,
                "min_ms": None, "max_ms": None,
                "hist": [0] * (len(BUCKETS_MS) + 1),
                "source": {},          # 'aot'|'jit' -> launch count
                "cost": None,          # flops / bytes_accessed join
                "pad_sets": 0, "pad_lanes": 0,
            }
        return e

    def record_launch(self, kernel, shape, wall_s, source="aot",
                      topology=None):
        """One kernel execution: wall seconds (measured around the
        executable call, block_until_ready included)."""
        ms = max(float(wall_s), 0.0) * 1e3
        topology = topology or _topology()
        with self._lock:
            e = self._entry(kernel, shape, topology)
            e["launches"] += 1
            e["total_ms"] += ms
            e["ewma_ms"] = (
                ms if e["ewma_ms"] is None
                else EWMA_ALPHA * ms + (1 - EWMA_ALPHA) * e["ewma_ms"]
            )
            e["min_ms"] = ms if e["min_ms"] is None else min(e["min_ms"], ms)
            e["max_ms"] = ms if e["max_ms"] is None else max(e["max_ms"], ms)
            e["hist"][_bucket_index(ms)] += 1
            e["source"][source] = e["source"].get(source, 0) + 1
            ewma = e["ewma_ms"]
            self._dirty = True
        LAUNCHES.with_labels(kernel, shape).inc()
        WALL_EWMA.with_labels(kernel, shape).set(round(ewma, 3))
        self._maybe_save()

    def record_cost(self, kernel, shape, cost, topology=None):
        """Join the static XLA cost numbers onto the key (once per
        compile/load; later launches reuse them)."""
        if not cost:
            return
        topology = topology or _topology()
        with self._lock:
            e = self._entry(kernel, shape, topology)
            e["cost"] = dict(cost)
            self._dirty = True

    def record_pad(self, kernel, shape, n_sets, n_lanes, topology=None):
        """One launch's pad occupancy: `n_sets` real inputs carried on
        `n_lanes` padded lanes (the planner's bucket)."""
        if n_lanes <= 0:
            return
        topology = topology or _topology()
        with self._lock:
            e = self._entry(kernel, shape, topology)
            e["pad_sets"] += int(n_sets)
            e["pad_lanes"] += int(n_lanes)
            waste = 1.0 - e["pad_sets"] / e["pad_lanes"]
            self._dirty = True
        PAD_WASTE.with_labels(kernel, shape).set(round(max(waste, 0.0), 4))

    # -- reading ------------------------------------------------------

    def key_count(self):
        """Distinct (kernel, shape, topology) keys held — the leak-watch
        depth surface (`lighthouse_structure_depth{structure=
        "profile_registry"}`): an unbounded-shape workload shows up here
        before it shows up as RSS."""
        with self._lock:
            return len(self._entries)

    def rows(self):
        """Per-(kernel, shape, topology) stat dicts, most total time
        first — the /lighthouse/profile payload."""
        with self._lock:
            entries = [dict(e) for e in self._entries.values()]
        for e in entries:
            if e["pad_lanes"] > 0:
                e["pad_waste_ratio"] = round(
                    max(1.0 - e["pad_sets"] / e["pad_lanes"], 0.0), 4
                )
            if e["launches"] > 0:
                e["mean_ms"] = round(e["total_ms"] / e["launches"], 3)
            for k in ("total_ms", "ewma_ms", "min_ms", "max_ms"):
                if isinstance(e.get(k), float):
                    e[k] = round(e[k], 3)
        entries.sort(key=lambda e: -e["total_ms"])
        return entries

    def snapshot(self):
        """Full registry view: rows plus the mesh-plan launch counters
        (sharded vs single-device program launches, PR-10 counters)."""
        try:
            from . import sharding

            launch_counts = sharding.launch_counts()
        except Exception:
            launch_counts = {}
        return {
            "schema": _SCHEMA,
            "path": self.path,
            "topology": _topology(),
            "launch_counts": launch_counts,
            "rows": self.rows(),
        }

    def reset(self):
        with self._lock:
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
                key = (row["kernel"], row["shape"], row["topology"])
                e = {
                    "kernel": row["kernel"], "shape": row["shape"],
                    "topology": row["topology"],
                    "launches": int(row.get("launches", 0)),
                    "total_ms": float(row.get("total_ms", 0.0)),
                    "ewma_ms": row.get("ewma_ms"),
                    "min_ms": row.get("min_ms"),
                    "max_ms": row.get("max_ms"),
                    "hist": list(row.get("hist") or
                                 [0] * (len(BUCKETS_MS) + 1)),
                    "source": dict(row.get("source") or {}),
                    "cost": row.get("cost"),
                    "pad_sets": int(row.get("pad_sets", 0)),
                    "pad_lanes": int(row.get("pad_lanes", 0)),
                }
                if len(e["hist"]) != len(BUCKETS_MS) + 1:
                    e["hist"] = [0] * (len(BUCKETS_MS) + 1)
                self._entries[key] = e
        except FileNotFoundError:
            pass
        except Exception as exc:
            # a corrupt profile never blocks verification — start fresh
            log.warning("kernel profile %s unreadable (%s); starting "
                        "empty", self.path, str(exc)[:120])

    def save(self, force=False):
        """Persist next to the AOT cache.  Throttled (at most one write
        per _SAVE_INTERVAL_S) unless forced — launch recording sits on
        the dispatch path and must never wait on repeated disk writes."""
        if not self.path:
            return False
        with self._lock:
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
            log.warning("kernel profile save failed: %s", str(exc)[:120])
            return False

    def _maybe_save(self):
        self.save(force=False)


_REGISTRY = None
_REG_LOCK = threading.Lock()


def _default_path():
    from .compile_cache import _default_cache_dir

    return os.path.join(_default_cache_dir(), "kernel_profile.json")


def get_registry() -> ProfileRegistry:
    global _REGISTRY
    with _REG_LOCK:
        if _REGISTRY is None:
            _REGISTRY = ProfileRegistry(_default_path())
        return _REGISTRY


def set_registry(registry):
    """Swap the process registry (tests point it at a tmp path)."""
    global _REGISTRY
    with _REG_LOCK:
        _REGISTRY = registry
