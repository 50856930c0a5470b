"""Compile-lifecycle subsystem: canonical shapes + persistent AOT cache.

The device path's dominant cost is no longer the kernel — it is XLA
compilation: 42-132 s warm per bucket shape, up to 314 s cold
(BENCH_WARM.json).  Every watchdog restart or fresh verifier host used
to pay that again, mid-slot.  This module kills the tax in three moves:

  1. **ShapePlanner** — every `(n_sets, max_pks)` batch lands on a shape
     drawn from a bounded, enumerable menu (pow-2 ladders capped at the
     compile bucket / a protocol-sized pubkey ceiling, env-overridable),
     so the set of distinct compiled programs is closed and can be
     walked ahead of time.  This replaces the ad-hoc `_next_pow2`
     padding scattered through bls.py/decompress.py.

  2. **CompileCache** — each canonical program is lowered once via
     ``jax.jit(f).lower(args).compile()`` and the executable is
     serialized (jax.experimental.serialize_executable) into an on-disk
     cache keyed on jax/jaxlib version + platform + device kind + CPU
     fingerprint + kernel-source hash + the exact arg-shape signature.
     A second process start pays DESERIALIZATION (milliseconds), not
     compilation (minutes).  Any mismatch — stale key, foreign host,
     corrupt file — degrades to a plain compile and overwrites the
     entry; a hard serialization failure falls back to ordinary jit.

  3. **prewarm()** — walks the canonical menu loading-or-compiling every
     kernel, with a progress callback the node uses to gate device
     admission (verify_service serves traffic on the host path until the
     menu is warm) and to drive the `verify_service_warmth` gauge.

Metrics: `compile_cache_{hits,misses}_total{kernel}`,
`compile_cache_{deserialize,compile}_ms{kernel,shape}` (last-duration
gauges; shape cardinality is bounded by the menu),
`compile_cache_deserialize_failures_total`,
`compile_cache_offmenu_total`.  `GET /lighthouse/compile-cache` serves
the live entry table.
"""

import hashlib
import os
import pickle
import threading
import time

import jax

from ...utils import metrics as _metrics
from ...utils.logging import get_logger

log = get_logger("crypto")

HITS = _metrics.counter(
    "compile_cache_hits_total",
    "AOT executable cache hits (deserialization instead of XLA compile)",
    labels=("kernel",),
)
MISSES = _metrics.counter(
    "compile_cache_misses_total",
    "AOT executable cache misses (full XLA compile paid)",
    labels=("kernel",),
)
DESERIALIZE_MS = _metrics.gauge(
    "compile_cache_deserialize_ms",
    "Milliseconds the last executable deserialization took, per kernel "
    "and canonical shape",
    labels=("kernel", "shape"),
)
COMPILE_MS = _metrics.gauge(
    "compile_cache_compile_ms",
    "Milliseconds the last full XLA compile took, per kernel and "
    "canonical shape",
    labels=("kernel", "shape"),
)
DESERIALIZE_FAILURES = _metrics.counter(
    "compile_cache_deserialize_failures_total",
    "Cache entries that failed to deserialize (stale key, foreign host, "
    "corrupt file) and fell back to a fresh compile",
)
OFFMENU = _metrics.counter(
    "compile_cache_offmenu_total",
    "Shape requests beyond the canonical menu ceiling (padded to the "
    "next power of two; should be zero for protocol traffic)",
)


def _pow2_ladder(cap):
    out = []
    v = 1
    while v < cap:
        out.append(v)
        v <<= 1
    out.append(cap)
    return out


def _next_pow2(n):
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _parse_menu(raw):
    vals = sorted({int(v) for v in raw.replace(";", ",").split(",") if v.strip()})
    if not vals or any(v < 1 for v in vals):
        raise ValueError(f"bad shape menu {raw!r}")
    return vals


class ShapePlanner:
    """Total map from a requested batch shape onto the canonical menu.

    * set axis: menu defaults to the pow-2 ladder up to the compile
      bucket (`LTPU_MAX_SETS_BUCKET`, default 32 — the BENCH_r05 knee);
      batches beyond the bucket are CHUNKED by the caller, so the axis
      never exceeds the menu top.
    * pubkey axis: pow-2 ladder up to `LTPU_SHAPE_MAX_PKS` (default
      4096, above any protocol committee), so the planner is total over
      real traffic.  A request beyond the ceiling still returns the next
      power of two — counted in `compile_cache_offmenu_total` — rather
      than failing verification, but it is unreachable for consensus
      work by construction.

    Env overrides: `LTPU_SHAPE_SETS_MENU` / `LTPU_SHAPE_PKS_MENU` /
    `LTPU_SHAPE_LANES_MENU` (comma-separated ascending values) pin a
    sparse production menu, e.g. `LTPU_SHAPE_PKS_MENU=1,2,64` on a host
    that only sees attestation/aggregate traffic; the lanes menu is the
    g2-decompress batch axis, independent of pubkeys-per-set.  `LTPU_PREWARM_SHAPES`
    (`NxM,NxM,...`, default `{bucket}x1,{bucket}x2`) names the shapes
    prewarm compiles ahead of admission.

    Mesh awareness: on a sharded mesh plan (sharding.MeshPlan) every
    planned set/lane bucket is rounded UP to a multiple of the dp axis
    (and the pubkey bucket to a multiple of mp), so `NamedSharding` can
    split the batch axis evenly — the pow-2 menus already satisfy this
    for pow-2 meshes, and an odd mesh just pads a little further.
    """

    def __init__(self, set_menu=None, pk_menu=None, prewarm=None):
        # the dp/mp divisibility the sharded placement needs; a failure
        # to consult the mesh (uninitialized backend) degrades to 1,
        # i.e. exactly the pre-mesh planner behavior
        try:
            from . import sharding as _sharding

            plan = _sharding.get_mesh_plan()
            self.dp_multiple = plan.dp_multiple
            self.mp_multiple = plan.mp_multiple
        except Exception:  # noqa: BLE001
            self.dp_multiple = 1
            self.mp_multiple = 1
        bucket = max(1, int(os.environ.get("LTPU_MAX_SETS_BUCKET", "32")))
        max_pks = max(1, int(os.environ.get("LTPU_SHAPE_MAX_PKS", "4096")))
        raw = os.environ.get("LTPU_SHAPE_SETS_MENU")
        self.set_menu = list(set_menu) if set_menu else (
            _parse_menu(raw) if raw else _pow2_ladder(bucket)
        )
        raw = os.environ.get("LTPU_SHAPE_PKS_MENU")
        self.pk_menu = list(pk_menu) if pk_menu else (
            _parse_menu(raw) if raw else _pow2_ladder(max_pks)
        )
        # decompress batch lanes are their OWN axis (signatures per
        # gossip decompress batch, unrelated to pubkeys-per-set): a
        # sparse production pk menu must not reshape decompress padding
        raw = os.environ.get("LTPU_SHAPE_LANES_MENU")
        self.lane_menu = (
            _parse_menu(raw) if raw else _pow2_ladder(max_pks)
        )
        self.bucket = self.set_menu[-1]
        raw = os.environ.get("LTPU_PREWARM_SHAPES")
        if prewarm is not None:
            self.prewarm_menu = list(prewarm)
        elif raw:
            self.prewarm_menu = []
            for part in raw.split(","):
                n, m = part.lower().split("x")
                self.prewarm_menu.append(
                    (self.plan_sets(int(n)), self.plan_pks(int(m)))
                )
        else:
            self.prewarm_menu = [(self.bucket, 1), (self.bucket, 2)]

    @staticmethod
    def _bucket_of(v, menu):
        for entry in menu:
            if entry >= v:
                return entry
        OFFMENU.inc()
        return _next_pow2(v)

    def _axis_round(self, v, menu, multiple):
        """Round a planned bucket up to `multiple` so a NamedSharding
        axis splits evenly; prefer a menu entry that already satisfies
        it (keeps the compiled-program set on the enumerable menu)."""
        if multiple <= 1 or v % multiple == 0:
            return v
        v = ((v + multiple - 1) // multiple) * multiple
        for entry in menu:
            if entry >= v and entry % multiple == 0:
                return entry
        return v

    def plan_sets(self, n, floor=1):
        """Canonical set-axis lanes for an `n`-set chunk (floor: the
        chunked paths pin every chunk of a batch to one shape).  On a
        sharded mesh the bucket is a multiple of the dp axis."""
        v = self._bucket_of(max(int(n), int(floor), 1), self.set_menu)
        return self._axis_round(v, self.set_menu, self.dp_multiple)

    def plan_pks(self, m, floor=1):
        """Canonical pubkey-axis lanes for a max-`m`-pubkey batch (a
        multiple of the mp axis on a sharded mesh, so the pubkey split
        divides evenly — a 1-pubkey bucket under mp>1 replicates
        instead, handled at placement)."""
        v = self._bucket_of(max(int(m), int(floor), 1), self.pk_menu)
        if v >= self.mp_multiple:
            v = self._axis_round(v, self.pk_menu, self.mp_multiple)
        return v

    def plan_lanes(self, n):
        """Canonical decompress-batch lanes for `n` signatures (dp
        multiple on a sharded mesh — the decompress batch axis shards
        with the same placement as the verify set axis)."""
        v = self._bucket_of(max(int(n), 1), self.lane_menu)
        return self._axis_round(v, self.lane_menu, self.dp_multiple)

    def plan(self, n_sets, max_pks, min_sets=1, min_pks=1):
        return (self.plan_sets(n_sets, min_sets),
                self.plan_pks(max_pks, min_pks))

    def shapes(self):
        """The full enumerable program menu (set x pk combinations)."""
        return [(n, m) for n in self.set_menu for m in self.pk_menu]

    def describe(self):
        return {
            "set_menu": list(self.set_menu),
            "pk_menu": list(self.pk_menu),
            "lane_menu": list(self.lane_menu),
            "bucket": self.bucket,
            "dp_multiple": self.dp_multiple,
            "mp_multiple": self.mp_multiple,
            "prewarm": [f"{n}x{m}" for n, m in self.prewarm_menu],
            "programs_bounded_at": len(self.set_menu) * len(self.pk_menu),
        }


_PLANNER = None
_PLANNER_ENV = None
_PLANNER_LOCK = threading.Lock()

_PLANNER_ENV_KEYS = (
    "LTPU_MAX_SETS_BUCKET", "LTPU_SHAPE_MAX_PKS",
    "LTPU_SHAPE_SETS_MENU", "LTPU_SHAPE_PKS_MENU",
    "LTPU_SHAPE_LANES_MENU", "LTPU_PREWARM_SHAPES",
    # the mesh knobs reshape the planner's dp/mp rounding too
    "LTPU_MESH", "LTPU_MESH_DISABLE",
)


def get_planner() -> ShapePlanner:
    """Process planner, rebuilt if the shape env knobs changed (tests
    and tools monkeypatch them)."""
    global _PLANNER, _PLANNER_ENV
    env = tuple(os.environ.get(k) for k in _PLANNER_ENV_KEYS)
    with _PLANNER_LOCK:
        if _PLANNER is None or env != _PLANNER_ENV:
            _PLANNER = ShapePlanner()
            _PLANNER_ENV = env
        return _PLANNER


# ------------------------------------------------------------- fingerprint


def _kernel_source_fingerprint():
    """Hash of every crypto/tpu module source (+ field constants): a
    kernel edit must invalidate the serialized executables built from
    the old graph."""
    here = os.path.dirname(os.path.abspath(__file__))
    h = hashlib.sha256()
    for name in sorted(os.listdir(here)):
        if not name.endswith(".py"):
            continue
        if name == "compile_cache.py":
            continue  # cache-policy edits must not nuke valid artifacts
        with open(os.path.join(here, name), "rb") as f:
            h.update(name.encode())
            h.update(f.read())
    const = os.path.join(os.path.dirname(here), "constants.py")
    try:
        with open(const, "rb") as f:
            h.update(f.read())
    except OSError:
        pass
    return h.hexdigest()[:16]


def _host_fingerprint():
    """jaxlib/platform/device/CPU-feature key: an artifact compiled
    elsewhere (or for another backend) must read as absent, not load as
    a hazard (XLA:CPU binaries are machine-feature-specific — see
    utils/xla_cache.py)."""
    from ...utils.xla_cache import _cpu_fingerprint

    try:
        dev = jax.devices()[0]
        device_kind = f"{dev.platform}:{getattr(dev, 'device_kind', '?')}"
    except Exception:
        device_kind = "uninitialized"
    bits = "|".join([
        jax.__version__,
        getattr(jax.lib, "__version__", "?"),
        device_kind,
        _cpu_fingerprint(),
    ])
    return hashlib.sha256(bits.encode()).hexdigest()[:16]


# ------------------------------------------------------------------ cache


def _default_cache_dir():
    from ...utils.xla_cache import aot_cache_dir

    return aot_cache_dir()


def _execution_devices(args):
    """The devices a program over `args` was compiled for: the mesh of a
    sharded leaf, else the default device.  A deserialized executable
    must load onto exactly these — left to itself it loads onto every
    local device and then refuses 1-device arguments."""
    for a in jax.tree_util.tree_leaves(args):
        mesh = getattr(getattr(a, "sharding", None), "mesh", None)
        if mesh is not None and mesh.size > 1:
            return list(mesh.devices.flat)
    return jax.devices()[:1]


def _leaf_sharding_tag(a):
    """Per-leaf placement component of the cache key: a NamedSharding
    over a >1-device mesh compiles a DIFFERENT (SPMD) program than the
    same shapes unsharded, so the two must never share an entry.
    Single-device/uncommitted leaves tag as '' — the unsharded key is
    byte-identical to the pre-mesh layout of this signature."""
    s = getattr(a, "sharding", None)
    mesh = getattr(s, "mesh", None)
    spec = getattr(s, "spec", None)
    if mesh is None or spec is None:
        return ""
    try:
        if mesh.size <= 1:
            return ""
        axes = ",".join(f"{k}{v}" for k, v in mesh.shape.items())
    except Exception:  # noqa: BLE001 — exotic sharding: key on its repr
        return str(s)
    return f"{axes}|{spec}"


def _shape_sig(args):
    """Flattened (shape, dtype, sharding) signature of an argument
    pytree — the part of the cache key that pins the canonical shape
    and its mesh placement."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = tuple(
        (tuple(getattr(a, "shape", ())),
         str(getattr(a, "dtype", type(a))),
         _leaf_sharding_tag(a))
        for a in leaves
    )
    return sig, str(treedef)


class CompileCache:
    """Disk + memory cache of compiled XLA executables.

    `load_or_compile(name, fn, args)` returns a callable for `fn`
    specialized to `args`' shapes: from the in-memory map, else
    deserialized from disk, else freshly compiled (and serialized back).
    Every failure mode degrades toward a working compile — the cache can
    make a process slower to start, never broken.
    """

    def __init__(self, cache_dir=None, enabled=None):
        if enabled is None:
            enabled = os.environ.get("LTPU_COMPILE_CACHE", "1") != "0"
        self.enabled = bool(enabled)
        self.cache_dir = cache_dir or _default_cache_dir()
        self._mem = {}
        self._inflight = {}          # key -> Event: first-caller dedup
        self._lock = threading.Lock()
        self._fingerprint = None
        self.hits = 0
        self.misses = 0
        self.deserialize_failures = 0
        # entry key -> {kernel, shape, source, ms} for the status route
        self.loaded = {}

    # -- keys ---------------------------------------------------------

    def fingerprint(self):
        """Host + kernel-source key, suffixed with the LIVE topology tag
        (device count + mesh axes, sharding.topology_fingerprint): a
        blob compiled under one topology must read as absent under
        another — even on the unsharded path, where a 1-device XLA:CPU
        executable would otherwise silently load into (and serve) an
        8-device process.  The host/source part is cached; the topology
        part is recomputed so env-driven mesh changes (tests, bench
        subprocesses) re-key immediately."""
        if self._fingerprint is None:
            self._fingerprint = (
                _host_fingerprint() + "-" + _kernel_source_fingerprint()
            )
        from . import sharding as _sharding

        return self._fingerprint + "-" + _sharding.topology_fingerprint()

    def _entry_path(self, name, shape_hash, fingerprint=None):
        fingerprint = fingerprint or self.fingerprint()
        return os.path.join(
            self.cache_dir, f"{name}-{shape_hash}-{fingerprint}.aot"
        )

    # -- core ---------------------------------------------------------

    def _key(self, name, args):
        sig, treedef = _shape_sig(args)
        shape_hash = hashlib.sha256(
            repr((sig, treedef)).encode()
        ).hexdigest()[:12]
        return sig, shape_hash

    def entry_on_disk(self, name, args):
        """Whether a current-fingerprint artifact exists for this
        program (prewarm orders compiles before deserializations with
        this — see prewarm())."""
        _, shape_hash = self._key(name, args)
        return os.path.exists(self._entry_path(name, shape_hash))

    def load_or_compile(self, name, fn, args, shape_label=None):
        """Callable for `fn` at `args`' shapes.  `args` may be concrete
        arrays or jax.ShapeDtypeStruct trees (prewarm passes the
        latter)."""
        sig, shape_hash = self._key(name, args)
        key = (name, shape_hash)
        while True:
            with self._lock:
                hit = self._mem.get(key)
                if hit is not None:
                    return hit
                pending = self._inflight.get(key)
                if pending is None:
                    # we are the builder for this (kernel, shape)
                    self._inflight[key] = threading.Event()
                    break
            # another thread is mid-compile for the same program: wait
            # for it instead of paying a duplicate multi-minute compile
            pending.wait()
        label = shape_label or self._label_from_sig(sig)
        try:
            exe, how, ms = self._load_from_disk(
                name, fn, args, shape_hash, label
            )
            with self._lock:
                self._mem[key] = exe
                self.loaded[f"{name}@{label}"] = {
                    "kernel": name, "shape": label, "source": how,
                    "ms": round(ms, 1),
                }
            # join the static XLA cost model onto the profile key once,
            # at the moment the executable enters the process — launches
            # then only pay the wall-clock sample
            try:
                from . import profile

                profile.get_registry().record_cost(
                    name, label, profile.extract_cost(exe)
                )
            except Exception:
                pass
            return exe
        finally:
            with self._lock:
                ev = self._inflight.pop(key, None)
            if ev is not None:
                ev.set()

    def call(self, name, fn, args, shape_label=None):
        return self.load_or_compile(name, fn, args, shape_label)(*args)

    @staticmethod
    def _label_from_sig(sig):
        # first leaf's trailing dims name the shape well enough for
        # metrics ("(24, 32, 2)" -> "32x2"); fall back to the hash label
        for shape, *_ in sig:
            if len(shape) >= 2:
                return "x".join(str(d) for d in shape[1:])
        return "scalar"

    def _load_from_disk(self, name, fn, args, shape_hash, label):
        """(callable, 'deserialized'|'compiled'|'jit', ms).

        The fingerprint is read once, here: its topology part follows the
        live mesh env, which may change while this program compiles, and
        the entry's file name and its blob must carry the same key."""
        fingerprint = self.fingerprint()
        path = self._entry_path(name, shape_hash, fingerprint)
        devices = _execution_devices(args)
        if self.enabled:
            exe, ms = self._try_deserialize(path, devices, fingerprint)
            if exe is not None:
                with self._lock:
                    self.hits += 1
                HITS.with_labels(name).inc()
                DESERIALIZE_MS.with_labels(name, label).set(round(ms, 1))
                return exe, "deserialized", ms
        with self._lock:
            self.misses += 1
        MISSES.with_labels(name).inc()
        t0 = time.monotonic()
        compiled = self._fresh_compile(fn, args)
        ms = (time.monotonic() - t0) * 1e3
        COMPILE_MS.with_labels(name, label).set(round(ms, 1))
        if self.enabled:
            self._try_serialize(path, compiled, name, shape_hash, devices,
                                fingerprint)
        return compiled, "compiled", ms

    @staticmethod
    def _fresh_compile(fn, args):
        """Compile with jax's OWN persistent compilation cache disabled:
        an executable that jax served from its cache was itself
        deserialized, and re-serializing a deserialized XLA:CPU
        executable drops the split-module kernel symbols (observed as
        `Symbols not found: [concatenate..., ...fusion...]` on the next
        load).  Only genuinely-compiled executables round-trip, so
        canonical kernels always compile for real — this AOT cache is
        their persistence tier."""
        from jax._src.config import enable_compilation_cache

        with enable_compilation_cache(False):
            return jax.jit(fn).lower(*args).compile()

    def _try_deserialize(self, path, devices, fingerprint):
        from jax.experimental import serialize_executable as se

        if not os.path.exists(path):
            return None, 0.0
        t0 = time.monotonic()
        try:
            with open(path, "rb") as f:
                blob = pickle.load(f)
            if blob.get("fingerprint") != fingerprint:
                raise ValueError("fingerprint mismatch")
            exe = se.deserialize_and_load(
                blob["payload"], blob["in_tree"], blob["out_tree"],
                execution_devices=devices,
            )
            return exe, (time.monotonic() - t0) * 1e3
        except Exception as e:
            with self._lock:
                self.deserialize_failures += 1
            DESERIALIZE_FAILURES.inc()
            log.warning(
                "compile-cache entry %s unusable (%s); recompiling",
                os.path.basename(path), str(e)[:120],
            )
            try:
                os.unlink(path)
            except OSError:
                pass
            return None, 0.0

    def _try_serialize(self, path, compiled, name, shape_hash, devices,
                       fingerprint):
        from jax.experimental import serialize_executable as se

        try:
            payload, in_tree, out_tree = se.serialize(compiled)
            # publish-time round-trip proof: a blob that cannot load NOW
            # (e.g. serialized from an executable some other cache layer
            # deserialized) must never reach disk, where it would poison
            # every later start with a deserialize-fail-recompile loop
            se.deserialize_and_load(
                payload, in_tree, out_tree, execution_devices=devices
            )
            blob = pickle.dumps({
                "fingerprint": fingerprint,
                "kernel": name,
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
            })
            os.makedirs(self.cache_dir, exist_ok=True)
            tmp = path + f".tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
            self._gc_stale_siblings(name, shape_hash, os.path.basename(path))
        except Exception as e:
            # executable not serializable on this backend/version: the
            # compiled program still serves this process
            log.warning(
                "compile-cache serialize failed for %s (%s); "
                "in-memory only", name, str(e)[:120],
            )

    def _gc_stale_siblings(self, name, shape_hash, published):
        """Unlink entries for the same (kernel, shape) under a DIFFERENT
        fingerprint: a jax upgrade or kernel edit orphans every prior
        multi-megabyte executable (they read as absent, never load), and
        without pruning an iterating dev/CI host accumulates gigabytes
        of dead artifacts.  Publishing the current-fingerprint entry is
        the moment its predecessors are provably superseded."""
        prefix = f"{name}-{shape_hash}-"
        try:
            for n in os.listdir(self.cache_dir):
                if (n.endswith(".aot") and n != published
                        and n.startswith(prefix)):
                    try:
                        os.unlink(os.path.join(self.cache_dir, n))
                    except OSError:
                        pass
        except OSError:
            pass

    def evict(self, name, args):
        """Forget one program in memory and on disk, counted as a failed
        deserialization: an XLA:CPU artifact can load and still lack a
        kernel function it shared with other programs in the process
        that wrote it — its first run then fails with NOT_FOUND."""
        _, shape_hash = self._key(name, args)
        with self._lock:
            self._mem.pop((name, shape_hash), None)
            self.deserialize_failures += 1
        DESERIALIZE_FAILURES.inc()
        try:
            os.unlink(self._entry_path(name, shape_hash))
        except OSError:
            pass

    # -- introspection ------------------------------------------------

    def clear_memory(self):
        """Drop the in-process executable map (tests: simulate a fresh
        process against the same disk cache)."""
        with self._lock:
            self._mem.clear()
            self.loaded.clear()

    def disk_entries(self):
        try:
            names = sorted(os.listdir(self.cache_dir))
        except OSError:
            return []
        out = []
        for n in names:
            if not n.endswith(".aot"):
                continue
            p = os.path.join(self.cache_dir, n)
            try:
                st = os.stat(p)
                out.append({
                    "file": n, "bytes": st.st_size,
                    "current_key": n.endswith(f"-{self.fingerprint()}.aot"),
                })
            except OSError:
                continue
        return out

    def stats(self):
        with self._lock:
            return {
                "enabled": self.enabled,
                "dir": self.cache_dir,
                "fingerprint": self.fingerprint(),
                "hits": self.hits,
                "misses": self.misses,
                "deserialize_failures": self.deserialize_failures,
                "loaded": dict(self.loaded),
            }


_CACHE = None
_CACHE_LOCK = threading.Lock()


def get_cache() -> CompileCache:
    global _CACHE
    with _CACHE_LOCK:
        if _CACHE is None:
            _CACHE = CompileCache()
        return _CACHE


def set_cache(cache):
    """Swap the process cache (tests point it at a tmp dir)."""
    global _CACHE
    with _CACHE_LOCK:
        _CACHE = cache


class CachedKernel:
    """jit-compatible callable that routes through the compile cache.

    Falls back to a plain `jax.jit` of the kernel whenever the cache is
    disabled or anything in the AOT path fails — verification must
    never be down because caching is."""

    def __init__(self, name, fn):
        self.name = name
        self.fn = fn
        self._jit = jax.jit(fn)

    def __call__(self, *args):
        cache = get_cache()
        if not cache.enabled:
            return self._timed(self._jit, args, "jit")
        try:
            exe = cache.load_or_compile(self.name, self.fn, args)
        except Exception as e:
            log.warning(
                "compile-cache path failed for %s (%s); plain jit",
                self.name, str(e)[:120],
            )
            return self._timed(self._jit, args, "jit")
        # execute OUTSIDE the fallback: only CACHE machinery failures
        # degrade to plain jit — a device fault during execution must
        # propagate to the circuit-breaker seam immediately, not
        # trigger a blocking inline recompile on the dispatch path
        try:
            return self._timed(exe, args, "aot")
        except jax.errors.JaxRuntimeError as e:
            # the one exception, on XLA:CPU only: a loaded artifact
            # missing a kernel function (CompileCache.evict) is replaced
            # by a compile.  On an accelerator every execution error
            # reaches the breaker as it is.
            msg = str(e)
            if (jax.default_backend() != "cpu"
                    or not msg.startswith("NOT_FOUND")
                    or "not found" not in msg):
                raise
            log.warning("compile-cache entry for %s cannot run (%s); "
                        "recompiling", self.name, msg[:120])
            cache.evict(self.name, args)
            exe = cache.load_or_compile(self.name, self.fn, args)
            return self._timed(exe, args, "aot")

    def _timed(self, runner, args, source):
        """Execute and feed the profile registry: wall time around the
        call INCLUDING block_until_ready, so the registry records device
        wall rather than async-dispatch wall.  Profiling failures never
        fail a launch — the result is already in hand."""
        t0 = time.monotonic()
        out = jax.block_until_ready(runner(*args))
        wall = time.monotonic() - t0
        try:
            from . import profile

            sig, _ = _shape_sig(args)
            profile.get_registry().record_launch(
                self.name, CompileCache._label_from_sig(sig), wall,
                source=source,
            )
        except Exception as e:
            log.debug("kernel profile record failed for %s: %s",
                      self.name, str(e)[:120])
        return out


# ---------------------------------------------------------------- prewarm


def prewarm(shapes=None, progress=None, cache=None, per_set=True):
    """Load-or-compile the canonical kernel menu ahead of admission.

    For each (n_sets, m_pks) prewarm shape: the batched-verdict kernel
    and (`per_set`) the attribution kernel.  With a populated cache this
    is pure deserialization — a fresh host is device-ready in seconds.
    `progress(frac)` is called after each program (the node maps it onto
    the `verify_service_warmth` gauge).  Returns a summary dict.
    """
    from . import bls

    shapes = list(shapes or get_planner().prewarm_menu)
    specs = []
    for n, m in shapes:
        specs.extend(bls.kernel_specs(n, m, per_set=per_set))
    return {
        "shapes": [f"{n}x{m}" for n, m in shapes],
        **load_programs(specs, progress=progress, cache=cache),
    }


def load_programs(specs, progress=None, cache=None):
    """Load-or-compile every (name, fn, example_args, label) spec.

    Compiles MISSING entries before deserializing present ones: on
    this jaxlib, an XLA:CPU executable compiled AFTER any
    deserialization in the same process serializes incompletely
    (`Symbols not found` at the publish-time round-trip proof), so a
    mixed menu would never grow the cache.  Missing-first keeps the
    publish window pristine; the hits still all land."""
    cache = cache or get_cache()
    specs = sorted(specs, key=lambda s: cache.entry_on_disk(s[0], s[2]))
    t0 = time.monotonic()
    hits0, misses0 = cache.hits, cache.misses
    done = []
    for i, (name, fn, args, label) in enumerate(specs):
        t1 = time.monotonic()
        cache.load_or_compile(name, fn, args, shape_label=label)
        done.append({
            "kernel": name, "shape": label,
            "s": round(time.monotonic() - t1, 3),
        })
        if progress is not None:
            try:
                progress((i + 1) / len(specs))
            except Exception:
                pass
    hits = cache.hits - hits0
    misses = cache.misses - misses0
    total = hits + misses
    return {
        "programs": len(specs),
        "cache_hits": hits,
        "cache_misses": misses,
        "cache_hit_rate": round(hits / total, 4) if total else 1.0,
        "wall_s": round(time.monotonic() - t0, 3),
        "programs_detail": done,
    }
