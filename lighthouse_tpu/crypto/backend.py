"""BLS backend seam with device→native→host fallback.

Mirror of the reference's compile-time backend selection in
/root/reference/crypto/bls/src/lib.rs:29-49 (supranational | milagro |
fake_crypto | ckb-vm behind `define_mod!`), recast as a runtime seam:

  * "tpu"    — the JAX batched kernel (crypto/tpu/bls.py), the product
  * "native" — the C++ engine (csrc/blsnative.cpp), the blst-slot CPU
               path (~150+ sets/s/core vs the oracle's ~1)
  * "oracle" — the pure-python host reference (crypto/ref/bls.py), the
               milagro-analogue differential oracle
  * "fake"   — always-true (fake_crypto.rs:29-33), for STF-only tests

"auto" decides in-process from JAX's default device.  An explicit "tpu"
with no TPU visible raises at construction.  A device failure at verify
time degrades to the native engine (then the oracle) instead of taking
the node down (SURVEY.md §7 hard part 7: "TPU server crash must degrade
to blst, or a node outage becomes consensus-critical"), counting the
event in metrics; `fallback=False` re-raises instead.
"""

from ..utils import metrics
from ..utils.logging import get_logger

log = get_logger("crypto")


def _host_verify(sets):
    """Best host path: native C++ when buildable, else the oracle.  A
    native failure degrades to the oracle (the fallback chain must never
    re-raise out of its middle hop — SURVEY §7 hard part 7)."""
    from . import native_bls

    if native_bls.available():
        try:
            return native_bls.verify_signature_sets(sets)
        except Exception as e:
            metrics.HOST_BACKEND_FALLBACKS.inc()
            log.warning("native verify failed (%s); oracle fallback", e)
    from .ref import bls as RB

    return RB.verify_signature_sets(sets)


def _host_per_set(sets):
    from . import native_bls

    if native_bls.available():
        try:
            return native_bls.verify_signature_sets_per_set(sets)
        except Exception as e:
            metrics.HOST_BACKEND_FALLBACKS.inc()
            log.warning("native per-set failed (%s); oracle fallback", e)
    from .ref import bls as RB

    return [RB.verify_signature_sets([s]) for s in sets]


_AUTO_RESOLVED = None


def _device_platform():
    """Platform of JAX's default device, decided in THIS process: a
    chip belongs to one process, so a child process cannot answer for
    a parent that may already hold it."""
    import jax

    return jax.devices()[0].platform


def resolve_auto():
    """Pick the production backend for THIS host, once per process: a
    TPU -> "tpu"; else the native C++ engine; else the oracle."""
    global _AUTO_RESOLVED
    if _AUTO_RESOLVED is not None:
        return _AUTO_RESOLVED
    from .native_bls import available as _native_available

    platform = _device_platform()
    if platform == "tpu":
        backend = "tpu"
    else:
        backend = "native" if _native_available() else "oracle"
    # a host backend is loud: JAX lands on the CPU without a word when
    # the TPU runtime cannot start (another process holding the chip)
    note = log.info if backend == "tpu" else log.warning
    note("auto crypto backend: device platform %s -> %r", platform, backend)
    _AUTO_RESOLVED = backend
    return backend


class SignatureVerifier:
    def __init__(self, backend="tpu", fallback=True):
        assert backend in ("auto", "tpu", "native", "oracle", "fake")
        if backend == "auto":
            backend = resolve_auto()
        elif backend == "tpu":
            platform = _device_platform()
            if platform != "tpu":
                # an explicit device request never degrades at build
                # time: a node that silently ran on the host would hide
                # the missing chip
                raise RuntimeError(
                    f"crypto backend 'tpu' needs a TPU; JAX's default "
                    f"device is {platform!r}"
                )
        self.backend = backend
        self.fallback = fallback
        # verify_service circuit-breaker seam: called with the exception
        # whenever a device attempt degrades to the host path
        self.on_device_fallback = None

    def _note_device_fallback(self, e):
        metrics.DEVICE_FALLBACKS.inc()
        cb = self.on_device_fallback
        if cb is not None:
            try:
                cb(e)
            except Exception:
                pass

    @property
    def mesh_devices(self):
        """Devices in the active verification mesh plan (1 for every
        host backend and for a single-device/disabled mesh).  The
        verify_service dispatcher scales its batch knee by this."""
        if self.backend != "tpu":
            return 1
        try:
            from .tpu import sharding

            return sharding.get_mesh_plan().n_devices
        except Exception:  # noqa: BLE001 — no usable jax backend
            return 1

    def prewarm(self, progress=None):
        """Load-or-compile the canonical device kernel menu ahead of
        admission (crypto/tpu/compile_cache.prewarm): with a populated
        AOT cache this is seconds of deserialization, not minutes of XLA
        compilation.  No-op (None) for host backends — they have no
        compile tax to pay."""
        if self.backend != "tpu":
            return None
        from .tpu import compile_cache

        return compile_cache.prewarm(progress=progress)

    def plan_pipeline(self, sets):
        """Two-stage (host-prep, device-execute) chunk plan for the
        verify_service dispatcher's prep/device pipeline, or None when
        this backend has no stage split (host backends do all their work
        in one place; nothing to overlap).  A device failure inside an
        execute stage propagates to the caller, which falls back to the
        plain `verify_signature_sets` path — and THAT call drives the
        normal device→native→oracle degrade chain."""
        if self.backend != "tpu":
            return None
        try:
            from .tpu import bls as tb

            return tb.plan_pipeline(sets)
        except Exception:
            return None

    def verify_signature_sets(self, sets, priority=None) -> bool:
        # `priority` is accepted (and ignored) so call sites can tag work
        # for the verify_service drop-in without caring which seam they
        # hold — the service honors it, the bare verifier does not.
        sets = list(sets)
        if self.backend == "fake":
            return True
        metrics.SIGNATURE_SETS_VERIFIED.inc(len(sets))
        if self.backend == "tpu":
            try:
                from .tpu import bls as tb

                return tb.verify_signature_sets(sets)
            except Exception as e:  # device/compile failure — degrade
                if not self.fallback:
                    raise
                self._note_device_fallback(e)
                log.warning("TPU verify failed (%s); host fallback", e)
            return _host_verify(sets)
        if self.backend == "native":
            try:
                from . import native_bls

                return native_bls.verify_signature_sets(sets)
            except Exception as e:
                if not self.fallback:
                    raise
                metrics.HOST_BACKEND_FALLBACKS.inc()
                log.warning("native verify failed (%s); oracle fallback", e)
        from .ref import bls as RB

        return RB.verify_signature_sets(sets)

    def verify_signature_sets_per_set(self, sets, priority=None) -> list:
        sets = list(sets)
        if self.backend == "fake":
            return [True] * len(sets)
        if self.backend == "tpu":
            try:
                from .tpu import bls as tb

                return tb.verify_signature_sets_per_set(sets)
            except Exception as e:
                if not self.fallback:
                    raise
                self._note_device_fallback(e)
                log.warning("TPU per-set verify failed (%s); host fallback", e)
            return _host_per_set(sets)
        if self.backend == "native":
            try:
                from . import native_bls

                return native_bls.verify_signature_sets_per_set(sets)
            except Exception as e:
                if not self.fallback:
                    raise
                metrics.HOST_BACKEND_FALLBACKS.inc()
                log.warning("native per-set failed (%s); oracle fallback", e)
        from .ref import bls as RB

        return [RB.verify_signature_sets([s]) for s in sets]
