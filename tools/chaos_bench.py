"""Chaos bench: verification goodput and recovery time under injected faults.

Drives the VerificationService with N submitter threads at a target
offered load while the `device.execute_chunk` failpoint fails a
configurable fraction of device passes — the fault storm the breaker,
host fallback and half-open probe exist for — and reports, per point:

  * goodput_per_sec  — verdicts delivered per second UNDER the storm
    (every future must still resolve: lost work would show up as a
    submitted/resolved gap, reported separately)
  * breaker_trips    — how often the storm pinned the service to host
  * breaker_recovery_seconds — after the faults stop, the time from
    disarm until a half-open probe batch restores the breaker to CLOSED

The device backend is a latency-shaped stub wired through the SAME
failpoint the real kernel launch hits (crypto/tpu/bls.execute_chunk), so
the sweep measures the recovery machinery, not BLS math, and runs in
seconds.

`--remote` switches to the remote verification fabric: the same offered-
load sweep against a SIMULATED verifier pool (in-process transport,
latency-shaped backends) under injected per-call fault/partition rates,
reporting `remote_goodput` (verdicts/s with the remote tier first),
`failover_seconds` (all targets die -> time until the next batch
resolves on the local tiers) and `audit_catch_rate` (fraction of lying-
verifier batches the random-recombination spot-check catches).

Usage:
    python tools/chaos_bench.py
    python tools/chaos_bench.py --fault-rates 0.0,0.2,0.5 --duration 2
    python tools/chaos_bench.py --remote --fault-rates 0.0,0.3
"""

import argparse
import json
import os
import random
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lighthouse_tpu.utils import failpoints  # noqa: E402
from lighthouse_tpu.verify_service import (  # noqa: E402
    InProcessTransport,
    RemoteVerifierPool,
    VerificationService,
)
from lighthouse_tpu.verify_service.circuit import CLOSED  # noqa: E402


class StubSet:
    """Opaque token standing in for a SignatureSet."""

    __slots__ = ()


class FaultyDeviceVerifier:
    """Device-shaped seam double wired through the `device.execute_chunk`
    failpoint: an injected fault degrades the call internally to the
    host-cost path and reports through on_device_fallback — exactly the
    observable behavior of SignatureVerifier's tpu→host fallback chain
    when the real kernel launch raises."""

    backend = "tpu"

    def __init__(self, fixed_ms=1.0, per_set_us=10.0, host_penalty=2.0,
                 chunk=32):
        self.fixed_s = fixed_ms / 1e3
        self.per_set_s = per_set_us / 1e6
        self.host_penalty = host_penalty
        self.chunk = max(1, int(chunk))
        self.on_device_fallback = None
        self.device_calls = 0
        self.faults = 0

    def _verify(self, sets):
        for i in range(0, max(len(sets), 1), self.chunk):
            n = len(sets[i:i + self.chunk])
            self.device_calls += 1
            cost = self.fixed_s + self.per_set_s * n
            try:
                failpoints.hit("device.execute_chunk")
            except failpoints.FailpointError as e:
                self.faults += 1
                cost *= self.host_penalty       # degraded host pass
                if self.on_device_fallback is not None:
                    self.on_device_fallback(e)
            time.sleep(cost)
        return True

    def verify_signature_sets(self, sets, priority=None):
        return self._verify(list(sets))

    def verify_signature_sets_per_set(self, sets, priority=None):
        sets = list(sets)
        self._verify(sets)
        return [True] * len(sets)


class HostVerifier(FaultyDeviceVerifier):
    """The breaker's pinned host path: same cost model at the host
    penalty, never touching the failpoint."""

    backend = "native"

    def _verify(self, sets):
        for i in range(0, max(len(sets), 1), self.chunk):
            n = len(sets[i:i + self.chunk])
            time.sleep((self.fixed_s + self.per_set_s * n)
                       * self.host_penalty)
        return True


def run_chaos_point(fault_rate=0.2, submitters=8, offered_rps=2000.0,
                    duration=1.5, seed=1234, target_batch=64,
                    breaker_threshold=3, breaker_cooldown=0.2,
                    recovery_timeout=10.0):
    """One storm + recovery measurement; returns a flat dict."""
    failpoints.seed_all(seed)
    device = FaultyDeviceVerifier()
    service = VerificationService(
        device, host_verifier=HostVerifier(),
        target_batch=target_batch,
        breaker_threshold=breaker_threshold,
        breaker_cooldown=breaker_cooldown,
    )
    failpoints.configure(
        "device.execute_chunk",
        f"error({fault_rate})" if fault_rate < 1.0 else "error",
    )
    if fault_rate <= 0.0:
        failpoints.configure("device.execute_chunk", "off")

    per_thread = offered_rps / submitters
    interval = 1.0 / per_thread if per_thread > 0 else 0.0
    stop_at = time.monotonic() + duration
    futures = [[] for _ in range(submitters)]
    rejected = [0] * submitters

    def submitter(i):
        nxt = time.monotonic()
        while time.monotonic() < stop_at:
            try:
                futures[i].append(service.submit([StubSet()]))
            except Exception:
                rejected[i] += 1
            nxt += interval
            delay = nxt - time.monotonic()
            if delay > 0:
                time.sleep(delay)

    t0 = time.monotonic()
    threads = [threading.Thread(target=submitter, args=(i,), daemon=True)
               for i in range(submitters)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    resolved = ok = 0
    for fl in futures:
        for f in fl:
            try:
                if f.result(timeout=30.0):
                    ok += 1
                resolved += 1
            except TimeoutError:
                pass                # LOST: the verdict never arrived
            except Exception:
                resolved += 1       # an errored future still RESOLVED
    wall = time.monotonic() - t0
    submitted = sum(len(fl) for fl in futures)
    trips = service.breaker.trips
    state_after_storm = service.breaker.state

    # recovery: faults off; keep offering probe ticks until the breaker's
    # half-open probe restores CLOSED
    failpoints.configure("device.execute_chunk", "off")
    recovery_s = 0.0
    if service.breaker.state != CLOSED:
        r0 = time.monotonic()
        while (service.breaker.state != CLOSED
               and time.monotonic() - r0 < recovery_timeout):
            try:
                service.submit([StubSet()], deadline=0.001).result(5.0)
            except Exception:
                pass
            time.sleep(0.02)
        recovery_s = time.monotonic() - r0
    recovered = service.breaker.state == CLOSED
    service.stop()
    return {
        "fault_rate": fault_rate,
        "offered_rps": offered_rps,
        "submitters": submitters,
        "submitted": submitted,
        "rejected": sum(rejected),
        "resolved": resolved,
        "lost": submitted - resolved,
        "verified_ok": ok,
        "goodput_per_sec": round(ok / wall, 1) if wall > 0 else 0.0,
        "device_faults": device.faults,
        "breaker_trips": trips,
        "breaker_state_after_storm": state_after_storm,
        "breaker_recovery_seconds": round(recovery_s, 3),
        "breaker_recovered": recovered,
    }


def measure_breaker_recovery(seed=1234, breaker_threshold=2,
                             breaker_cooldown=0.2, timeout=10.0):
    """Deterministic trip→half-open-probe→restore measurement: force the
    breaker OPEN with a 100% device fault, disarm, and time how long the
    cooldown + bounded probe take to restore CLOSED.  The number the
    bench artifact records as `breaker_recovery_seconds`."""
    failpoints.seed_all(seed)
    device = FaultyDeviceVerifier()
    service = VerificationService(
        device, host_verifier=HostVerifier(), target_batch=8,
        breaker_threshold=breaker_threshold,
        breaker_cooldown=breaker_cooldown,
    )
    try:
        failpoints.configure("device.execute_chunk", "error")
        deadline = time.monotonic() + timeout
        while (service.breaker.trips == 0
               and time.monotonic() < deadline):
            service.submit([StubSet()], deadline=0.001).result(5.0)
        failpoints.configure("device.execute_chunk", "off")
        t0 = time.monotonic()
        while (service.breaker.state != CLOSED
               and time.monotonic() - t0 < timeout):
            try:
                service.submit([StubSet()], deadline=0.001).result(5.0)
            except Exception:
                pass
            time.sleep(0.02)
        recovery = time.monotonic() - t0
    finally:
        failpoints.configure("device.execute_chunk", "off")
        service.stop()
    return {
        "breaker_cooldown_seconds": breaker_cooldown,
        "breaker_trips": service.breaker.trips,
        "breaker_recovery_seconds": round(recovery, 3),
        "breaker_recovered": service.breaker.state == CLOSED,
    }


# --------------------------------------------------------- remote mode


class TruthVerifier:
    """Audit truth source for the simulated fabric: every StubSet is
    valid (so any False verdict from a lying backend is a catch)."""

    backend = "native"

    def verify_signature_sets(self, sets, priority=None):
        return True

    def verify_signature_sets_per_set(self, sets, priority=None):
        return [True] * len(sets)


class SimRemoteBackend:
    """One simulated verifier host behind InProcessTransport: latency-
    shaped, failing a configurable fraction of calls (the fault/partition
    injection), optionally lying (inverted verdicts) for the audit-catch
    measurement."""

    def __init__(self, rng, fault_rate=0.0, latency_ms=2.0, lie=False):
        self._rng = rng
        self.fault_rate = fault_rate
        self.latency_s = latency_ms / 1e3
        self.lie = lie
        self.calls = 0
        self.faults = 0

    def __call__(self, sets, priority, deadline_s):
        self.calls += 1
        if self._rng.random() < self.fault_rate:
            self.faults += 1
            raise OSError("injected remote fault")
        time.sleep(self.latency_s)
        verdicts = [not self.lie] * len(sets)
        return verdicts, 0


def _build_remote_service(backends, seed, hedge_budget, audit_rate,
                          breaker_cooldown=0.2, target_batch=16):
    pool = RemoteVerifierPool(
        list(backends), InProcessTransport(backends),
        audit_verifier=TruthVerifier(), audit_rate=audit_rate,
        hedge_budget=hedge_budget, breaker_cooldown=breaker_cooldown,
        rng=random.Random(seed),
    )
    service = VerificationService(
        HostVerifier(), target_batch=target_batch, remote_pool=pool
    )
    return service, pool


def run_remote_point(fault_rate=0.2, submitters=4, offered_rps=500.0,
                     duration=1.5, seed=1234, n_targets=2,
                     hedge_budget=0.05, audit_rate=0.1, latency_ms=2.0,
                     failover_timeout=10.0):
    """One remote-fabric storm point: offered load with the remote tier
    first while each simulated target drops `fault_rate` of its calls;
    then kill EVERY target and time the failover to the local tiers."""
    backends = {
        f"sim{i}": SimRemoteBackend(
            random.Random(f"{seed}:{i}"), fault_rate, latency_ms
        )
        for i in range(n_targets)
    }
    service, pool = _build_remote_service(
        backends, seed, hedge_budget, audit_rate
    )

    per_thread = offered_rps / submitters
    interval = 1.0 / per_thread if per_thread > 0 else 0.0
    stop_at = time.monotonic() + duration
    futures = [[] for _ in range(submitters)]
    rejected = [0] * submitters

    def submitter(i):
        nxt = time.monotonic()
        while time.monotonic() < stop_at:
            try:
                futures[i].append(service.submit([StubSet()]))
            except Exception:
                rejected[i] += 1
            nxt += interval
            delay = nxt - time.monotonic()
            if delay > 0:
                time.sleep(delay)

    t0 = time.monotonic()
    threads = [threading.Thread(target=submitter, args=(i,), daemon=True)
               for i in range(submitters)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    resolved = ok = 0
    for fl in futures:
        for f in fl:
            try:
                if f.result(timeout=30.0):
                    ok += 1
                resolved += 1
            except TimeoutError:
                pass                # LOST: the verdict never arrived
            except Exception:
                resolved += 1
    wall = time.monotonic() - t0
    submitted = sum(len(fl) for fl in futures)
    snap = pool.snapshot()

    # failover: every remote target dies; time from the kill until one
    # subsequent submit resolves (on the local tiers, breakers tripping
    # along the way)
    for b in backends.values():
        b.fault_rate = 1.0
    f0 = time.monotonic()
    try:
        assert service.submit([StubSet()]).result(timeout=failover_timeout)
        failover_s = time.monotonic() - f0
        failed_over = True
    except Exception:
        failover_s = failover_timeout
        failed_over = False
    service.stop()
    pool.stop()
    return {
        "fault_rate": fault_rate,
        "offered_rps": offered_rps,
        "n_targets": n_targets,
        "submitted": submitted,
        "rejected": sum(rejected),
        "resolved": resolved,
        "lost": submitted - resolved,
        "verified_ok": ok,
        "remote_goodput": round(ok / wall, 1) if wall > 0 else 0.0,
        "remote_batches": snap["jobs_remote"],
        "local_batches": snap["jobs_local"],
        "hedges": snap["hedges"],
        "failover_seconds": round(failover_s, 3),
        "failed_over": failed_over,
    }


def measure_audit_catch(seed=1234, rounds=8):
    """Lying-verifier detection rate: a backend inverting every verdict,
    audited on every batch (a fresh pool per round — a caught target is
    quarantined, which would otherwise end the experiment after one)."""
    catches = 0
    for r in range(rounds):
        backends = {"liar": SimRemoteBackend(
            random.Random(f"{seed}:liar:{r}"), 0.0, 1.0, lie=True
        )}
        pool = RemoteVerifierPool(
            ["liar"], InProcessTransport(backends),
            audit_verifier=TruthVerifier(), audit_rate=1.0,
            hedge_budget=0.05, rng=random.Random(seed + r),
        )
        out = pool.verify_batch([StubSet() for _ in range(4)])
        snap = pool.snapshot()
        # a caught lie returns None (local re-verify) and quarantines
        if out is None and snap["audit_catches"] >= 1:
            catches += 1
        pool.stop()
    return {
        "lying_batches": rounds,
        "caught": catches,
        "audit_catch_rate": round(catches / rounds, 3) if rounds else 0.0,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fault-rates", default="0.0,0.2,0.5",
                    help="comma-separated device fault probabilities")
    ap.add_argument("--offered-rps", type=float, default=2000.0)
    ap.add_argument("--submitters", type=int, default=8)
    ap.add_argument("--duration", type=float, default=1.5,
                    help="seconds per storm point")
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--target-batch", type=int, default=64)
    ap.add_argument("--remote", action="store_true",
                    help="sweep the remote verification fabric instead "
                         "of the local device storm")
    ap.add_argument("--n-targets", type=int, default=2,
                    help="simulated verifier hosts (--remote)")
    args = ap.parse_args(argv)

    points = []
    try:
        if args.remote:
            for rate in (float(r) for r in args.fault_rates.split(",")):
                pt = run_remote_point(
                    fault_rate=rate, submitters=args.submitters,
                    offered_rps=args.offered_rps, duration=args.duration,
                    seed=args.seed, n_targets=args.n_targets,
                )
                points.append(pt)
                print(json.dumps(pt), flush=True)
            recovery = measure_audit_catch(seed=args.seed)
            print(json.dumps(recovery), flush=True)
        else:
            for rate in (float(r) for r in args.fault_rates.split(",")):
                pt = run_chaos_point(
                    fault_rate=rate, submitters=args.submitters,
                    offered_rps=args.offered_rps, duration=args.duration,
                    seed=args.seed, target_batch=args.target_batch,
                )
                points.append(pt)
                print(json.dumps(pt), flush=True)
            recovery = measure_breaker_recovery(seed=args.seed)
            print(json.dumps(recovery), flush=True)
    finally:
        failpoints.reset()
    print(json.dumps(
        {"tool": "chaos_bench", "points": points, "recovery": recovery}
    ))
    # a lost verdict is a harness failure, not a data point
    return 1 if any(pt["lost"] for pt in points) else 0


if __name__ == "__main__":
    sys.exit(main())
