"""verify_service: cross-caller continuous batching.

Covers the ISSUE acceptance criteria: concurrent single-set submitters
coalesce into device-sized batches (mean dispatched batch >= 32 with 8
submitters), a poisoned set fails only its own submitter's future,
deadline-driven dispatch for sub-target batches, circuit-breaker
trip/recover, queue-overflow admission control, and the drop-in
SignatureVerifier compat surface (including node wiring).
"""

import threading
import time
from types import SimpleNamespace

import pytest

from lighthouse_tpu.crypto.backend import SignatureVerifier
from lighthouse_tpu.verify_service import (
    QueueFullError,
    VerificationService,
)
from lighthouse_tpu.verify_service.circuit import CLOSED, HALF_OPEN, OPEN


def mk(poison=False):
    return SimpleNamespace(poison=poison)


class StubVerifier:
    """Backend-seam double: opaque sets with a `poison` mark; records
    every dispatched batch in order."""

    backend = "stub"

    def __init__(self, latency=0.0, gate=None):
        self.batches = []
        self.latency = latency
        self.gate = gate            # one-shot: only the first call waits
        self.on_device_fallback = None

    def verify_signature_sets(self, sets, priority=None):
        sets = list(sets)
        gate, self.gate = self.gate, None
        if gate is not None:
            gate.wait(10.0)
        if self.latency:
            time.sleep(self.latency)
        self.batches.append(sets)
        return all(not getattr(s, "poison", False) for s in sets)

    def verify_signature_sets_per_set(self, sets, priority=None):
        sets = list(sets)
        self.batches.append(sets)
        return [not getattr(s, "poison", False) for s in sets]


def _submit_from_threads(service, n_threads=8, per_thread=64, poison_at=None):
    """Each thread offers `per_thread` single-set requests as fast as it
    can (futures collected, awaited afterwards).  `poison_at`: (thread,
    index) whose set is poisoned."""
    futures = [[] for _ in range(n_threads)]
    sets = [[] for _ in range(n_threads)]

    def run(i):
        for j in range(per_thread):
            s = mk(poison=(poison_at == (i, j)))
            sets[i].append(s)
            futures[i].append(service.submit([s]))

    threads = [threading.Thread(target=run, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return futures, sets


def test_coalesces_concurrent_submitters_to_device_sized_batches():
    stub = StubVerifier(latency=0.002)
    service = VerificationService(stub, target_batch=64, max_batch=512)
    futures, _ = _submit_from_threads(service, n_threads=8, per_thread=64)
    results = [f.result(timeout=30.0) for fl in futures for f in fl]
    assert all(results)
    batches = list(service.dispatched_batches)
    assert sum(batches) == 8 * 64
    mean = sum(batches) / len(batches)
    # the acceptance bar: single-set offers from 8 threads must land in
    # device-sized batches, not 512 singleton dispatches
    assert mean >= 32, f"mean dispatched batch {mean} < 32 ({batches})"
    assert service.stats()["mean_batch_sets"] == pytest.approx(mean)
    service.stop()


def test_poisoned_set_fails_only_its_submitter():
    stub = StubVerifier(latency=0.002)
    service = VerificationService(stub, target_batch=64, max_batch=512)
    futures, _ = _submit_from_threads(
        service, n_threads=8, per_thread=32, poison_at=(3, 7)
    )
    for i, fl in enumerate(futures):
        for j, f in enumerate(fl):
            expected = not (i == 3 and j == 7)
            assert f.result(timeout=30.0) is expected, (i, j)
    service.stop()


def test_blocking_wrappers_are_drop_in():
    stub = StubVerifier()
    service = VerificationService(stub)
    good, bad = mk(), mk(poison=True)
    assert service.verify_signature_sets([good]) is True
    assert service.verify_signature_sets([good, bad]) is False
    assert service.verify_signature_sets_per_set([good, bad, good]) == [
        True, False, True,
    ]
    assert service.backend == "stub"
    service.stop()


def test_deadline_dispatches_sub_target_batches():
    stub = StubVerifier()
    # target far above anything submitted: only the deadline can fire
    service = VerificationService(stub, target_batch=10**6)
    t0 = time.monotonic()
    fut = service.submit([mk()], deadline=0.05)
    assert fut.result(timeout=10.0) is True
    elapsed = time.monotonic() - t0
    assert 0.03 <= elapsed < 5.0, elapsed
    assert list(service.dispatched_batches) == [1]
    service.stop()


def test_priority_classes_drain_blocks_first():
    gate = threading.Event()
    stub = StubVerifier(gate=gate)
    service = VerificationService(stub, target_batch=1)
    gating = service.submit([mk()], priority="discovery")
    # dispatcher is now parked inside verify(); queue both classes behind it
    time.sleep(0.05)
    disc_set, block_set = mk(), mk()
    f_disc = service.submit([disc_set], priority="discovery")
    f_block = service.submit([block_set], priority="block")
    gate.set()
    assert gating.result(timeout=10.0) and f_disc.result(timeout=10.0)
    assert f_block.result(timeout=10.0)
    merged = stub.batches[1]
    pos = {id(s): i for i, s in enumerate(merged)}
    assert pos[id(block_set)] < pos[id(disc_set)]
    service.stop()


def test_queue_overflow_admission_control():
    gate = threading.Event()
    stub = StubVerifier(gate=gate)
    service = VerificationService(
        stub, target_batch=1, queue_caps={"attestation": 4}
    )
    service.submit([mk()])          # occupies the dispatcher (gated)
    time.sleep(0.05)
    accepted = 0
    with pytest.raises(QueueFullError):
        for _ in range(10):
            service.submit([mk()])
            accepted += 1
    assert accepted == 4
    # the blocking compat wrapper degrades to a direct backend call
    # instead of failing admitted-but-unverifiable work
    assert service.verify_signature_sets([mk()]) is True
    gate.set()
    service.stop()


class FlakyDeviceVerifier(StubVerifier):
    """Device-backed seam double: while `broken`, every verify degrades
    internally (and reports it through on_device_fallback), exactly like
    SignatureVerifier's tpu->host fallback."""

    backend = "tpu"

    def __init__(self):
        super().__init__()
        self.broken = True
        self.calls = 0

    def verify_signature_sets(self, sets, priority=None):
        self.calls += 1
        if self.broken and self.on_device_fallback is not None:
            self.on_device_fallback(RuntimeError("device error"))
        return super().verify_signature_sets(sets, priority)


def test_circuit_breaker_trips_and_recovers():
    device = FlakyDeviceVerifier()
    host = StubVerifier()
    service = VerificationService(
        device, host_verifier=host,
        breaker_threshold=2, breaker_cooldown=0.2,
    )

    def one(prio="attestation"):
        return service.submit([mk()], deadline=0.001).result(timeout=10.0)

    assert one() is True                      # failure 1 (still closed)
    assert one() is True                      # failure 2 -> trips OPEN
    assert service.breaker.state == OPEN
    calls_when_open = device.calls
    assert one() is True                      # pinned to host
    assert device.calls == calls_when_open
    assert len(host.batches) == 1

    time.sleep(0.25)                          # cooldown elapses
    device.broken = False
    assert one() is True                      # half-open probe succeeds
    assert service.breaker.state == CLOSED
    assert device.calls == calls_when_open + 1
    service.stop()


def test_circuit_breaker_reopens_on_failed_probe():
    device = FlakyDeviceVerifier()
    host = StubVerifier()
    service = VerificationService(
        device, host_verifier=host,
        breaker_threshold=1, breaker_cooldown=0.1,
    )
    assert service.submit([mk()], deadline=0.001).result(10.0) is True
    assert service.breaker.state == OPEN
    time.sleep(0.15)
    # probe goes to the (still broken) device: breaker must reopen at once
    assert service.submit([mk()], deadline=0.001).result(10.0) is True
    assert service.breaker.state == OPEN
    service.stop()


def test_executor_shutdown_never_strands_submitters():
    """After the supervising executor shuts the dispatcher down, the
    blocking wrappers must answer through the bare seam — not hang on a
    queue nobody drains."""
    from lighthouse_tpu.utils.task_executor import TaskExecutor

    stub = StubVerifier()
    service = VerificationService(stub)
    executor = TaskExecutor()
    service.start(executor)
    assert service.verify_signature_sets([mk()]) is True
    executor.shutdown("test shutdown")
    time.sleep(0.4)   # let the dispatcher notice (0.25 s wait cap) and exit
    t0 = time.monotonic()
    assert service.verify_signature_sets([mk()]) is True
    assert service.verify_signature_sets_per_set([mk()]) == [True]
    assert time.monotonic() - t0 < 2.0


def test_empty_and_stopped_delegate_to_seam():
    fake = SignatureVerifier("fake")
    service = VerificationService(fake)
    assert service.verify_signature_sets([]) is True          # fake semantics
    assert service.verify_signature_sets_per_set([]) == []
    service.stop()
    # a stopped service still answers through the bare seam
    assert service.verify_signature_sets([mk()]) is True
    assert service.verify_signature_sets_per_set([mk()]) == [True]


def test_real_oracle_seam_roundtrip():
    from lighthouse_tpu.crypto.ref import bls as RB

    sk, msg = 4242, b"\x05" * 32
    good = RB.SignatureSet(RB.sign(sk, msg), [RB.sk_to_pk(sk)], msg)
    bad = RB.SignatureSet(RB.sign(sk, msg), [RB.sk_to_pk(sk + 1)], msg)
    service = VerificationService(SignatureVerifier("oracle"))
    assert service.verify_signature_sets([good], priority="block") is True
    assert service.verify_signature_sets([bad]) is False
    service.stop()


def test_chain_block_import_through_service():
    """The rewired L4 path end-to-end: a real block's gossip + bulk
    verification routed through the service's micro-batcher over the
    oracle backend."""
    from lighthouse_tpu.beacon.chain import BeaconChain
    from lighthouse_tpu.testing.harness import Harness
    from lighthouse_tpu.types import ChainSpec, MinimalPreset

    spec = ChainSpec(preset=MinimalPreset)
    h = Harness(16, spec)
    service = VerificationService(SignatureVerifier("oracle"))
    chain = BeaconChain(h.state.copy(), spec, verifier=service)
    block = h.produce_block(1)
    h.process_block(block, strategy="no_verification")
    chain.on_tick(1)
    root = chain.process_block(block)
    assert chain.head_root == root
    assert sum(service.dispatched_batches) >= 2   # proposer set + bulk batch
    service.stop()


def test_node_wiring_routes_through_service():
    from lighthouse_tpu.beacon.node import ClientBuilder
    from lighthouse_tpu.state_processing.genesis import (
        interop_genesis_state,
        interop_keypairs,
    )
    from lighthouse_tpu.types import ChainSpec, MinimalPreset

    spec = ChainSpec(preset=MinimalPreset)
    state = interop_genesis_state(interop_keypairs(4), 0, spec)
    node = (
        ClientBuilder(spec)
        .genesis_state(state)
        .memory_store()
        .crypto_backend("fake")
        .build()
    )
    try:
        assert isinstance(node.chain.verifier, VerificationService)
        assert node.chain.verifier.backend == "fake"
        assert node.chain.verifier.verify_signature_sets([mk()]) is True
    finally:
        node.stop()
