"""Failpoints + supervised recovery: the ISSUE 5 chaos acceptance.

Covers the fault-injection registry itself (modes, env syntax, seeded
determinism), the injection seams (device kernel launch, verify
dispatcher/prep, store put/compact, eth1 + engine RPC, wire req/resp),
the recovery layers built on top (shared retries, breaker half-open
bounded probe, watchdog restart with queues intact, compaction crash
safety), the /lighthouse/failpoints HTTP control surface, and the
chaos-storm acceptance: under 20% device faults + engine delay + one
store panic, zero verdicts are lost, every submitted set resolves, and
the breaker trips -> half-open-probes -> restores.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request
from types import SimpleNamespace

import pytest

from lighthouse_tpu.utils import failpoints
from lighthouse_tpu.utils import logging as ltpu_logging
from lighthouse_tpu.utils import metrics
from lighthouse_tpu.utils.retries import RetryPolicy
from lighthouse_tpu.utils.watchdog import Watchdog
from lighthouse_tpu.verify_service import VerificationService
from lighthouse_tpu.verify_service.circuit import CLOSED, HALF_OPEN, OPEN


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.reset()
    yield
    failpoints.reset()


def mk():
    return SimpleNamespace(poison=False)


class RecordingDevice:
    """Device-backed seam double: records every dispatched batch size;
    while `broken`, reports an internal device→host degrade through
    on_device_fallback (SignatureVerifier's tpu fallback observable)."""

    backend = "tpu"

    def __init__(self):
        self.batches = []
        self.broken = False
        self.on_device_fallback = None

    def verify_signature_sets(self, sets, priority=None):
        sets = list(sets)
        self.batches.append(len(sets))
        if self.broken and self.on_device_fallback is not None:
            self.on_device_fallback(RuntimeError("device error"))
        return True

    def verify_signature_sets_per_set(self, sets, priority=None):
        self.verify_signature_sets(sets)
        return [True] * len(list(sets))


class RecordingHost(RecordingDevice):
    backend = "native"

    def __init__(self):
        super().__init__()
        self.broken = False


# ------------------------------------------------------------- registry


def test_mode_parsing_and_env_syntax():
    fp = failpoints.configure("eth1.rpc", "error(0.25)")
    assert fp.mode == "error" and fp.arg == 0.25
    assert fp.spec() == "error(0.25)"
    assert failpoints.configure("eth1.rpc", "delay(50)").spec() == "delay(50)"
    assert failpoints.configure("eth1.rpc", "off").mode == "off"
    assert failpoints.parse_env(
        "store.put=corrupt; engine.rpc=delay(5),wire.rpc=error"
    ) == {"store.put": "corrupt", "engine.rpc": "delay(5)",
          "wire.rpc": "error"}
    for bad in ("bogus", "error(2.0)", "delay(-1)", "delay", "error(x)",
                "error(", "panic_once(0.5)", "off(1)"):
        with pytest.raises(ValueError):
            failpoints.configure("eth1.rpc", bad)
    with pytest.raises(ValueError):
        failpoints.parse_env("no-equals-sign")
    # snapshot lists every declared site with counters
    snap = failpoints.snapshot()
    assert "device.execute_chunk" in snap and "store.compact" in snap
    assert snap["device.execute_chunk"]["mode"] == "off"


def test_env_arming_validates_atomically(monkeypatch):
    # a bad spec mid-list arms NOTHING (the PATCH route's contract)
    monkeypatch.setenv("LTPU_FAILPOINTS", "store.put=error;engine.rpc=junk(5)")
    failpoints._load_env()
    assert failpoints.get("store.put").mode == "off"
    # a typo'd name must not mint a never-firing failpoint
    monkeypatch.setenv("LTPU_FAILPOINTS", "device.exec_chunk=error")
    failpoints._load_env()
    assert failpoints.get("device.exec_chunk") is None
    # a valid storm arms
    monkeypatch.setenv("LTPU_FAILPOINTS",
                       "store.put=delay(1);wire.rpc=error(0.5)")
    failpoints._load_env()
    assert failpoints.get("store.put").spec() == "delay(1)"
    assert failpoints.get("wire.rpc").spec() == "error(0.5)"


def test_stop_terminates_dispatcher_under_armed_error():
    """service.stop() must end the dispatcher loop even while
    verify.dispatch=error fires every iteration — the fault arm falls
    through to the canonical stopping exit."""

    class StubOK:
        backend = "stub"
        on_device_fallback = None

        def verify_signature_sets(self, sets, priority=None):
            return True

        def verify_signature_sets_per_set(self, sets, priority=None):
            return [True] * len(list(sets))

    service = VerificationService(StubOK(), target_batch=10**6)
    service.submit([mk()], deadline=30.0)       # starts the dispatcher
    thread = service._thread
    failpoints.configure("verify.dispatch", "error")
    time.sleep(0.05)                            # loop is in the fault arm
    service.stop()
    thread.join(timeout=5.0)
    assert not thread.is_alive()


def test_error_probability_is_seed_deterministic():
    def storm():
        failpoints.seed_all(99)
        failpoints.configure("wire.rpc", "error(0.3)")
        fired = []
        for _ in range(200):
            try:
                failpoints.hit("wire.rpc")
                fired.append(False)
            except failpoints.FailpointError:
                fired.append(True)
        failpoints.configure("wire.rpc", "off")
        return fired

    a, b = storm(), storm()
    assert a == b                       # reproducible storm
    assert 20 < sum(a) < 100            # ~30% of 200


def test_panic_once_fires_once_then_disarms():
    failpoints.configure("store.compact", "panic_once")
    with pytest.raises(failpoints.FailpointPanic):
        failpoints.hit("store.compact")
    assert failpoints.get("store.compact").mode == "off"
    failpoints.hit("store.compact")     # now inert
    assert failpoints.get("store.compact").fired == 1


def test_corrupt_mode_flips_payload_bytes():
    failpoints.configure("store.put", "corrupt")
    blob = bytes(range(16))
    out = failpoints.hit("store.put", data=blob)
    assert out != blob and len(out) == len(blob)
    failpoints.configure("store.put", "off")
    assert failpoints.hit("store.put", data=blob) == blob


# -------------------------------------------------------------- retries


def test_retry_policy_backoff_jitter_and_reraise():
    sleeps = []
    calls = [0]
    pol = RetryPolicy(attempts=4, base_delay=0.1, max_delay=0.3,
                      deadline=100.0, retry_on=(ValueError,),
                      sleep=sleeps.append, rng=lambda: 1.0)

    def flaky():
        calls[0] += 1
        if calls[0] < 3:
            raise ValueError("transient")
        return 42

    assert pol.call(flaky, target="t_retry") == 42
    # full jitter at rng=1.0: min(max_delay, base * 2^attempt)
    assert sleeps == [0.1, 0.2]

    def always():
        raise ValueError("permanent")

    pol2 = RetryPolicy(attempts=2, base_delay=0.0, retry_on=(ValueError,),
                       sleep=lambda s: None)
    with pytest.raises(ValueError):     # the ORIGINAL class re-raises
        pol2.call(always, target="t_retry")

    # non-retryable exceptions propagate on the first raise
    first = [0]

    def wrong_kind():
        first[0] += 1
        raise KeyError("nope")

    with pytest.raises(KeyError):
        pol.call(wrong_kind, target="t_retry")
    assert first[0] == 1

    # a backoff that would cross the deadline gives up immediately
    now = [0.0]
    pol3 = RetryPolicy(attempts=10, base_delay=5.0, max_delay=5.0,
                       deadline=1.0, retry_on=(ValueError,),
                       sleep=lambda s: None, clock=lambda: now[0],
                       rng=lambda: 1.0)
    tries = [0]

    def tick():
        tries[0] += 1
        raise ValueError("slow upstream")

    with pytest.raises(ValueError):
        pol3.call(tick, target="t_retry")
    assert tries[0] == 1


def test_eth1_rpc_failpoint_heals_through_retries():
    from lighthouse_tpu.eth1.service import Eth1Cache, MockEth1Chain

    chain = MockEth1Chain()
    chain.mine_blocks(3)
    cache = Eth1Cache(chain, follow_distance=0, retries=RetryPolicy(
        attempts=3, base_delay=0.0, max_delay=0.0, deadline=5.0,
        retry_on=(failpoints.FailpointError,), sleep=lambda s: None,
    ))
    failpoints.configure("eth1.rpc", "panic_once")
    blk = cache.head_block()            # first attempt panics, retry wins
    assert blk.number == 3
    assert failpoints.get("eth1.rpc").fired == 1
    failpoints.configure("eth1.rpc", "error")
    with pytest.raises(failpoints.FailpointError):   # exhausted: re-raised
        cache.head_block()
    failpoints.configure("eth1.rpc", "off")
    assert cache.head_block().number == 3


def test_engine_transport_faults_retry_and_reraise():
    from lighthouse_tpu.execution.engine_http import (
        EngineApiError,
        EngineTransportError,
        HttpJsonRpcClient,
    )

    sleeps = []
    client = HttpJsonRpcClient(
        "http://127.0.0.1:1", b"\x00" * 32, timeout=0.2,
        retries=RetryPolicy(attempts=3, base_delay=0.001, max_delay=0.002,
                            deadline=10.0, retry_on=(EngineTransportError,),
                            sleep=sleeps.append),
    )
    with pytest.raises(EngineTransportError) as ei:
        client.call("engine_newPayloadV1", [])
    assert isinstance(ei.value, EngineApiError)      # compat subclass
    assert len(sleeps) == 2                          # 3 attempts, 2 backoffs

    # injected transport fault is retryable too
    sleeps.clear()
    failpoints.configure("engine.rpc", "error")
    with pytest.raises(EngineTransportError, match="injected"):
        client.call("engine_newPayloadV1", [])
    assert len(sleeps) == 2


# ------------------------------------------------------- injection seams


def test_device_execute_chunk_failpoint():
    from lighthouse_tpu.crypto.tpu import bls as tb

    c = tb.PreparedChunk()
    c.invalid = True
    c.n_sets = c.n_pad = 0
    c.args = None
    c.t_prep0 = c.t_prep1 = 0.0
    failpoints.configure("device.execute_chunk", "error")
    with pytest.raises(failpoints.FailpointError):
        tb.execute_chunk(c)             # fires BEFORE the launch path
    failpoints.configure("device.execute_chunk", "off")
    assert tb.execute_chunk(c) is False


def test_verify_prep_failpoint_falls_back_to_plain_path():
    class PipelineStub:
        backend = "stub"

        def __init__(self):
            self.plain_calls = 0
            self.pipeline_execs = 0
            self.on_device_fallback = None

        def plan_pipeline(self, sets):
            sets = list(sets)
            if len(sets) <= 2:
                return None
            chunks = [sets[i:i + 2] for i in range(0, len(sets), 2)]

            def prepare(chunk):
                return chunk

            def execute(prepared, overlap_ratio=None):
                self.pipeline_execs += 1
                return True

            return chunks, prepare, execute

        def verify_signature_sets(self, sets, priority=None):
            self.plain_calls += 1
            return True

        def verify_signature_sets_per_set(self, sets, priority=None):
            return [True] * len(list(sets))

    stub = PipelineStub()
    service = VerificationService(stub, target_batch=1)
    failpoints.configure("verify.prep", "error")
    # an injected prep fault aborts the pipeline; the batch must still
    # verify correctly through the plain path
    assert service.submit([mk() for _ in range(6)]).result(10.0) is True
    assert stub.plain_calls == 1 and stub.pipeline_execs == 0
    failpoints.configure("verify.prep", "off")
    assert service.submit([mk() for _ in range(6)]).result(10.0) is True
    assert stub.pipeline_execs >= 3 and stub.plain_calls == 1
    service.stop()


def test_store_put_corrupt_reaches_disk(tmp_path):
    from lighthouse_tpu.beacon.store import PyFileKV

    kv = PyFileKV(str(tmp_path / "c.log"))
    failpoints.configure("store.put", "corrupt")
    kv.put(b"k", bytes(8))
    failpoints.configure("store.put", "off")
    stored = kv.get(b"k")
    assert stored != bytes(8) and len(stored) == 8   # bit-rot injected
    kv.put(b"k", bytes(8))
    assert kv.get(b"k") == bytes(8)
    kv.close()


def test_store_compact_crash_safety(tmp_path, monkeypatch):
    """Satellite: a crash between the compaction write and the rename
    must never publish a torn file — the temp is fsynced (file AND
    directory) before os.replace, and a panic in that window leaves the
    original log fully live and the store usable."""
    from lighthouse_tpu.beacon.store import PyFileKV

    path = str(tmp_path / "db.log")
    kv = PyFileKV(path)
    for i in range(20):
        kv.put(f"k{i}".encode(), bytes([i]) * 100)
    for i in range(10):
        kv.delete(f"k{i}".encode())

    events = []
    real_fsync, real_replace = os.fsync, os.replace
    monkeypatch.setattr(
        os, "fsync", lambda fd: (events.append("fsync"), real_fsync(fd))[1]
    )
    monkeypatch.setattr(
        os, "replace",
        lambda a, b: (events.append("replace"), real_replace(a, b))[1],
    )
    failpoints.configure("store.compact", "panic_once")
    with pytest.raises(failpoints.FailpointPanic):
        kv.compact()
    # crash window: the temp was already durable, the rename never ran
    assert events.count("fsync") >= 2 and "replace" not in events
    # the original log is still live and the handle still usable
    assert kv.get(b"k15") == bytes([15]) * 100
    kv.put(b"extra", b"v")
    kv.compact()                        # the retried compaction succeeds
    assert events.index("replace") > 0
    assert events[: events.index("replace")].count("fsync") >= 2
    assert kv.get(b"k15") == bytes([15]) * 100
    assert kv.get(b"extra") == b"v"
    assert kv.get(b"k3") is None
    kv.close()
    reopened = PyFileKV(path)           # on-disk state replays cleanly
    assert reopened.get(b"k15") == bytes([15]) * 100
    assert reopened.get(b"extra") == b"v"
    reopened.close()


def test_store_fsync_policy_validation_and_env(tmp_path, monkeypatch):
    from lighthouse_tpu.beacon.store import PyFileKV

    with pytest.raises(ValueError, match="LTPU_STORE_FSYNC"):
        PyFileKV(str(tmp_path / "bad.log"), fsync_policy="sometimes")
    monkeypatch.setenv("LTPU_STORE_FSYNC", "always")
    kv = PyFileKV(str(tmp_path / "env.log"))
    assert kv.fsync_policy == "always"
    kv.close()


def test_store_fsync_always_syncs_every_put(tmp_path, monkeypatch):
    from lighthouse_tpu.beacon.store import PyFileKV

    kv = PyFileKV(str(tmp_path / "a.log"), fsync_policy="always")
    calls = []
    real_fsync = os.fsync
    monkeypatch.setattr(
        os, "fsync", lambda fd: (calls.append(fd), real_fsync(fd))[1]
    )
    for i in range(5):
        kv.put(f"k{i}".encode(), b"v")
    assert len(calls) == 5
    kv.delete(b"k0")
    assert len(calls) == 6                  # tombstones are durable too
    kv.close()


def test_store_fsync_group_commit_amortizes_a_burst(tmp_path, monkeypatch):
    """Satellite: group policy — a burst of puts inside one interval
    rides at most one fsync (after the interval-opening sync), close
    flushes the dirty window, and a `batch` is ONE sync no matter how
    many ops it carries."""
    from lighthouse_tpu.beacon.store import PyFileKV

    kv = PyFileKV(str(tmp_path / "g.log"), fsync_policy="group",
                  fsync_interval=3600.0)
    calls = []
    real_fsync = os.fsync
    monkeypatch.setattr(
        os, "fsync", lambda fd: (calls.append(fd), real_fsync(fd))[1]
    )
    for i in range(50):
        kv.put(f"k{i}".encode(), bytes(64))
    # monotonic >> _last_fsync==0 opens the interval on the first put;
    # the other 49 ride the window
    assert len(calls) == 1
    assert kv._dirty
    n0 = len(calls)
    kv.batch([("put", f"b{i}".encode(), b"v") for i in range(10)])
    assert len(calls) == n0                 # window still open: no sync
    kv.close()                              # dirty window flushed on close
    assert len(calls) >= n0 + 1

    # under `always`, a 10-op batch is still ONE group-committed sync
    kv2 = PyFileKV(str(tmp_path / "b.log"), fsync_policy="always")
    calls.clear()
    kv2.batch([("put", f"c{i}".encode(), b"v") for i in range(10)])
    assert len(calls) == 1
    kv2.close()


def test_store_fsync_group_straggler_timer_bounds_idle_tail(tmp_path):
    """A write landing inside the group window must become durable
    within one interval even when NO later write arrives to piggyback
    the sync on — the one-shot straggler timer fires."""
    from lighthouse_tpu.beacon.store import PyFileKV

    kv = PyFileKV(str(tmp_path / "s.log"), fsync_policy="group",
                  fsync_interval=0.05)
    kv.put(b"a", b"1")              # opens the interval: synced
    kv.put(b"b", b"2")              # inside the window: buffered
    assert kv._dirty and kv._group_timer is not None
    deadline = time.monotonic() + 5.0
    while kv._dirty and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not kv._dirty, "straggler flush fired without another write"
    kv.close()


def test_store_fsync_group_crash_window_bounded(tmp_path):
    """Satellite crash-window proof: under `group`, losing everything
    after the last fsync leaves a log that replays cleanly to exactly
    the synced prefix — records before the sync survive, the unsynced
    tail is gone but never torn."""
    from lighthouse_tpu.beacon.store import PyFileKV

    path = str(tmp_path / "c.log")
    kv = PyFileKV(path, fsync_policy="group", fsync_interval=3600.0)
    kv.put(b"durable", b"A" * 100)          # opens the interval: synced
    synced_size = os.path.getsize(path)
    kv.put(b"window", b"B" * 100)           # buffered: inside the window
    # crash now: the OS never saw the tail (it sits in the user-space
    # append buffer) — simulate by truncating a copy to the synced size
    crashed = str(tmp_path / "crashed.log")
    with open(path, "rb") as f:
        blob = f.read(synced_size)
    with open(crashed, "wb") as f:
        f.write(blob)
    kv.close()

    survivor = PyFileKV(crashed, fsync_policy="group")
    assert survivor.get(b"durable") == b"A" * 100
    assert survivor.get(b"window") is None  # lost, bounded by the window
    survivor.put(b"new", b"v")              # and the log is still usable
    assert survivor.get(b"new") == b"v"
    survivor.close()

    # a torn half-record past the synced prefix replays to the same state
    torn = str(tmp_path / "torn.log")
    with open(torn, "wb") as f:
        f.write(blob + b"\x07\x00\x00\x00")  # 4 of 8 header bytes
    reopened = PyFileKV(torn)
    assert reopened.get(b"durable") == b"A" * 100
    reopened.close()


def test_wire_reqresp_failpoints():
    from lighthouse_tpu.network.wire import WireError, WireNode

    a, b = WireNode(), WireNode()
    try:
        pid = a.dial("127.0.0.1", b.port)
        failpoints.configure("wire.rpc", "error")
        with pytest.raises(WireError, match="injected"):
            a.request_status(pid)
        failpoints.configure("wire.rpc", "off")
        assert a.request_status(pid) is not None
        # server-side fault surfaces to the client as R_SERVER_ERROR
        failpoints.configure("wire.serve", "error")
        with pytest.raises(WireError):
            a.request_status(pid)
        failpoints.configure("wire.serve", "off")
        assert a.request_status(pid) is not None
    finally:
        a.stop()
        b.stop()


# ---------------------------------------------- breaker half-open probe


def test_half_open_probe_is_bounded_and_restores():
    device, host = RecordingDevice(), RecordingHost()
    service = VerificationService(
        device, host_verifier=host, breaker_threshold=1,
        breaker_cooldown=0.1, breaker_probe_max=4, target_batch=10**6,
    )
    device.broken = True
    assert service.submit([mk()], deadline=0.001).result(10.0) is True
    assert service.breaker.state == OPEN
    device.broken = False
    time.sleep(0.15)                    # cooldown elapses -> half-open
    fut = service.submit([mk() for _ in range(20)], priority="block",
                         deadline=0.001)
    assert fut.result(10.0) is True
    # the probe was BOUNDED: the device saw probe_max sets, the host the
    # remainder, and the successful probe restored the breaker
    assert device.batches[-1] == 4
    assert host.batches[-1] == 16
    assert service.breaker.state == CLOSED
    service.stop()


def test_failed_half_open_probe_reopens():
    device, host = RecordingDevice(), RecordingHost()
    service = VerificationService(
        device, host_verifier=host, breaker_threshold=1,
        breaker_cooldown=0.1, breaker_probe_max=4, target_batch=10**6,
    )
    device.broken = True
    assert service.submit([mk()], deadline=0.001).result(10.0) is True
    assert service.breaker.state == OPEN
    time.sleep(0.15)
    # device still broken: the bounded probe fails -> straight back OPEN,
    # and the batch verdict is unharmed (host carried the remainder)
    fut = service.submit([mk() for _ in range(20)], priority="block",
                         deadline=0.001)
    assert fut.result(10.0) is True
    assert device.batches[-1] == 4
    assert service.breaker.state == OPEN
    assert service.breaker.trips == 2
    service.stop()


def test_breaker_state_gauge_and_transition_logs():
    from lighthouse_tpu.verify_service.circuit import CircuitBreaker

    b = CircuitBreaker(threshold=1, cooldown=0.01)
    b.record_failure()
    assert b.state == OPEN
    assert "verify_service_breaker_state 1" in metrics.gather()
    time.sleep(0.02)
    assert b.allow_device() is True and b.state == HALF_OPEN
    assert "verify_service_breaker_state 2" in metrics.gather()
    b.record_success()
    assert "verify_service_breaker_state 0" in metrics.gather()
    recs = ltpu_logging.recent(limit=64, component="verify_service")
    msgs = " | ".join(r["msg"] for r in recs)
    assert "tripped" in msgs and "half-open" in msgs and "restored" in msgs
    assert any(r["level"] == "warning" and "tripped" in r["msg"]
               for r in recs)


# ----------------------------------------------------- watchdog recovery


def test_watchdog_restarts_wedged_dispatcher_with_queues_intact():
    """Acceptance: a deliberately-wedged dispatcher (delay failpoint at
    the loop top, before any batch is popped) goes heartbeat-stale, the
    watchdog restarts it within its budget, and every queued request
    still resolves — nothing dropped by the recovery."""

    class StubOK:
        backend = "stub"
        on_device_fallback = None

        def verify_signature_sets(self, sets, priority=None):
            return True

        def verify_signature_sets_per_set(self, sets, priority=None):
            return [True] * len(list(sets))

    service = VerificationService(StubOK(), target_batch=1)
    wd = Watchdog()
    wd.register("verify_service", heartbeat=lambda: service.heartbeat,
                restart=service.restart_dispatcher, budget=0.2)
    failpoints.configure("verify.dispatch", "delay(30000)")
    futs = [service.submit([mk()]) for _ in range(5)]   # wedges on start
    time.sleep(0.5)
    failpoints.configure("verify.dispatch", "off")
    assert not any(f.done() for f in futs)              # truly wedged
    restarted = wd.check_once()
    assert restarted == ["verify_service"]
    assert all(f.result(timeout=10.0) is True for f in futs)
    assert service.restarts == 1
    dump = wd.last_dumps["verify_service"]
    assert dump["heartbeat_age_s"] > 0.2 and dump["records"]
    service.stop()


def test_watchdog_busy_budget_tolerates_long_pass_but_not_a_hang():
    """A worker mid work pass (busy() True — e.g. a device batch paying
    a first-time XLA compile) is judged against the larger busy budget:
    staleness past the idle budget does NOT restart it, staleness past
    the busy budget DOES — a hung pass stays detectable, not invisible."""
    now = [0.0]
    restarts = []
    busy = [False]
    wd = Watchdog(clock=lambda: now[0])
    wd.register("worker", heartbeat=lambda: 0.0,
                restart=lambda: restarts.append(1) or True,
                budget=1.0, busy=lambda: busy[0], busy_budget=10.0)
    busy[0] = True
    now[0] = 5.0                      # stale past idle budget, mid-pass
    assert wd.check_once() == []      # a long compile is not a wedge
    now[0] = 11.0                     # stale past the busy budget too
    assert wd.check_once() == ["worker"]
    assert restarts == [1]
    # idle workers keep the tight budget
    busy[0] = False
    now[0] = 13.0                     # 2.0 past the restart anchor (11.0)
    assert wd.check_once() == ["worker"]


def test_watchdog_stop_then_start_resumes_sweeping():
    wd = Watchdog(interval=0.02)
    wd.start()
    first = wd._thread
    assert first.is_alive()
    wd.stop()
    first.join(timeout=2.0)
    assert not first.is_alive()
    wd.start()                          # must NOT be a silent no-op
    assert wd._thread is not first and wd._thread.is_alive()
    wd.stop()


def test_watchdog_restarts_wedged_processor():
    from lighthouse_tpu.beacon.beacon_processor import BeaconProcessor
    from lighthouse_tpu.utils.task_executor import TaskExecutor

    class FakeChain:
        def process_block(self, block, observed_at=None):
            return b"\x00" * 32

    proc = BeaconProcessor(FakeChain())
    executor = TaskExecutor()
    wd = Watchdog()
    wd.register("beacon_processor", heartbeat=lambda: proc.heartbeat,
                restart=proc.restart_run_loop, budget=0.2)
    failpoints.configure("processor.tick", "delay(30000)")
    executor.spawn(proc.run, "beacon_processor")
    proc.enqueue_block(SimpleNamespace(message=SimpleNamespace(slot=1)))
    time.sleep(0.5)
    failpoints.configure("processor.tick", "off")
    assert not proc.results                              # wedged pre-drain
    assert wd.check_once() == ["beacon_processor"]
    deadline = time.monotonic() + 5.0
    while not proc.results and time.monotonic() < deadline:
        time.sleep(0.01)
    assert proc.results and proc.results[0][:2] == ("block", True)
    assert proc.restarts == 1
    executor.shutdown("test done")


def test_watchdog_restarts_wedged_slot_timer():
    """Satellite: the slot timer is a watchdog target — a timer loop
    wedged inside clock.now() goes heartbeat-stale, restart_slot_timer
    supersedes it generation-wise, and ticks resume under the fresh
    thread while the old one exits at its next pass."""
    from lighthouse_tpu.beacon.node import BeaconNode
    from lighthouse_tpu.utils.task_executor import TaskExecutor

    node = BeaconNode.__new__(BeaconNode)
    ticks = []
    wedge = threading.Event()
    slots = iter(range(1, 10**6))

    def now():
        if wedge.is_set():
            time.sleep(30.0)            # the wedged generation never returns
        return next(slots)

    node.chain = SimpleNamespace(on_tick=ticks.append)
    node.clock = SimpleNamespace(now=now, duration_to_next_slot=lambda: 0.01)
    node.executor = TaskExecutor()
    node.timer_heartbeat = None
    node._timer_gen = 0
    node._timer_tick_lock = threading.Lock()
    node.timer_tick_started = None
    node.timer_restarts = 0

    wd = Watchdog()
    wd.register("slot_timer", heartbeat=lambda: node.timer_heartbeat,
                restart=node.restart_slot_timer, budget=0.2)
    node.executor.spawn(node._timer_loop, "slot_timer")
    deadline = time.monotonic() + 5.0
    while not ticks and time.monotonic() < deadline:
        time.sleep(0.01)
    assert ticks, "timer ticking before the wedge"

    wedge.set()
    time.sleep(0.4)                     # heartbeat goes stale past budget
    wedge.clear()
    n0 = len(ticks)
    assert wd.check_once() == ["slot_timer"]
    assert node.timer_restarts == 1
    deadline = time.monotonic() + 5.0
    while len(ticks) <= n0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(ticks) > n0, "fresh generation resumed ticking"
    node.executor.shutdown("test done")


def test_wire_heartbeat_watchdog_restart_supersedes():
    """Satellite: the gossip heartbeat thread stamps beat_stamp and can
    be superseded by restart_heartbeat_thread — mesh maintenance
    continues under the replacement."""
    from lighthouse_tpu.network.wire import WireNode

    node = WireNode()
    try:
        deadline = time.monotonic() + 5.0
        while node.beat_stamp is None and time.monotonic() < deadline:
            time.sleep(0.02)
        assert node.beat_stamp is not None, "heartbeat stamping"

        assert node.restart_heartbeat_thread() is True
        assert node.heartbeat_restarts == 1
        fresh = node._heartbeat_thread
        assert fresh.is_alive()
        stamp0 = node.beat_stamp
        deadline = time.monotonic() + 5.0
        while (node.beat_stamp == stamp0 and
               time.monotonic() < deadline):
            time.sleep(0.02)
        assert node.beat_stamp != stamp0, "replacement generation beats"
    finally:
        node.stop()
        assert node.restart_heartbeat_thread() is False  # stopped: no-op


def test_wire_reaps_reader_stalled_in_dispatch():
    """Satellite: a reader thread stuck INSIDE one frame dispatch past
    the stall budget costs that peer its connection (socket teardown
    unblocks the thread); an idle reader (blocked on recv, no dispatch
    in flight) is never reaped."""
    from lighthouse_tpu.network.wire import WireNode

    a, b = WireNode(), WireNode()
    try:
        pid = a.dial("127.0.0.1", b.port)
        assert a.request_status(pid) is not None
        peer = a.peers[pid]

        # idle connection: dispatch_started is None -> untouched
        a.reader_stall_budget = 0.05
        a._reap_stalled_readers()
        assert pid in a.peers

        # wedged dispatch: stamp far in the past -> reaped
        peer.dispatch_started = time.monotonic() - 1.0
        a._reap_stalled_readers()
        assert pid not in a.peers
        assert not peer._alive
    finally:
        a.stop()
        b.stop()


# ------------------------------------------------------- http control


def test_failpoints_http_api():
    from lighthouse_tpu.api.http_api import BeaconApiServer
    from lighthouse_tpu.beacon.chain import BeaconChain
    from lighthouse_tpu.crypto.backend import SignatureVerifier
    from lighthouse_tpu.testing.harness import Harness
    from lighthouse_tpu.types import ChainSpec, MinimalPreset

    h = Harness(8, ChainSpec(preset=MinimalPreset))
    chain = BeaconChain(h.state.copy(), ChainSpec(preset=MinimalPreset),
                        verifier=SignatureVerifier("fake"))
    server = BeaconApiServer(chain).start()
    try:
        base = f"http://127.0.0.1:{server.port}"

        def patch(body):
            req = urllib.request.Request(
                base + "/lighthouse/failpoints",
                data=json.dumps(body).encode(), method="PATCH",
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req) as r:
                return json.load(r)["data"]

        with urllib.request.urlopen(base + "/lighthouse/failpoints") as r:
            snap = json.load(r)["data"]
        assert snap["device.execute_chunk"]["mode"] == "off"

        out = patch({"name": "store.put", "mode": "delay(1)"})
        assert out["store.put"]["mode"] == "delay(1)"
        out = patch({"failpoints": {"store.put": "off",
                                    "eth1.rpc": "error(0.5)"}})
        assert out["store.put"]["mode"] == "off"
        assert out["eth1.rpc"]["mode"] == "error(0.5)"
        with urllib.request.urlopen(base + "/lighthouse/failpoints") as r:
            snap = json.load(r)["data"]
        assert snap["eth1.rpc"]["mode"] == "error(0.5)"

        with pytest.raises(urllib.error.HTTPError) as ei:
            patch({"name": "eth1.rpc", "mode": "bogus"})
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            patch({"nonsense": True})
        assert ei.value.code == 400
        # a storm with one bad entry rejects ATOMICALLY: the valid
        # entry must not be armed behind the 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            patch({"failpoints": {"wire.rpc": "error",
                                  "store.put": "junk(1)"}})
        assert ei.value.code == 400
        assert failpoints.get("wire.rpc").mode == "off"
        # a typo'd name must not mint a never-firing registry entry
        with pytest.raises(urllib.error.HTTPError) as ei:
            patch({"name": "store.putt", "mode": "error"})
        assert ei.value.code == 400
        assert failpoints.get("store.putt") is None
    finally:
        server.stop()


# --------------------------------------------------- chaos acceptance


def test_chaos_storm_acceptance():
    """The fault storm: 20% device `error`, engine `delay`, one store
    `panic_once` — zero lost verdicts, every submitted set resolves
    exactly once, the breaker trips -> half-open-probes -> restores, and
    the store survives its crash window."""
    import importlib.util

    spec_ = importlib.util.spec_from_file_location(
        "chaos_bench",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "chaos_bench.py"),
    )
    cb = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(cb)

    failpoints.seed_all(7)
    device = cb.FaultyDeviceVerifier()
    service = VerificationService(
        device, host_verifier=cb.HostVerifier(), target_batch=32,
        breaker_threshold=2, breaker_cooldown=0.1,
    )
    failpoints.configure("device.execute_chunk", "error(0.2)")
    failpoints.configure("engine.rpc", "delay(5)")
    failpoints.configure("store.compact", "panic_once")

    n_threads, per_thread = 6, 30
    futures = [[] for _ in range(n_threads)]

    def submitter(i):
        for _ in range(per_thread):
            futures[i].append(service.submit([cb.StubSet()]))

    threads = [threading.Thread(target=submitter, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    results = [f.result(timeout=30.0) for fl in futures for f in fl]
    # zero lost verdicts: every submitted set resolved, and resolved True
    assert len(results) == n_threads * per_thread
    assert all(results)
    assert sum(service.dispatched_batches) == n_threads * per_thread
    # the storm must actually storm: coalescing makes the number of
    # device chunks (hence 20%-fault draws) nondeterministic, so keep
    # offering until at least one injected fault lands (bounded)
    deadline = time.monotonic() + 10.0
    extra = 0
    while device.faults == 0 and time.monotonic() < deadline:
        assert service.submit([cb.StubSet()], deadline=0.001).result(10.0)
        extra += 1
    assert device.faults > 0

    # engine delay: the RPC stalls but succeeds (no retry storm needed)
    from lighthouse_tpu.execution.engine_server import MockEngineServer
    from lighthouse_tpu.execution.engine_http import HttpJsonRpcClient
    from lighthouse_tpu.types.state import state_types
    from lighthouse_tpu.types import MinimalPreset

    eng = MockEngineServer(state_types(MinimalPreset), bytes(range(32)))
    try:
        client = HttpJsonRpcClient(eng.url, bytes(range(32)))
        assert client.call("lighthouse_elGenesisHash", []).startswith("0x")
    finally:
        eng.close()
    assert failpoints.get("engine.rpc").fired >= 1

    # store panic_once: crash window survived, retry compacts clean
    from lighthouse_tpu.beacon.store import PyFileKV
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        kv = PyFileKV(os.path.join(d, "storm.log"))
        for i in range(8):
            kv.put(bytes([i]), bytes([i]) * 32)
        with pytest.raises(failpoints.FailpointPanic):
            kv.compact()
        assert kv.get(bytes([5])) == bytes([5]) * 32
        kv.compact()
        assert kv.get(bytes([5])) == bytes([5]) * 32
        kv.close()

    # breaker acceptance: force a trip, then watch the half-open probe
    # restore CLOSED only after it succeeds
    failpoints.configure("device.execute_chunk", "error")
    deadline = time.monotonic() + 10.0
    while service.breaker.state != OPEN and time.monotonic() < deadline:
        service.submit([cb.StubSet()], deadline=0.001).result(10.0)
    assert service.breaker.state == OPEN
    trips_before_recovery = service.breaker.trips
    assert trips_before_recovery >= 1
    failpoints.configure("device.execute_chunk", "off")
    deadline = time.monotonic() + 10.0
    while service.breaker.state != CLOSED and time.monotonic() < deadline:
        service.submit([cb.StubSet()], deadline=0.001).result(10.0)
        time.sleep(0.02)
    assert service.breaker.state == CLOSED
    service.stop()
