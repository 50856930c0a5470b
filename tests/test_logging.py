"""Structured logging flight-recorder (ISSUE 3 acceptance).

End-to-end: a WARN emitted inside a traced verify_service dispatch
appears in /lighthouse/logs/recent with the matching trace_id,
increments lighthouse_logs_total{level="warning",
component="verify_service"} on /metrics, and streams over
/eth/v1/events-style SSE framing from /lighthouse/logs; a runtime level
change via PATCH /lighthouse/logs/level suppresses and re-enables
records without a restart.  Shed-by-class: with the device circuit
open, discovery submissions are shed (verify_service_shed_total) while
block-class submissions still resolve.  Plus the log-hygiene print
lint, file rotation, the monitoring-body shape, and the
/lighthouse/ui/validator-metrics endpoint.
"""

import json
import os
import re
import socket
import threading
import time
import urllib.request
from pathlib import Path
from types import SimpleNamespace

import pytest

from lighthouse_tpu.utils import logging as L
from lighthouse_tpu.utils import metrics, tracing


def mk(poison=False):
    return SimpleNamespace(poison=poison)


class StubVerifier:
    backend = "stub"
    on_device_fallback = None

    def verify_signature_sets(self, sets, priority=None):
        return all(not getattr(s, "poison", False) for s in sets)

    def verify_signature_sets_per_set(self, sets, priority=None):
        return [not getattr(s, "poison", False) for s in sets]


class BrokenDeviceVerifier(StubVerifier):
    """Device-backed seam double that always degrades internally."""

    backend = "tpu"

    def verify_signature_sets(self, sets, priority=None):
        if self.on_device_fallback is not None:
            self.on_device_fallback(RuntimeError("device error"))
        return super().verify_signature_sets(sets, priority)


# ------------------------------------------------------------- unit layer


def test_structured_record_fields_and_counter():
    log = L.get_logger("t_unit")
    before = metrics.counter(
        "lighthouse_logs_total", "", labels=("level", "component")
    ).with_labels("warning", "t_unit").value
    log.warning("queue %s overflowed", "alpha", depth=17)
    recs = L.recent(component="t_unit")
    assert recs, "record missing from ring"
    rec = recs[0]
    assert rec["level"] == "warning"
    assert rec["component"] == "t_unit"
    assert rec["msg"] == "queue alpha overflowed"
    assert rec["fields"] == {"depth": 17}
    assert rec["trace_id"] is None
    assert rec["ts"] > 0
    after = L.LOGS_TOTAL.with_labels("warning", "t_unit").value
    assert after == before + 1
    text = metrics.gather()
    assert 'lighthouse_logs_total{level="warning",component="t_unit"}' in text


def test_trace_id_injected_from_current_trace():
    log = L.get_logger("t_traced")
    tr = tracing.start_trace("t_logging_unit")
    with tracing.use(tr):
        log.error("inside the pipeline")
    rec = L.recent(component="t_traced")[0]
    assert rec["trace_id"] == tr.trace_id


def test_legacy_stdlib_loggers_are_captured_with_derived_component():
    import logging as stdlog

    stdlog.getLogger("lighthouse_tpu.t_legacy").warning("old-style call")
    rec = L.recent(component="t_legacy")[0]
    assert rec["msg"] == "old-style call"
    assert rec["component"] == "t_legacy"


def test_level_filter_is_at_or_above():
    log = L.get_logger("t_floor")
    log.info("info record")
    log.error("error record")
    msgs = [r["msg"] for r in L.recent(level="warning", component="t_floor")]
    assert "error record" in msgs
    assert "info record" not in msgs


def test_rate_limited_warning_collapses_bursts():
    log = L.get_logger("t_throttle")
    emitted = sum(
        log.warning_rate_limited("k", 5.0, "burst warn") for _ in range(20)
    )
    assert emitted == 1
    assert len(L.recent(component="t_throttle")) == 1
    # a different key is independent
    assert log.warning_rate_limited("k2", 5.0, "other key") is True


def test_set_level_rejects_unknown_components():
    """stdlib loggers live forever once minted: arbitrary client-chosen
    component names must not allocate one per PATCH."""
    with pytest.raises(ValueError):
        L.set_level("no_such_component_xyz", "info")
    assert "no_such_component_xyz" not in L.levels()
    assert L.set_level(None, L.levels()["root"])    # root always settable


def test_set_level_suppresses_and_reenables_without_restart():
    log = L.get_logger("t_levelctl")
    L.set_level("t_levelctl", "error")
    log.warning("suppressed")
    assert not L.recent(component="t_levelctl")
    assert L.levels()["t_levelctl"] == "error"
    L.set_level("t_levelctl", "info")
    log.warning("audible again")
    assert L.recent(component="t_levelctl")[0]["msg"] == "audible again"


def test_severity_totals_and_ring_depth():
    log = L.get_logger("t_totals")
    base = L.severity_totals()
    log.warning("one")
    log.error("two")
    now = L.severity_totals()
    assert now["warning"] == base["warning"] + 1
    assert now["error"] == base["error"] + 1
    assert set(now) == {"debug", "info", "warning", "error", "critical"}
    assert L.ring_depth() >= 2


def test_json_file_handler_rotates(tmp_path):
    path = str(tmp_path / "node.log")
    h = L.add_file_handler(path, max_bytes=600, backup_count=2, fmt="json")
    log = L.get_logger("t_rotate")
    try:
        for i in range(40):
            log.warning("rotation filler record %d with some padding", i)
        h.flush()
        assert os.path.exists(path)
        assert os.path.exists(path + ".1"), "no rotation happened"
        line = open(path).readlines()[-1].strip()
        rec = json.loads(line)
        assert rec["component"] == "t_rotate"
        assert rec["level"] == "warning"
    finally:
        import logging as stdlog

        stdlog.getLogger(L.ROOT).removeHandler(h)
        h.close()


def test_setup_logging_is_idempotent_and_routes_cli_flags():
    import logging as stdlog

    from lighthouse_tpu.cli import build_parser

    # both daemon subcommands expose the flags
    bn = build_parser().parse_args(
        ["bn", "--log-format", "json", "--logfile", "/tmp/x.log",
         "--log-level", "warning", "--interop-validators", "2"]
    )
    assert (bn.log_format, bn.logfile, bn.log_level) == (
        "json", "/tmp/x.log", "warning")
    vc = build_parser().parse_args(["vc", "--log-format", "text"])
    assert vc.log_format == "text"

    root = stdlog.getLogger(L.ROOT)
    saved = list(root.handlers)
    saved_level, saved_prop = root.level, root.propagate
    try:
        L.setup_logging(level="warning", fmt="json")
        L.setup_logging(level="info", fmt="text")   # re-run must replace
        managed = [h for h in root.handlers
                   if getattr(h, "_ltpu_managed", False)]
        assert len(managed) == 1
        assert root.level == stdlog.INFO
    finally:
        for h in root.handlers[:]:
            if getattr(h, "_ltpu_managed", False):
                root.removeHandler(h)
                h.close()
        for h in saved:
            if h not in root.handlers:
                root.addHandler(h)
        root.setLevel(saved_level)
        root.propagate = saved_prop


# --------------------------------------------------------- shed-by-class


def test_shed_discovery_when_breaker_open_but_blocks_resolve():
    from lighthouse_tpu.verify_service import (
        LoadShedError,
        VerificationService,
    )
    from lighthouse_tpu.verify_service.circuit import OPEN

    device = BrokenDeviceVerifier()
    host = StubVerifier()
    shed_before = (
        metrics.counter("verify_service_shed_total", "", labels=("class",))
        .with_labels("discovery").value
    )
    service = VerificationService(
        device, host_verifier=host,
        breaker_threshold=1, breaker_cooldown=3600.0,
    )
    # first dispatch trips the breaker OPEN
    assert service.submit([mk()], deadline=0.001).result(10.0) is True
    assert service.breaker.state == OPEN

    with pytest.raises(LoadShedError):
        service.submit([mk()], priority="discovery")
    # the light_client alias is the same shed class
    with pytest.raises(LoadShedError):
        service.submit([mk()], priority="light_client")
    # blocking wrappers fail closed instead of verifying inline
    assert service.verify_signature_sets(
        [mk()], priority="discovery") is False
    assert service.verify_signature_sets_per_set(
        [mk(), mk()], priority="discovery") == [False, False]
    # block-class (and attestation, level-2 only) work still resolves
    assert service.submit(
        [mk()], priority="block", deadline=0.001).result(10.0) is True
    assert service.submit(
        [mk()], priority="attestation", deadline=0.001).result(10.0) is True

    shed_after = metrics.counter(
        "verify_service_shed_total", "", labels=("class",)
    ).with_labels("discovery").value
    assert shed_after >= shed_before + 4
    assert 'verify_service_shed_total{class="discovery"}' in metrics.gather()
    # the shed WARN went through the rate-limited component logger
    warns = [r for r in L.recent(component="verify_service")
             if "shedding discovery" in r["msg"]]
    assert warns and warns[0]["level"] == "warning"
    service.stop()


def test_shed_verdicts_never_enter_discovery_cache():
    """A shed page is dropped (all False) but must NOT poison the
    discovery record-verdict cache — its invariant is that a record's
    verdict never changes, and these records may be perfectly valid
    once the overload clears."""
    from lighthouse_tpu.network.discovery import verify_records
    from lighthouse_tpu.verify_service import ShedVerdicts

    class Record:
        pubkey = b"\x00" * 48
        signature = b"\x00" * 96

        def __init__(self, n):
            self._n = bytes([n]) * 8

        def to_bytes(self):
            return self._n

        def _signed_content(self):
            return self._n

    class SheddingService:
        backend = "stub"

        def submit(self, *a, **k):      # hasattr(submit) -> service path
            raise AssertionError("unused")

        def verify_signature_sets_per_set(self, sets, priority=None):
            return ShedVerdicts([False] * len(sets))

    class HealthyService(SheddingService):
        def verify_signature_sets_per_set(self, sets, priority=None):
            return [True] * len(sets)

    records = [Record(1), Record(2)]
    cache = {}
    assert verify_records(records, SheddingService(), cache=cache) == [
        False, False]
    assert cache == {}, "shed verdicts leaked into the verdict cache"
    # after the overload clears the SAME records verify and cache
    assert verify_records(records, HealthyService(), cache=cache) == [
        True, True]
    assert len(cache) == 2


def test_processor_drop_warn_emitted_outside_lock(monkeypatch):
    from lighthouse_tpu.beacon import beacon_processor as bp

    monkeypatch.setattr(bp, "MAX_GOSSIP_BLOCK_QUEUE", 2)
    proc = bp.BeaconProcessor(chain=None)
    blk = SimpleNamespace(message=SimpleNamespace(slot=1))
    assert proc.enqueue_block(blk) is True
    assert proc.enqueue_block(blk) is True
    assert proc.enqueue_block(blk) is False      # full -> dropped
    assert not proc._lock.locked()
    recs = [r for r in L.recent(component="beacon_processor")
            if "block queue full" in r["msg"]]
    assert recs and recs[0]["level"] == "warning"


def test_shed_attestations_only_at_saturation():
    from lighthouse_tpu.verify_service import (
        LoadShedError,
        VerificationService,
    )

    gate = threading.Event()

    class GatedStub(StubVerifier):
        def verify_signature_sets(self, sets, priority=None):
            gate.wait(10.0)
            return super().verify_signature_sets(sets, priority)

    service = VerificationService(
        GatedStub(), target_batch=1, shed_watermark=4,
    )
    futs = [service.submit([mk()], priority="block")]   # parks dispatcher
    time.sleep(0.05)
    # climb past the watermark (level 1): discovery sheds, attestation not
    futs += [service.submit([mk()], priority="block") for _ in range(4)]
    with pytest.raises(LoadShedError):
        service.submit([mk()], priority="discovery")
    futs.append(service.submit([mk()], priority="attestation"))
    # climb past 4x the watermark (level 2): attestations shed too,
    # blocks and aggregates never
    futs += [service.submit([mk()], priority="block") for _ in range(12)]
    with pytest.raises(LoadShedError):
        service.submit([mk()], priority="attestation")
    futs.append(service.submit([mk()], priority="aggregate"))
    futs.append(service.submit([mk()], priority="block"))
    gate.set()
    assert all(f.result(20.0) for f in futs)
    service.stop()


# ------------------------------------------------- end-to-end acceptance


def _sse_reader(port, query, out, connected):
    """Open /lighthouse/logs and collect raw bytes until a log frame
    (or the socket dies)."""
    s = socket.create_connection(("127.0.0.1", port), timeout=15)
    try:
        s.sendall(
            f"GET /lighthouse/logs{query} HTTP/1.1\r\n"
            f"Host: 127.0.0.1\r\nConnection: close\r\n\r\n".encode()
        )
        buf = b""
        # response headers first: once they arrive the handler has
        # already subscribed to the broadcaster
        while b"\r\n\r\n" not in buf:
            buf += s.recv(4096)
        connected.set()
        body = buf.split(b"\r\n\r\n", 1)[1]
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if b"event: log" in body and body.rstrip().endswith(b"}"):
                break
            chunk = s.recv(4096)
            if not chunk:
                break
            body += chunk
        out.append(body)
    finally:
        s.close()


def test_e2e_traced_warn_recent_metrics_sse_and_patch_level():
    from lighthouse_tpu.api.http_api import BeaconApiServer
    from lighthouse_tpu.beacon.chain import BeaconChain
    from lighthouse_tpu.testing.harness import Harness
    from lighthouse_tpu.types import ChainSpec, MinimalPreset
    from lighthouse_tpu.verify_service import VerificationService

    spec = ChainSpec(preset=MinimalPreset)
    h = Harness(8, spec)
    service = VerificationService(StubVerifier())
    chain = BeaconChain(h.state.copy(), spec, verifier=service)
    server = BeaconApiServer(chain).start()
    base = f"http://127.0.0.1:{server.port}"

    def get(path):
        with urllib.request.urlopen(base + path, timeout=10) as r:
            return json.load(r)

    def patch(path, body):
        req = urllib.request.Request(
            base + path, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="PATCH",
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            return json.load(r)

    try:
        warns_before = len([
            r for r in L.recent(component="verify_service")
            if "poisoned verification batch" in r["msg"]
        ])
        # a poisoned per-set request forces the attribution pass, whose
        # WARN is emitted while the dispatcher's verify_batch trace is
        # current
        assert service.verify_signature_sets_per_set(
            [mk(), mk(poison=True)]) == [True, False]

        # --- /lighthouse/logs/recent carries the WARN with a trace_id
        recs = get(
            "/lighthouse/logs/recent?component=verify_service&level=warning"
        )["data"]
        poisoned = [r for r in recs
                    if "poisoned verification batch" in r["msg"]]
        assert len(poisoned) == warns_before + 1
        rec = poisoned[0]
        assert rec["level"] == "warning"
        assert rec["component"] == "verify_service"
        assert rec["trace_id"] is not None

        # --- the trace_id joins against the /lighthouse/tracing span ring
        traces = get("/lighthouse/tracing?kind=verify_batch")["data"]
        match = [t for t in traces if t["trace_id"] == rec["trace_id"]]
        assert match, "WARN's trace_id not found among verify_batch traces"
        span_names = {s["name"] for s in match[0]["spans"]}
        assert "attribution" in span_names

        # --- /metrics carries the labeled severity counter
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            text = r.read().decode()
        line = next(
            ln for ln in text.splitlines()
            if ln.startswith('lighthouse_logs_total{level="warning",'
                             'component="verify_service"}')
        )
        assert int(line.rsplit(" ", 1)[1]) >= 1

        # --- live SSE framing from /lighthouse/logs
        out, connected = [], threading.Event()
        reader = threading.Thread(
            target=_sse_reader,
            args=(server.port, "?component=verify_service&level=warning",
                  out, connected),
        )
        reader.start()
        assert connected.wait(10.0), "SSE stream never connected"
        assert service.verify_signature_sets_per_set(
            [mk(poison=True), mk()]) == [False, True]
        reader.join(20.0)
        assert out, "SSE reader returned nothing"
        frames = [f for f in out[0].split(b"\n\n")
                  if f.startswith(b"event: log")]
        assert frames, f"no log frame in stream: {out[0][:400]!r}"
        payload = json.loads(
            frames[0].split(b"\ndata: ", 1)[1].decode()
        )
        assert payload["component"] == "verify_service"
        assert payload["level"] == "warning"
        assert "poisoned verification batch" in payload["msg"]

        # --- PATCH /lighthouse/logs/level: suppress, then re-enable,
        # no restart
        tlog = L.get_logger("t_patch")
        assert patch("/lighthouse/logs/level",
                     {"component": "t_patch", "level": "error"})["data"] == {
            "component": "t_patch", "level": "error"}
        tlog.warning("must be suppressed")
        assert not [r for r in L.recent(component="t_patch")
                    if r["msg"] == "must be suppressed"]
        patch("/lighthouse/logs/level",
              {"component": "t_patch", "level": "debug"})
        tlog.warning("back on air")
        assert L.recent(component="t_patch")[0]["msg"] == "back on air"
        assert get("/lighthouse/logs/level")["data"]["t_patch"] == "debug"

        # malformed PATCH bodies are 400s, not 500s
        for bad in ({}, {"component": "x"}, {"level": "nope"}):
            req = urllib.request.Request(
                base + "/lighthouse/logs/level",
                data=json.dumps(bad).encode(), method="PATCH",
            )
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 400
    finally:
        server.stop()
        service.stop()


def test_validator_metrics_endpoint_through_harness():
    from lighthouse_tpu.api.http_api import BeaconApiServer
    from lighthouse_tpu.beacon.chain import BeaconChain
    from lighthouse_tpu.crypto.backend import SignatureVerifier
    from lighthouse_tpu.testing.harness import Harness
    from lighthouse_tpu.types import ChainSpec, MinimalPreset

    spec = ChainSpec(preset=MinimalPreset)
    h = Harness(8, spec)
    chain = BeaconChain(h.state.copy(), spec,
                        verifier=SignatureVerifier("fake"))
    for v in range(8):
        chain.validator_monitor.register(v, current_epoch=0)
    block = h.produce_block(1)
    h.process_block(block, strategy="no_verification")
    chain.on_tick(1)
    root = chain.process_block(block)
    assert chain.head_root == root
    proposer = int(block.message.proposer_index)

    server = BeaconApiServer(chain).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        with urllib.request.urlopen(
            base + "/lighthouse/ui/validator-metrics?epoch=0", timeout=10
        ) as r:
            data = json.load(r)["data"]
        assert set(data["validators"]) == {str(v) for v in range(8)}
        summary = data["validators"][str(proposer)]
        assert summary["proposals"] == [1]
        assert data["epoch"] == 0
        row = data["epoch_summary"][str(proposer)]
        assert row["proposed_slots"] == [1]
        assert "attestation_hit" in row and "balance" in row
    finally:
        server.stop()


# ------------------------------------------------ monitoring body shape


def test_gather_snapshot_includes_observability_section():
    from lighthouse_tpu.utils.monitoring import gather_snapshot

    L.get_logger("t_snapshot").error("counted in the body")
    body = gather_snapshot()
    obs = body["observability"]
    assert set(obs) == {"log_totals", "log_ring_depth",
                       "tracing_ring_depth"}
    assert set(obs["log_totals"]) == {
        "debug", "info", "warning", "error", "critical"}
    assert obs["log_totals"]["error"] >= 1
    assert obs["log_ring_depth"] >= 1
    assert isinstance(obs["tracing_ring_depth"], int)
    assert json.dumps(body)    # the pushed body must be JSON-serializable


# --------------------------------------------------------- log hygiene


def test_no_bare_print_in_daemon_modules():
    """Thin wrapper since PR 11: the lint itself is the print-hygiene
    rule in lighthouse_tpu/analysis (AST-based — docstrings and string
    literals can no longer trip it, aliased calls can no longer hide
    from it).  Daemon code must log through the flight recorder:
    stdout writes are invisible to /lighthouse/logs, carry no
    severity, and never reach the rotated logfile."""
    from lighthouse_tpu import analysis

    report = analysis.run_analysis(rules=["print-hygiene"])
    assert report["clean"], analysis.format_report(report)
