"""Per-kernel profile registry (crypto/tpu/profile.py): launch EWMA +
histogram accumulation, the cost_analysis join, pad-waste ratios,
persistence beside the AOT cache, the CachedKernel chokepoint hook,
and the tools/profile_report.py summary/exit contract."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from lighthouse_tpu.crypto.tpu import profile

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def reg(tmp_path):
    r = profile.ProfileRegistry(str(tmp_path / "kernel_profile.json"))
    old = profile.get_registry()
    profile.set_registry(r)
    yield r
    profile.set_registry(old)


def test_launch_accumulation_ewma_histogram(reg):
    for wall_ms in (1.0, 2.0, 4.0):
        reg.record_launch("k", "32x2", wall_ms / 1e3, source="aot",
                          topology="d1")
    rows = reg.rows()
    assert len(rows) == 1
    e = rows[0]
    assert (e["kernel"], e["shape"], e["topology"]) == ("k", "32x2", "d1")
    assert e["launches"] == 3
    assert e["total_ms"] == pytest.approx(7.0, abs=0.01)
    assert e["min_ms"] == pytest.approx(1.0, abs=0.01)
    assert e["max_ms"] == pytest.approx(4.0, abs=0.01)
    # EWMA(0.2): 1 -> 1.2 -> 1.76
    assert e["ewma_ms"] == pytest.approx(1.76, abs=0.01)
    assert sum(e["hist"]) == 3
    assert e["source"] == {"aot": 3}
    assert e["mean_ms"] == pytest.approx(7.0 / 3, abs=0.01)


def test_keys_split_by_shape_and_topology(reg):
    reg.record_launch("k", "32x2", 0.001, topology="d1")
    reg.record_launch("k", "64x2", 0.001, topology="d1")
    reg.record_launch("k", "32x2", 0.001, topology="d8dp8mp1")
    assert len(reg.rows()) == 3


def test_cost_join_and_pad_waste(reg):
    reg.record_cost("k", "32x2", {"flops": 1e9, "bytes_accessed": 5e6},
                    topology="d1")
    reg.record_launch("k", "32x2", 0.002, topology="d1")
    reg.record_pad("k", "32x2", 20, 32, topology="d1")
    reg.record_pad("k", "32x2", 30, 32, topology="d1")
    e = reg.rows()[0]
    assert e["cost"] == {"flops": 1e9, "bytes_accessed": 5e6}
    # 50 real sets over 64 lanes
    assert e["pad_waste_ratio"] == pytest.approx(1 - 50 / 64, abs=1e-4)


def test_persistence_roundtrip(reg, tmp_path):
    reg.record_launch("k", "32x2", 0.003, topology="d1")
    reg.record_cost("k", "32x2", {"flops": 2.0}, topology="d1")
    assert reg.save(force=True)
    # a fresh registry at the same path resumes the accumulated state
    reborn = profile.ProfileRegistry(reg.path)
    e = reborn.rows()[0]
    assert e["launches"] == 1 and e["cost"] == {"flops": 2.0}
    reborn.record_launch("k", "32x2", 0.003, topology="d1")
    assert reborn.rows()[0]["launches"] == 2


def test_corrupt_profile_starts_empty(tmp_path):
    p = tmp_path / "kernel_profile.json"
    p.write_text("{not json")
    r = profile.ProfileRegistry(str(p))
    assert r.rows() == []          # never raises


def test_snapshot_and_summary_shape(reg):
    reg.record_launch("a", "s", 0.010, topology="d1")
    reg.record_launch("b", "s", 0.001, topology="d1")
    snap = reg.snapshot()
    assert set(snap) >= {"schema", "topology", "launch_counts", "rows"}
    # rows sorted by total wall, heaviest first
    assert [r["kernel"] for r in snap["rows"]] == ["a", "b"]


def test_extract_cost_tolerates_shapes():
    class ListCA:
        def cost_analysis(self):
            return [{"flops": 10.0, "bytes accessed": 4.0, "junk": "x"}]

    class DictCA:
        def cost_analysis(self):
            return {"flops": 3.0}

    class Broken:
        def cost_analysis(self):
            raise RuntimeError("no cost model")

    assert profile.extract_cost(ListCA()) == {
        "flops": 10.0, "bytes_accessed": 4.0,
    }
    assert profile.extract_cost(DictCA()) == {"flops": 3.0}
    assert profile.extract_cost(Broken()) is None
    assert profile.extract_cost(object()) is None


def test_cached_kernel_launch_feeds_registry(reg, tmp_path, monkeypatch):
    """The CachedKernel chokepoint: one launch lands one profile row
    keyed by the kernel name and the canonical shape label, with the
    cost join captured at load time."""
    import jax.numpy as jnp

    from lighthouse_tpu.crypto.tpu import compile_cache as cc

    old_cache = cc.get_cache()
    cc.set_cache(cc.CompileCache(cache_dir=str(tmp_path / "aot")))
    try:
        k = cc.CachedKernel("profile_probe", lambda x: x * 2 + 1)
        x = jnp.ones((4, 8), jnp.int32)
        assert k(x).shape == (4, 8)
        rows = reg.rows()
        probe = [r for r in rows if r["kernel"] == "profile_probe"]
        assert probe, rows
        e = probe[0]
        assert e["shape"] == "8"           # trailing dims of (4, 8)
        assert e["launches"] == 1
        assert e["source"].get("aot") == 1
        assert e["ewma_ms"] is not None and e["ewma_ms"] >= 0
        # second launch reuses the key
        k(x)
        assert [r for r in reg.rows()
                if r["kernel"] == "profile_probe"][0]["launches"] == 2
    finally:
        cc.set_cache(old_cache)


def test_disabled_cache_records_jit_source(reg, monkeypatch):
    import jax.numpy as jnp

    from lighthouse_tpu.crypto.tpu import compile_cache as cc

    old_cache = cc.get_cache()
    cc.set_cache(cc.CompileCache(enabled=False))
    try:
        k = cc.CachedKernel("profile_probe_jit", lambda x: x + 1)
        k(jnp.ones((2, 4), jnp.int32))
        e = [r for r in reg.rows() if r["kernel"] == "profile_probe_jit"][0]
        assert e["source"] == {"jit": 1}
    finally:
        cc.set_cache(old_cache)


def test_http_profile_route_serves_rows_after_workload(reg, tmp_path):
    """Acceptance: GET /lighthouse/profile serves per-(kernel, shape,
    topology) wall rows after a verify workload (here: a real
    CachedKernel launch through the chokepoint)."""
    import json
    import urllib.request

    import jax.numpy as jnp

    from lighthouse_tpu.api.http_api import BeaconApiServer
    from lighthouse_tpu.beacon.chain import BeaconChain
    from lighthouse_tpu.crypto.backend import SignatureVerifier
    from lighthouse_tpu.crypto.tpu import compile_cache as cc
    from lighthouse_tpu.testing.harness import Harness
    from lighthouse_tpu.types import ChainSpec, MinimalPreset

    old_cache = cc.get_cache()
    cc.set_cache(cc.CompileCache(cache_dir=str(tmp_path / "aot")))
    try:
        k = cc.CachedKernel("profile_http_probe", lambda x: x.sum())
        k(jnp.ones((2, 4), jnp.int32))
    finally:
        cc.set_cache(old_cache)
    h = Harness(8, ChainSpec(preset=MinimalPreset))
    chain = BeaconChain(h.state.copy(), ChainSpec(preset=MinimalPreset),
                        verifier=SignatureVerifier("fake"))
    server = BeaconApiServer(chain).start()
    try:
        url = f"http://127.0.0.1:{server.port}/lighthouse/profile"
        with urllib.request.urlopen(url) as r:
            data = json.load(r)["data"]
    finally:
        server.stop()
    rows = [r for r in data["rows"] if r["kernel"] == "profile_http_probe"]
    assert rows, data
    e = rows[0]
    assert e["launches"] >= 1 and e["ewma_ms"] is not None
    assert e["topology"] == data["topology"]
    assert {"sharded", "single"} <= set(data["launch_counts"])


# ------------------------------------------------------------- report tool


def _run_report(*args):
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / "profile_report.py"), *args],
        capture_output=True, text=True, cwd=str(REPO), timeout=120,
    )


def test_report_tool_summarizes_registry(tmp_path):
    reg = profile.ProfileRegistry(str(tmp_path / "p.json"))
    reg.record_launch("bls_batched_verify", "32x2", 0.004, topology="d1")
    reg.record_cost("bls_batched_verify", "32x2", {"flops": 1e8},
                    topology="d1")
    reg.record_pad("bls_batched_verify", "32x2", 20, 32, topology="d1")
    reg.save(force=True)
    res = _run_report("--path", str(tmp_path / "p.json"), "--json")
    assert res.returncode == 0, res.stderr
    out = json.loads(res.stdout)
    assert out["total_launches"] == 1
    assert out["top_sinks"][0]["kernel"] == "bls_batched_verify"
    assert out["cost_fit"] and out["cost_fit"][0]["gflops"] > 0
    # human table renders too
    res = _run_report("--path", str(tmp_path / "p.json"))
    assert res.returncode == 0
    assert "bls_batched_verify" in res.stdout
    assert "wall-time sinks" in res.stdout


def test_report_tool_errors_on_missing_empty_and_malformed(tmp_path):
    # missing file
    res = _run_report("--path", str(tmp_path / "nope.json"), "--json")
    assert res.returncode == 1
    assert "error" in json.loads(res.stdout)
    # empty registry (rows: []) is an ERROR under --json, not success
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"schema": 1, "rows": []}))
    res = _run_report("--path", str(empty), "--json")
    assert res.returncode == 1
    # malformed rows
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"rows": [{"kernel": "k"}]}))
    res = _run_report("--path", str(bad), "--json")
    assert res.returncode == 1
    # outright non-JSON
    garbage = tmp_path / "garbage.json"
    garbage.write_text("{")
    res = _run_report("--path", str(garbage))
    assert res.returncode == 1
    assert "error" in res.stderr


def test_report_tool_errors_on_zero_launch_rows(tmp_path):
    """A registry holding only zero-launch keys (cost/pad rows that
    never saw a launch) is as vacuous as an empty one — the CI contract
    fails it instead of rendering an all-zero table."""
    p = tmp_path / "zero.json"
    p.write_text(json.dumps({"schema": 1, "rows": [{
        "kernel": "k", "shape": "32x2", "topology": "d1",
        "launches": 0, "total_ms": 0.0,
    }]}))
    res = _run_report("--path", str(p), "--json")
    assert res.returncode == 1
    assert "no recorded launches" in json.loads(res.stdout)["error"]


def test_report_tool_state_mode(tmp_path):
    """--state runs the same summarize/exit contract over the
    state-transition observatory registry."""
    from lighthouse_tpu.observability import stage_profile

    p = str(tmp_path / "state_profile.json")
    reg = stage_profile.StageProfileRegistry(p)
    reg.record_stage("altair", "rewards_penalties", 64, 0.004, ops=64)
    reg.record_stage("altair", "ssz_hashing", 64, 0.001)
    assert reg.save(force=True)
    res = _run_report("--state", "--path", p, "--json")
    assert res.returncode == 0, res.stderr
    out = json.loads(res.stdout)
    assert out["total_calls"] == 2
    assert out["top_sinks"][0]["stage"] == "rewards_penalties"
    assert out["stages"]["ssz_hashing"]["calls"] == 1
    # human table renders too
    res = _run_report("--state", "--path", p)
    assert res.returncode == 0
    assert "rewards_penalties" in res.stdout
    assert "wall-time sinks" in res.stdout


def test_report_tool_state_mode_error_contract(tmp_path):
    # missing file
    res = _run_report("--state", "--path", str(tmp_path / "no.json"),
                      "--json")
    assert res.returncode == 1
    assert "error" in json.loads(res.stdout)
    # empty registry
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"schema": 1, "rows": []}))
    res = _run_report("--state", "--path", str(empty), "--json")
    assert res.returncode == 1
    assert "no stages recorded" in json.loads(res.stdout)["error"]
    # rows but zero calls
    zero = tmp_path / "zero.json"
    zero.write_text(json.dumps({"schema": 1, "rows": [{
        "fork": "altair", "stage": "slashings", "vbucket": "<=256",
        "calls": 0, "total_ms": 0.0,
    }]}))
    res = _run_report("--state", "--path", str(zero), "--json")
    assert res.returncode == 1
    assert "no recorded calls" in json.loads(res.stdout)["error"]
    # malformed row (kernel-profile shape fed to --state)
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"schema": 1, "rows": [{
        "kernel": "k", "shape": "s", "topology": "d1",
        "launches": 3, "total_ms": 1.0,
    }]}))
    res = _run_report("--state", "--path", str(bad), "--json")
    assert res.returncode == 1
