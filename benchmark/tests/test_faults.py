"""A whole run at a tiny size with the device check stubbed and the
kernel layer replaced underneath the service: sound verdicts give
`correct` true, and each fault a cell can have gives it false.

Faults, planted where the verdict is produced (crypto/tpu/bls.py's
entry points, which the service reaches through SignatureVerifier):
  first_half    half of each batch left out: only its first half is
                checked
  second_half   the mirror: only its second half is checked
  flipped       an answer altered where it is produced: every fourth
                call's verdict inverted
  control       the reference with the pairing check left out, in the
                program's place: a set is accepted when its signature
                is a point of G2 (every signature here is)
and one that must show in `failed`:
  host          the device raises: the service's host verifier refuses
"""

import argparse
import io
import json
import os
import threading
import time
from contextlib import redirect_stdout

import pytest

from harness import cells, program, session, traffic
from reference import pool as message_pool
from reference.verdicts import Reference

from conftest import BENCH_DIR, ROOT

TINY = dict(SLOTS_PER_EPOCH=4, MAX_COMMITTEES_PER_SLOT=4,
            TARGET_COMMITTEE_SIZE=16)
CELLS = ("att_gossip_1m.saturate", "block_1m.import")


class _Dev:
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


def _tiny_cell(name):
    cell = cells.load(BENCH_DIR, name, os.path.join(ROOT, "BENCHMARK.json"))
    cfg = json.loads(json.dumps(cell.config))
    cfg["active_validators"] = 4096
    cfg["preset"].update(TINY)
    if "SYNC_COMMITTEE_SIZE" in cfg["preset"]:
        cfg["preset"]["SYNC_COMMITTEE_SIZE"] = 32
    cfg["program_env"] = {"LTPU_SHAPE_SETS_MENU": "4"}
    tr = json.loads(json.dumps(cell.traffic))
    if tr["request"]["unit"] == "block":
        tr["request"]["ring_slots"] = 4
    else:
        tr["request"]["count"] = 16
    cell.config, cell.traffic = cfg, tr
    return cell


class _Kernel:
    """Stand-in for the device entry points of crypto/tpu/bls.py."""

    def __init__(self, fault):
        self.fault = fault
        self.truth = {}
        self.calls = 0
        self.lock = threading.Lock()

    def _verdicts(self, sets):
        time.sleep(0.01)
        with self.lock:
            self.calls += 1
            call = self.calls
        if self.fault == "host":
            raise RuntimeError("device lost")
        if self.fault == "control":
            return [True] * len(sets)
        truth = [self.truth[id(s)] for s in sets]
        half = len(sets) // 2
        if self.fault == "first_half":
            truth = truth[:half] + [True] * (len(sets) - half)
        if self.fault == "second_half":
            truth = [True] * half + truth[half:]
        if self.fault == "flipped" and call % 4 == 0:
            truth = [not v for v in truth]
        return truth

    def verify_signature_sets(self, sets, dst=None, rng=None):
        return all(self._verdicts(list(sets)))

    def verify_signature_sets_per_set(self, sets, dst=None):
        return self._verdicts(list(sets))


@pytest.fixture
def stubbed(monkeypatch):
    import lighthouse_tpu.crypto.backend as backend
    from lighthouse_tpu.crypto.tpu import bls as tb

    def install(fault):
        kernel = _Kernel(fault)
        ref = Reference(message_pool.load())
        build = traffic.build

        def recording_build(*a, **kw):
            plan = build(*a, **kw)
            for req in plan.warmup + plan.trace + plan.window:
                for s, ok in zip(req.sets, ref.request(req)):
                    kernel.truth[id(s)] = ok
            return plan

        monkeypatch.setattr(backend, "_device_platform", lambda: "tpu")
        monkeypatch.setattr(program, "devices", lambda: [_Dev()])
        monkeypatch.setattr(program, "load_programs",
                            lambda compiles, width, per_set: {})
        monkeypatch.setattr(traffic, "build", recording_build)
        monkeypatch.setattr(tb, "verify_signature_sets",
                            kernel.verify_signature_sets)
        monkeypatch.setattr(tb, "verify_signature_sets_per_set",
                            kernel.verify_signature_sets_per_set)
        monkeypatch.setattr(tb, "plan_pipeline", lambda sets, *a: None)
        return kernel

    return install


def _run(cell, trace=0):
    args = argparse.Namespace(workload=cell.name, seed=2**31 + 99,
                              seconds=2.0, trace=trace)
    out = io.StringIO()
    with redirect_stdout(out):
        rc = session.run(cell, args, time.monotonic())
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


@pytest.mark.parametrize("name", CELLS)
def test_sound_verdicts_are_correct(stubbed, name):
    stubbed("sound")
    rc, result = _run(_tiny_cell(name))
    assert rc == 0
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert result["checks"]["wrong_verdicts"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("fault", ["first_half", "second_half", "flipped",
                                   "control"])
@pytest.mark.parametrize("name", CELLS)
def test_each_fault_is_not_correct(stubbed, name, fault):
    kernel = stubbed(fault)
    cell = _tiny_cell(name)
    if fault in ("flipped", "control"):
        # the warm-up must pass for the run to reach its window
        kernel.fault, planted = "sound", fault
        orig = kernel._verdicts

        def after_warmup(sets):
            if kernel.calls >= 4:
                kernel.fault = planted
            return orig(sets)

        kernel._verdicts = after_warmup
    rc, result = _run(cell)
    assert rc == 0
    assert result["correct"] is False, result
    assert result["checks"]["wrong_verdicts"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_the_host_path_shows_in_failed(stubbed, name):
    kernel = stubbed("sound")
    orig = kernel._verdicts

    def lose_device_after_warmup(sets):
        if kernel.calls >= 4:
            kernel.fault = "host"
        return orig(sets)

    kernel._verdicts = lose_device_after_warmup
    rc, result = _run(_tiny_cell(name))
    assert rc == 0
    assert result["failed"] > 0
    assert result["failed"] <= result["attempted"]


@pytest.mark.parametrize("name", CELLS)
def test_a_traced_run_reads_its_window(stubbed, name):
    """The traced slice runs before the window and the run still
    reaches its result; with no device plane (and the kernel stubbed)
    the readers find nothing to read and leave their metrics out."""
    stubbed("sound")
    cell = _tiny_cell(name)
    rc, result = _run(cell, trace=1)
    assert rc == 0 and result["correct"] is True
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert result["metrics"] == {}
