"""The trace reduction: busy time as the union of device op intervals,
idle gaps, and the clock shift onto the span clock."""

import json
import os

import pytest

from harness import trace

from conftest import BENCH_DIR

FIXTURE = os.path.join(BENCH_DIR, "tests", "fixtures", "trace_v5e.json")
MS = 1_000_000


def _planes():
    ops = [("fusion.1", 10 * MS, 30 * MS),        # 10-40
           ("fusion.2", 30 * MS, 20 * MS),        # 30-50, overlaps
           ("copy.3", 70 * MS, 10 * MS),          # 70-80
           ("fusion.1", 95 * MS, 20 * MS)]        # 95-115, past the end
    modules = [("jit_batched_verify_kernel(1)", 10 * MS, 40 * MS),
               ("jit_other(2)", 70 * MS, 10 * MS)]
    return [("/device:TPU:0", {"XLA Ops": ops, "XLA Modules": modules}),
            ("/host:CPU", {"python3": [("x", 0, 5)]})]


def test_union_and_gaps():
    # the mark at 5 ms of trace time is monotonic 100.0; window to 100.1
    s = trace.reduce_planes(_planes(), 100.0, 100.1, 5 * MS)
    assert s.devices == 1
    # busy: 10-50, 70-80, 95-105 (clipped) ms of trace time
    assert s.busy_s == pytest.approx(0.040 + 0.010 + 0.010)
    assert s.window_s == pytest.approx(0.1)
    gaps = sorted((round(a - 100, 4), round(b - 100, 4)) for a, b in s.gaps)
    assert gaps == [(0.0, 0.005), (0.045, 0.065), (0.075, 0.09)]
    assert s.ops["fusion.1"] == pytest.approx(0.050)


def test_a_plane_without_op_line_uses_every_line():
    planes = [("/device:TPU:0", {"Steps": [("s", 0, 10 * MS)]})]
    s = trace.reduce_planes(planes, 0.0, 0.02, 0)
    assert s.busy_s == pytest.approx(0.01)


def test_profiler_output_is_read(tmp_path):
    """A trace recorded here (CPU: no device plane) still yields the
    clock mark through ProfileData."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    tr = trace.Tracer(str(tmp_path / "t"))
    tr.start()
    f(jnp.ones(4)).block_until_ready()
    path = tr.stop()
    s = trace.reduce(path, tr)
    assert s.devices == 0 and s.busy_s == 0.0
    assert s.window_s > 0


def test_recorded_v5e_trace():
    """Two verify launches recorded on a TPU v5e chip (a cut of a
    profile, the device planes' events with the `bench.clock` mark),
    after which the device dropped its trace buffers: the window ends at
    the drop."""
    with open(FIXTURE) as f:
        fx = json.load(f)
    planes = [(name, lines) for name, lines in fx["planes"]]
    t0 = 1000.0
    t1 = t0 + (fx["stop_ns"] - fx["mark_ns"]) / 1e9
    s = trace.reduce_planes(planes, t0, t1, fx["mark_ns"])
    assert s.devices == 1
    assert s.window_s == pytest.approx((2591444862 - 50179755) / 1e9)
    assert s.busy_s == pytest.approx((1273111268 + 1251433256) / 1e9)
    # idle: 9.7 ms before the first launch, 7.0 ms between the two
    gaps = sorted(b - a for a, b in s.gaps)
    assert gaps == pytest.approx([1e-9, 0.007017359, 0.009703223],
                                 abs=1e-7)
