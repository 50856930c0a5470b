"""The span readers of metrics/: launch_ms_per_set.*, launch_gap_pct.*
and prep_wait_ms.block, on hand-built span lists."""

import pytest

from harness import cells, readers

from conftest import BENCH_DIR


def _read(metric, spans, t0=100.0, t_end=110.0):
    w = readers.Window(cell=None, t0=t0, t_end=t_end, records=[],
                       before={}, after={}, spans=sorted(spans, key=lambda s:
                                                         s[1]),
                       device=None, programs={})
    return cells.module(BENCH_DIR, "metrics", metric).read(w)


def launch(a, b, kernel="bls_batched_verify"):
    return ("launch", a, b, {"parent": "kernel", "kernel": kernel,
                             "shape": "32x1", "source": "aot"})


def chunk(a, b, sets, lanes=32, per_set=False):
    return ("device_chunk", a, b, {"sets": sets, "lanes": lanes,
                                   "per_set": per_set, "host_prep_ms": 7.0})


@pytest.mark.parametrize("cell", ["gossip", "block"])
def test_launch_ms_per_set_counts_batched_launches_in_the_window(cell):
    spans = [launch(99.0, 100.5), chunk(99.0, 100.5, 32),   # starts before
             launch(101.0, 102.0), chunk(100.99, 102.0, 32),
             launch(102.0, 103.5), chunk(101.99, 103.5, 16),
             launch(104.0, 105.0, kernel="bls_per_set_verify"),
             chunk(103.99, 105.0, 8, per_set=True)]
    got = _read(f"launch_ms_per_set.{cell}", spans)
    assert got["value"] == pytest.approx(2500.0 / 48)
    assert got["launches"] == 2 and got["sets"] == 48 and got["lanes"] == 64
    assert got["mean_launch_ms"] == pytest.approx(1250.0)


@pytest.mark.parametrize("cell", ["gossip", "block"])
def test_launch_ms_per_set_is_none_without_launch_spans(cell):
    assert _read(f"launch_ms_per_set.{cell}",
                 [chunk(101.0, 102.0, 32)]) is None


@pytest.mark.parametrize("cell", ["gossip", "block"])
def test_launch_gap_pct_unions_overlaps_and_clips_at_the_edges(cell):
    spans = [launch(99.0, 101.0),                 # clipped to 100-101
             launch(102.0, 104.0), launch(103.0, 105.0),   # union 102-105
             launch(109.0, 111.0),                # clipped to 109-110
             ("queue_wait", 105.0, 109.5, {}),
             ("kernel", 105.2, 111.0, {}),
             ("prep_wait", 105.2, 108.9, {"parent": "kernel"})]
    got = _read(f"launch_gap_pct.{cell}", spans)
    # covered 1 + 3 + 1 = 5 s of 10
    assert got["value"] == pytest.approx(50.0)
    assert got["longest_gap_ms"] == pytest.approx(4000.0)
    assert got["longest_gap_in"] == ["kernel", "prep_wait", "queue_wait"]


@pytest.mark.parametrize("cell", ["gossip", "block"])
def test_launch_gap_pct_none_without_a_launch_in_the_window(cell):
    spans = [launch(90.0, 99.0), launch(111.0, 112.0),
             ("kernel", 100.0, 105.0, {})]
    assert _read(f"launch_gap_pct.{cell}", spans) is None


def test_launch_gap_pct_fully_covered_window():
    got = _read("launch_gap_pct.gossip", [launch(99.0, 111.0)])
    assert got["value"] == pytest.approx(0.0)
    assert got["longest_gap_ms"] == 0.0 and "longest_gap_in" not in got


def _wait(a, b, drain=False):
    return ("prep_wait", a, b, {"parent": "kernel", "chunk": 0,
                                "drain": drain})


def test_prep_wait_ms_means_over_the_batches_inside_the_window():
    spans = [("kernel", 99.0, 100.5, {}), _wait(99.0, 99.5),   # before
             ("kernel", 100.5, 103.0, {}), _wait(100.5, 100.65),
             _wait(101.0, 101.01),
             ("kernel", 103.0, 105.0, {}), _wait(103.0, 103.15),
             _wait(103.5, 103.6, drain=True), _wait(103.7, 103.8, drain=True),
             ("kernel", 109.0, 111.0, {}), _wait(109.0, 109.2)]  # past end
    got = _read("prep_wait_ms.block", spans)
    assert got["batches"] == 2
    assert got["value"] == pytest.approx((160.0 + 350.0) / 2)
    assert got["drain_ms"] == pytest.approx(200.0 / 2)


def test_prep_wait_ms_none_without_prep_wait_spans():
    assert _read("prep_wait_ms.block", [("kernel", 101.0, 103.0, {})]) is None
    assert _read("prep_wait_ms.block", [_wait(101.0, 102.0)]) is None
