"""The mesh-placement readers of metrics/: place_ms.gossip_dp4 and
sharded_chunk_pct.gossip_dp4, on hand-built span lists."""

import pytest

from harness import cells, readers

from conftest import BENCH_DIR


def _read(metric, spans, t0=100.0, t_end=110.0):
    w = readers.Window(cell=None, t0=t0, t_end=t_end, records=[],
                       before={}, after={},
                       spans=sorted(spans, key=lambda s: s[1]),
                       device=None, programs={})
    return cells.module(BENCH_DIR, "metrics", metric).read(w)


def place(a, b, shards=4, nbytes=40_000, per_set=False):
    return ("place", a, b, {"parent": "kernel", "chunk": 0, "shards": shards,
                            "bytes": nbytes, "per_set": per_set})


def test_place_ms_means_the_batched_place_spans_in_the_window():
    spans = [place(99.9, 100.1),                           # starts before
             place(101.0, 101.002), place(102.0, 102.004, nbytes=20_000),
             place(103.0, 103.5, per_set=True),            # per-set chunk
             place(110.5, 110.6)]                          # past the end
    got = _read("place_ms.gossip_dp4", spans)
    assert got["value"] == pytest.approx(3.0)
    assert got["chunks"] == 2
    assert got["mean_bytes"] == pytest.approx(30_000)
    assert got["window_pct"] == pytest.approx(100.0 * 0.006 / 10.0)


def test_sharded_chunk_pct_all_chunks_split():
    spans = [place(100.0 + i, 100.001 + i) for i in range(8)]
    got = _read("sharded_chunk_pct.gossip_dp4", spans)
    assert got == {"value": pytest.approx(100.0), "chunks": 8}


def test_sharded_chunk_pct_counts_a_chunk_left_on_one_device():
    spans = [place(100.0 + i, 100.001 + i) for i in range(3)]
    spans.append(place(104.0, 104.0001, shards=1))
    spans.append(place(105.0, 105.0001, shards=1, per_set=True))
    got = _read("sharded_chunk_pct.gossip_dp4", spans)
    assert got["value"] == pytest.approx(75.0) and got["chunks"] == 4


@pytest.mark.parametrize("metric", ["place_ms.gossip_dp4",
                                    "sharded_chunk_pct.gossip_dp4"])
def test_place_readers_none_without_a_place_span(metric):
    spans = [("launch", 101.0, 102.0, {"parent": "kernel",
                                       "kernel": "bls_batched_verify"}),
             ("device_chunk", 100.99, 102.0, {"sets": 32, "lanes": 32,
                                              "per_set": False}),
             place(90.0, 90.1), place(104.0, 104.1, per_set=True)]
    assert _read(metric, spans) is None
