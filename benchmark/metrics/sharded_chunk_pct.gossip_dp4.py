"""Mesh placement (crypto/tpu/sharding.py MeshPlan.place_verify_args):
100 x the window's batched `place` spans whose chunk was split over
four chips (`shards` == 4) over all of them.  A chunk that falls back to
one device (a set axis the mesh cannot divide) runs all its sets on one
chip and reads below 100.  Notes: the chunks.  None where the program
records no `place` span."""

from harness import readers

SHARDS = 4


def read(w):
    spans = [s for s in readers.spans_named(w, "place")
             if not s[3].get("per_set")]
    if not spans:
        return None
    split = sum(s[3]["shards"] == SHARDS for s in spans)
    return {"value": 100.0 * split / len(spans), "chunks": len(spans)}
