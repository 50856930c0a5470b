"""Mesh placement (crypto/tpu/sharding.py MeshPlan.place_verify_args):
the mean duration of the batched `place` spans that start in the window,
the host-to-mesh transfer of each prepared chunk on the dispatcher
thread, between its `prep_wait` and its `launch`.  Notes: the chunks,
their mean `bytes`, and the placement's share of the window (the summed
`place` spans over the window, in %).  None where the program records
no `place` span."""

from harness import readers


def read(w):
    spans = [s for s in readers.spans_named(w, "place")
             if not s[3].get("per_set")]
    if not spans:
        return None
    total = sum(s[2] - s[1] for s in spans)
    return {"value": 1e3 * total / len(spans), "chunks": len(spans),
            "mean_bytes": sum(s[3]["bytes"] for s in spans) / len(spans),
            "window_pct": 100.0 * total / (w.t_end - w.t0)}
