"""Dispatcher (verify_service/service.py): milliseconds a verify batch
spends with the dispatcher waiting for staged chunks, the mean over the
window's batches of their summed `prep_wait` spans.  A batch is the
dispatcher's `kernel` span; the window's batches are those whose
`kernel` span starts and ends in it, and a batch's waits are the
`prep_wait` spans that start inside its `kernel` span.  Notes: the part
spent after the batch's verdict settled False (`drain`), and the
batches.  None where the program records no `prep_wait` span."""


def read(w):
    batches = [s for s in w.spans if s[0] == "kernel"
               and w.t0 <= s[1] and s[2] <= w.t_end]
    waits = [s for s in w.spans if s[0] == "prep_wait"]
    if not batches or not waits:
        return None
    total = drain = 0.0
    for _, k0, k1, _ in batches:
        for _, a, b, attrs in waits:
            if k0 <= a < k1:
                total += b - a
                if attrs.get("drain"):
                    drain += b - a
    n = len(batches)
    return {"value": 1e3 * total / n, "drain_ms": 1e3 * drain / n,
            "batches": n}
