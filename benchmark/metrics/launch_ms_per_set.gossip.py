"""Device launch (crypto/tpu/compile_cache.py CachedKernel): milliseconds
of batched-verify launch per real set in the window, from the `launch`
spans of bls_batched_verify (the executable call through
block_until_ready) over the `sets` of the batched device_chunk spans,
both counted when they start in the window.  Notes: the launches, their
sets and lanes, the mean launch.  None where the program records no
`launch` span."""

from harness import readers

KERNEL = "bls_batched_verify"


def read(w):
    launches = [s for s in readers.spans_named(w, "launch")
                if s[3].get("kernel") == KERNEL]
    chunks = [s for s in readers.spans_named(w, "device_chunk")
              if not s[3].get("per_set")]
    sets = sum(s[3]["sets"] for s in chunks)
    if not launches or not sets:
        return None
    ms = 1e3 * sum(s[2] - s[1] for s in launches)
    return {"value": ms / sets, "launches": len(launches), "sets": sets,
            "lanes": sum(s[3]["lanes"] for s in chunks),
            "mean_launch_ms": ms / len(launches)}
