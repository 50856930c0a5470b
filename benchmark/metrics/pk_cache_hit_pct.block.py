"""Host prep, pubkey limb cache: hits over lookups in the window, from
verify_pubkey_cache_{hits,misses}_total."""

def read(w):
    hits = w.delta("pk_cache_hits")
    total = hits + w.delta("pk_cache_misses")
    return 100.0 * hits / total if total else None
