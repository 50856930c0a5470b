"""Frozen copy of the pre-tier naive aggregation pool.

This is the OLD `OperationPool.insert_attestation` path verbatim: a host
G2 decompress → point-add → compress round-trip per insert, Python-list
bitset loops, no validation.  It exists as (a) the differential oracle
for the aggregation tier's byte-identity property tests and (b) the
per-insert host-aggregation baseline the tier is compared against.
Do not "fix" it — its value is being exactly what the tier replaced.
"""

from collections import defaultdict

from ..ssz import hash_tree_root


def _bits_or(a, b):
    return [x | y for x, y in zip(a, b)]


def _bits_overlap(a, b):
    return any(x & y for x, y in zip(a, b))


class NaiveAggregationPool:
    """data root -> [{"bits", "att"}] with eager per-insert host math."""

    def __init__(self):
        self.attestations = defaultdict(list)

    def insert_attestation(self, attestation):
        from ..crypto.ref import bls as RB
        from ..crypto.ref.curves import g2_compress, g2_decompress

        key = hash_tree_root(attestation.data)
        bits = list(attestation.aggregation_bits)
        for entry in self.attestations[key]:
            if not _bits_overlap(entry["bits"], bits):
                agg = RB.aggregate(
                    [
                        g2_decompress(
                            bytes(entry["att"].signature), subgroup_check=False
                        ),
                        g2_decompress(
                            bytes(attestation.signature), subgroup_check=False
                        ),
                    ]
                )
                entry["att"].aggregation_bits = _bits_or(entry["bits"], bits)
                entry["att"].signature = g2_compress(agg)
                entry["bits"] = list(entry["att"].aggregation_bits)
                return
        self.attestations[key].append(
            {"bits": bits, "att": attestation.copy()}
        )

    def entries_for(self, data_root):
        return self.attestations.get(bytes(data_root), [])

    def packed_pairs(self):
        """Sorted (bits tuple, signature bytes) across all entries — the
        comparison surface for byte-identity assertions."""
        out = []
        for entries in self.attestations.values():
            for e in entries:
                out.append(
                    (tuple(int(b) for b in e["bits"]), bytes(e["att"].signature))
                )
        return sorted(out)
