"""Request unit "block": a block's signature sets in the order block
processing makes them: the proposal and RANDAO of its proposer (one
pubkey each), the aggregates of the two previous slots' committees, the
sync aggregate.  Blocks walk a ring of `ring_slots` slots of the
epoch, so block r carries the aggregates of ring slots r - 1 and r - 2
and each block brings one slot of keys the one before it had not
seen.  Participation follows the configuration's `assumed` shares: the
same counts every seed, in a seeded order."""

import numpy as np

KEYS = {"ring_slots"}


def _participants(members, absent, rng):
    keep = np.sort(rng.choice(len(members), len(members) - absent,
                              replace=False))
    return [int(members[k]) for k in keep]


def requests(config, dep, msgs, params, n):
    ring = int(params["ring_slots"])
    assumed = config["assumed"]
    rng = dep.rng
    lo, hi = assumed["aggregate_participation"]
    slot_aggs = []
    for q in range(ring):
        comms = dep.committees(q)
        spread = [round(len(c) * (1 - (lo + (hi - lo) * k
                                       / max(dep.per_slot - 1, 1))))
                  for k, c in enumerate(comms)]
        absent = rng.permutation(spread)
        slot_aggs.append([
            (_participants(c, int(a), rng), msgs(("att", q, i)))
            for i, (c, a) in enumerate(zip(comms, absent))])
    size = int(config["preset"]["SYNC_COMMITTEE_SIZE"])
    sync = rng.choice(dep.n, size, replace=False)
    sync_absent = size - round(size * assumed["sync_participation"])
    blocks = []
    for r in range(ring):
        proposer = int(rng.integers(dep.n))
        sets = [([proposer], msgs(("proposal", r))),
                ([proposer], msgs(("randao", r)))]
        for q in ((r - 1) % ring, (r - 2) % ring):
            sets.extend(slot_aggs[q])
        sets.append((_participants(sync, sync_absent, rng),
                     msgs(("sync", r))))
        blocks.append(sets)
    return [blocks[b % ring] for b in range(n)]
