"""The one traffic generator: reads a configuration and a traffic mix,
draws everything from the seed, and returns the requests of a run.

A deployment (configs/<name>.json) fixes the validator set and the
preset: validator i has the secret i + 1 and the pubkey (i + 1)G1, an
epoch's shuffle (a permutation drawn from the seed) splits the validators
into SLOTS_PER_EPOCH slots of committees as the spec's
compute_committee slices them, and messages come from the committed
pool, in an order drawn from the seed.

A mix (traffic/<name>.json) names its request unit and its loop, each a
module of its own found by name (cells.py):

    traffic/units/<unit>.py   requests(config, dep, msgs, params, n):
                              n requests, each [(validator indices,
                              message pool index)], one per set
    traffic/loops/<kind>.py   how the requests are sent (loops.py)

Invalid sets carry the signature of the set after them in the stream:
a point of G2, valid for another key or message.  Every `every`-th
window request (positions every - 1, 2 * every - 1, ...) carries one,
in the device chunk that `chunks` names in turn ("first" or "last"), at
an offset inside that chunk drawn from the seed.  The chunks are fixed
by the mix and not drawn from the seed: the service stops launching a
request's chunks at its first failing one, so the chunk sets the work,
and every seed gets the same work, in another order.
"""

import numpy as np

from reference import bls12_381 as B

CHUNKS = ("first", "last")


class SetMeta:
    """What the reference needs of one set: the sum of its signers'
    secrets and its message's pool index."""

    __slots__ = ("secret", "msg")

    def __init__(self, secret, msg):
        self.secret = secret
        self.msg = msg


class Request:
    __slots__ = ("index", "sets", "meta", "invalid")

    def __init__(self, index, sets, meta, invalid):
        self.index = index
        self.sets = sets          # [program SignatureSet]
        self.meta = meta          # [SetMeta], one per set
        self.invalid = invalid    # positions carrying a foreign signature


class Plan:
    """Warm-up requests, the traced slice's requests (a traced run
    only) and the window's requests, which a loop may send more than
    once."""

    def __init__(self, warmup, trace, window):
        self.warmup = warmup
        self.trace = trace
        self.window = window


class Deployment:
    """Committees, proposers and sync committee of one seeded epoch."""

    def __init__(self, config, seed):
        preset = config["preset"]
        self.n = int(config["active_validators"])
        self.slots = int(preset["SLOTS_PER_EPOCH"])
        self.per_slot = max(1, min(
            int(preset["MAX_COMMITTEES_PER_SLOT"]),
            self.n // self.slots // int(preset["TARGET_COMMITTEE_SIZE"])))
        self.rng = np.random.default_rng(seed)
        self.shuffle = self.rng.permutation(self.n)

    def committees(self, slot):
        """The slot's committees (arrays of validator indices), sliced
        as consensus-specs compute_committee slices the shuffle."""
        if not 0 <= slot < self.slots:
            raise ValueError(f"slot {slot} is not in the epoch's "
                             f"{self.slots} slots")
        count = self.slots * self.per_slot
        out = []
        for c in range(self.per_slot):
            i = slot * self.per_slot + c
            lo, hi = self.n * i // count, self.n * (i + 1) // count
            out.append(self.shuffle[lo:hi])
        return out


class Messages:
    """Pool entries handed out in a seeded order, one per key."""

    def __init__(self, pool, rng):
        self.pool = pool
        self.order = rng.permutation(len(pool))
        self.taken = {}

    def __call__(self, key):
        if key not in self.taken:
            if len(self.taken) >= len(self.pool):
                raise ValueError("the message pool is exhausted")
            self.taken[key] = int(self.order[len(self.taken)])
        return self.taken[key]


def _signatures(metas, pool):
    """Valid signature [secret]H(m) of every SetMeta, one table of
    doublings per message."""
    by_msg = {}
    for i, m in enumerate(metas):
        by_msg.setdefault(m.msg, []).append(i)
    out = [None] * len(metas)
    for msg, idx in by_msg.items():
        pts = B.g2_multiples(pool[msg][1], [metas[i].secret for i in idx])
        for i, p in zip(idx, pts):
            out[i] = p
    return out


def invalid_positions(spec, seed, sizes, bucket):
    """{window request index: [position]} of the sets that carry a
    foreign signature; `sizes` are the window requests' set counts."""
    if not spec:
        return {}
    every = int(spec["every"])
    chunks = spec["chunks"]
    rng = np.random.default_rng([seed, 2])
    out = {}
    for j, i in enumerate(range(every - 1, len(sizes), every)):
        n = sizes[i]
        lo = 0 if chunks[j % len(chunks)] == "first" else \
            (n - 1) // bucket * bucket
        hi = min(lo + bucket, n)
        out[i] = [lo + int(rng.integers(hi - lo))]
    return out


def build(cell, seed, pool, bucket, signature_set, traced=False):
    """The run's Plan.  `bucket` is the program's set bucket (the size
    of a device chunk); `signature_set(sig, pks, msg)` builds the
    program's input type.  A traced run also gets the loop's traced
    slice requests, all valid, placed between the warm-up and the
    window."""
    mix = cell.traffic
    dep = Deployment(cell.config, seed)
    msgs = Messages(pool, np.random.default_rng([seed, 1]))
    n_warm = int(mix["warmup"]["requests"])
    n_trace = cell.loop.SLICE_REQUESTS if traced else 0
    n_window = cell.loop.window_requests(mix["loop"])
    lead = n_warm + n_trace       # requests before the window's
    raw = cell.unit.requests(cell.config, dep, msgs, mix["request"],
                             lead + n_window)

    # keys and valid signatures of every distinct set
    distinct = {}
    for req in raw:
        for pks, m in req:
            distinct.setdefault((tuple(pks), m), None)
    keys = B.pubkeys_of_secrets(v + 1 for pks, _ in distinct for v in pks)
    metas = [SetMeta(sum(v + 1 for v in pks), m) for pks, m in distinct]
    for (k, meta), sig in zip(zip(list(distinct), metas),
                              _signatures(metas, pool)):
        distinct[k] = (meta, sig)

    flat = [(pks, m) for req in raw for pks, m in req]
    sizes = [len(req) for req in raw]
    bad = {lead + i: pos for i, pos in invalid_positions(
        mix.get("invalid"), seed, sizes[lead:], bucket).items()}

    requests, g = [], 0
    for i, req in enumerate(raw):
        sets, metas_i = [], []
        for j, (pks, m) in enumerate(req):
            meta, sig = distinct[(tuple(pks), m)]
            if j in bad.get(i, ()):
                nxt = flat[(g + j + 1) % len(flat)]
                sig = distinct[(tuple(nxt[0]), nxt[1])][1]
            sets.append(signature_set(sig, [keys[v + 1] for v in pks],
                                      pool[m][0]))
            metas_i.append(meta)
        requests.append(Request(i, sets, metas_i, sorted(bad.get(i, []))))
        g += len(req)
    return Plan(requests[:n_warm], requests[n_warm:lead], requests[lead:])
