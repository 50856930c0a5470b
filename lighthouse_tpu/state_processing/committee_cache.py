"""Per-epoch committee cache.

Mirror of the reference's shuffling cache
(/root/reference/beacon_node/beacon_chain/src/shuffling_cache.rs and
`BeaconState` committee caches in consensus/types): the swap-or-not
shuffle over the active-validator set runs ONCE per (state, epoch); every
committee lookup afterwards is an O(1) slice.  Round-1's
`get_beacon_committee` re-shuffled the whole registry per attestation
— at mainnet scale that is ~128 full shuffles per block
instead of one.

The cache attaches to the state instance and is keyed by
(epoch, registry rev at build time is NOT enough — the active set for an
epoch is fixed once the epoch starts, and states are copied/advanced
constantly), so the key is (epoch, seed, registry length); the active set
for a given epoch cannot change once the seed is observable.
"""

import numpy as np

from ..observability import stage_profile
from .shuffle import shuffle_list


class EpochCommittees:
    """All committees of one epoch: one shuffle, O(1) slicing."""

    def __init__(self, active_indices, seed, committees_per_slot, preset):
        self.active = np.asarray(active_indices, dtype=np.uint64)
        self.seed = seed
        self.committees_per_slot = committees_per_slot
        self.slots_per_epoch = preset.slots_per_epoch
        self.shuffled = shuffle_list(self.active, seed)
        self.count = committees_per_slot * preset.slots_per_epoch

    def committee(self, slot, index):
        committee_index = (slot % self.slots_per_epoch) * self.committees_per_slot + index
        n = len(self.shuffled)
        start = n * committee_index // self.count
        end = n * (committee_index + 1) // self.count
        return self.shuffled[start:end]


def committees_for_epoch(state, epoch, preset):
    """Fetch (or build) the committee cache for `epoch` on this state."""
    from . import phase0

    caches = getattr(state, "_committee_caches", None)
    if caches is None:
        caches = {}
        object.__setattr__(state, "_committee_caches", caches)
    seed = phase0.get_seed(state, epoch, phase0.DOMAIN_BEACON_ATTESTER, preset)
    key = (epoch, seed, len(state.validators))
    cache = caches.get(key)
    if cache is None:
        with stage_profile.timer(state).stage("committee_cache_build"):
            indices = phase0.get_active_validator_indices_np(state, epoch)
            per_slot = phase0.get_committee_count_per_slot(state, epoch, preset)
            cache = EpochCommittees(indices, seed, per_slot, preset)
        if len(caches) > 8:
            caches.clear()
        caches[key] = cache
    return cache
