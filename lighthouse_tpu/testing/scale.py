"""Scaled-state construction rig — build N-validator states in O(arrays).

The reference hits the 1M-validator regime with mainnet data
(SURVEY.md §5.7); tests and benchmarks here synthesize equivalent states
directly into the SoA registry (types/collections.py) without per-object
Python work: random pubkeys (signature verification is not part of the
epoch-replay benchmark — BASELINE.md config 5 runs the BlockReplayer with
NoVerification, mirroring /root/reference/consensus/state_processing/src/
block_replayer.rs strategy seams), full effective balances, and
`participation`-dense pending attestations for the previous/current epoch.
"""

import numpy as np

from ..state_processing import phase0
from ..state_processing.committee_cache import committees_for_epoch
from ..types.containers import AttestationData, Checkpoint
from ..types.state import state_types

FAR = 2**64 - 1
MAX_EB = 32 * 10**9


def make_pubkey_pool(k=64, seed=0):
    """(k, 48) uint8 array of DISTINCT VALID compressed G1 pubkeys —
    generator multiples, built with k cheap incremental adds.  Scaled
    registries tile this pool so pubkey-cache import (which dedupes by
    encoding) and PK_CACHE gathers see real curve points at any N."""
    from ..crypto.ref.curves import G1_GEN, g1_add, g1_compress

    out = np.empty((k, 48), np.uint8)
    p = G1_GEN
    for i in range(k):
        out[i] = np.frombuffer(g1_compress(p), np.uint8)
        p = g1_add(p, G1_GEN)
    return out


def make_signature_pool(k=256):
    """k distinct valid compressed G2 points (generator multiples via
    incremental adds) — synthetic gossip signatures and selection
    proofs.  Not signatures OVER anything: scale replays run against a
    fake/verdict-free backend; the pool keeps every decompress path
    (insert, flush, signature-set construction) on real curve points."""
    from ..crypto.ref.curves import G2_GEN, g2_add, g2_compress

    out = []
    p = G2_GEN
    for _ in range(k):
        out.append(g2_compress(p))
        p = g2_add(p, G2_GEN)
    return out


def make_scaled_state(n_validators, spec, epoch=4, participation=0.99, seed=0,
                      pubkey_pool=None, fork="phase0"):
    """A BeaconState at the start of `epoch` with a full previous-epoch
    attestation load at the given participation rate.

    `pubkey_pool` (from `make_pubkey_pool`) tiles valid pubkeys across
    the registry instead of random bytes; `fork="altair"` builds the
    Altair container (dense participation flags, zero inactivity scores,
    sync committees drawn from the registry) so sync-committee traffic
    has a home."""
    preset = spec.preset
    T = state_types(preset)
    rng = np.random.default_rng(seed)

    state = T.BeaconStateAltair() if fork == "altair" else T.BeaconState()
    reg = state.validators
    cap = max(16, 1 << max(n_validators - 1, 1).bit_length())
    if pubkey_pool is not None:
        reg.pubkey = pubkey_pool[np.arange(cap) % len(pubkey_pool)]
    else:
        reg.pubkey = rng.integers(0, 256, (cap, 48), dtype=np.int64).astype(np.uint8)
    reg.withdrawal_credentials = np.zeros((cap, 32), np.uint8)
    reg.effective_balance = np.full(cap, MAX_EB, np.uint64)
    reg.slashed = np.zeros(cap, bool)
    reg.activation_eligibility_epoch = np.zeros(cap, np.uint64)
    reg.activation_epoch = np.zeros(cap, np.uint64)
    reg.exit_epoch = np.full(cap, FAR, np.uint64)
    reg.withdrawable_epoch = np.full(cap, FAR, np.uint64)
    reg._n = n_validators
    reg.dirty = set(range(n_validators))
    reg.rev += 1

    bal = state.balances
    bal._a = np.full(cap, MAX_EB, np.uint64)
    bal._n = n_validators
    bal.dirty = set(range(n_validators))
    bal.rev += 1

    state.slot = epoch * preset.slots_per_epoch
    state.genesis_validators_root = b"\x11" * 32
    for i in range(len(state.randao_mixes)):
        state.randao_mixes[i] = bytes(
            rng.integers(0, 256, 32, dtype=np.int64).astype(np.uint8)
        )
    # block roots: distinct per slot so matching-head logic has targets
    for s in range(min(state.slot, len(state.block_roots))):
        state.block_roots[s % len(state.block_roots)] = (
            int(s).to_bytes(8, "little") + b"\x22" * 24
        )
    prev_epoch = epoch - 1
    state.previous_justified_checkpoint = Checkpoint(
        epoch=max(prev_epoch - 1, 0),
        root=phase0.get_block_root(state, max(prev_epoch - 1, 0), preset),
    )
    state.current_justified_checkpoint = Checkpoint(
        epoch=prev_epoch, root=phase0.get_block_root(state, prev_epoch, preset)
    )
    state.finalized_checkpoint = Checkpoint(
        epoch=max(prev_epoch - 1, 0),
        root=phase0.get_block_root(state, max(prev_epoch - 1, 0), preset),
    )
    state.justification_bits = [1, 1, 0, 0]

    if fork == "altair":
        for name in ("previous_epoch_participation",
                     "current_epoch_participation"):
            part = getattr(state, name)
            part._a = np.full(cap, 0b111, np.uint8)   # source|target|head
            part._n = n_validators
            part.dirty = set(range(n_validators))
            part.rev += 1
        scores = state.inactivity_scores
        scores._a = np.zeros(cap, np.uint64)
        scores._n = n_validators
        scores.dirty = set(range(n_validators))
        scores.rev += 1
        size = preset.sync_committee_size
        members = [bytes(reg.pubkey[i % n_validators]) for i in range(size)]
        agg = bytes(reg.pubkey[0])
        state.current_sync_committee = T.SyncCommittee(
            pubkeys=members, aggregate_pubkey=agg
        )
        state.next_sync_committee = T.SyncCommittee(
            pubkeys=members, aggregate_pubkey=agg
        )
    else:
        fill_epoch_attestations(
            state, prev_epoch, spec, participation, rng, target="previous"
        )
    return state


class _NewValidator:
    """Attribute bag matching the `Validator` container surface the
    registry's append() reads — the churn helper's deposit shape."""

    __slots__ = ("pubkey", "withdrawal_credentials", "effective_balance",
                 "slashed", "activation_eligibility_epoch",
                 "activation_epoch", "exit_epoch", "withdrawable_epoch")

    def __init__(self, pubkey, epoch):
        self.pubkey = pubkey
        self.withdrawal_credentials = b"\x00" * 32
        self.effective_balance = MAX_EB
        self.slashed = False
        self.activation_eligibility_epoch = epoch
        self.activation_epoch = epoch
        self.exit_epoch = FAR
        self.withdrawable_epoch = FAR


def churn_registry(state, spec, *, epoch, exits=0, deposits=0,
                   pubkey_pool=None, seed=0):
    """Epoch-to-epoch validator churn on a scaled state: mark `exits`
    active validators exited AT `epoch` (they leave the active set for
    `epoch` onward — `is_active_validator` is activation <= e < exit)
    and append `deposits` fresh validators activated at `epoch`.

    This is the soak's continuation seam: churned registries re-shuffle
    every later epoch's committees, grow the chain's
    `ValidatorPubkeyCache` (the `_import_new_pubkeys` path), and make
    exited validators' `bls.PK_CACHE` limb entries stale (the
    `rekey_for_churn` path).  Registry-tracking sidecar lists
    (balances, Altair participation / inactivity scores) are extended in
    step so epoch processing stays consistent.  Spec churn limits are
    deliberately NOT modeled — the rig synthesizes the post-churn
    registry directly, as `make_scaled_state` does at boot.

    Returns (exited_indices, new_index_range)."""
    rng = np.random.default_rng(seed)
    reg = state.validators
    n = len(reg)
    active = np.flatnonzero(
        (reg.activation_epoch[:n] <= np.uint64(epoch))
        & (reg.exit_epoch[:n] > np.uint64(epoch))
    )
    exits = int(min(exits, max(len(active) - 1, 0)))
    exited = (
        np.sort(rng.choice(active, size=exits, replace=False))
        if exits else np.empty(0, np.int64)
    )
    for i in exited:
        i = int(i)
        reg.exit_epoch[i] = epoch
        reg.withdrawable_epoch[i] = epoch + getattr(
            spec, "min_validator_withdrawability_delay", 256
        )
        reg.dirty.add(i)
    if exits:
        reg.rev += 1

    if pubkey_pool is None:
        pubkey_pool = make_pubkey_pool(16)
    new_start = n
    for j in range(int(deposits)):
        pk = bytes(pubkey_pool[(n + j) % len(pubkey_pool)])
        reg.append(_NewValidator(pk, int(epoch)))
        state.balances.append(MAX_EB)
        if hasattr(state, "inactivity_scores"):
            state.inactivity_scores.append(0)
            state.previous_epoch_participation.append(0)
            state.current_epoch_participation.append(0)
    return [int(i) for i in exited], range(new_start, len(reg))


def build_full_block(state, spec, participation=0.99, seed=1):
    """An unsigned full-load block for the state's current slot: one
    attestation per committee of the previous slot, full bits — the
    transition-blocks benchmark payload (no valid signatures; apply with
    NoVerification)."""
    preset = spec.preset
    T = state_types(preset)
    rng = np.random.default_rng(seed)
    slot = int(state.slot)
    att_slot = slot - 1
    cache = committees_for_epoch(state, att_slot // preset.slots_per_epoch, preset)
    target_epoch = att_slot // preset.slots_per_epoch
    target_root = phase0.get_block_root(state, target_epoch, preset)
    source = (
        state.current_justified_checkpoint
        if target_epoch == phase0.get_current_epoch(state, preset)
        else state.previous_justified_checkpoint
    )
    atts = []
    for index in range(cache.committees_per_slot):
        committee = cache.committee(att_slot, index)
        bits = (rng.random(len(committee)) < participation).astype(int).tolist()
        if not any(bits):
            bits[0] = 1
        atts.append(
            T.Attestation(
                aggregation_bits=bits,
                data=AttestationData(
                    slot=att_slot,
                    index=index,
                    beacon_block_root=phase0.get_block_root_at_slot(
                        state, att_slot, preset
                    ),
                    source=source,
                    target=Checkpoint(epoch=target_epoch, root=target_root),
                ),
                signature=b"\x00" * 96,
            )
        )
    block = T.BeaconBlock(
        slot=slot,
        proposer_index=phase0.get_beacon_proposer_index(state, preset),
        parent_root=phase0.hash_tree_root(state.latest_block_header),
        state_root=bytes(32),
        body=T.BeaconBlockBody(
            eth1_data=state.eth1_data,
            attestations=atts[: preset.max_attestations],
        ),
    )
    return T.SignedBeaconBlock(message=block, signature=b"\x00" * 96)


def fill_epoch_attestations(state, epoch, spec, participation, rng, target="previous"):
    """Append PendingAttestations covering every committee of `epoch`."""
    preset = spec.preset
    T = state_types(preset)
    cache = committees_for_epoch(state, epoch, preset)
    target_root = phase0.get_block_root(state, epoch, preset)
    source = (
        state.previous_justified_checkpoint
        if target == "previous"
        else state.current_justified_checkpoint
    )
    dest = (
        state.previous_epoch_attestations
        if target == "previous"
        else state.current_epoch_attestations
    )
    for slot in range(
        epoch * preset.slots_per_epoch, (epoch + 1) * preset.slots_per_epoch
    ):
        for index in range(cache.committees_per_slot):
            committee = cache.committee(slot, index)
            bits = (rng.random(len(committee)) < participation).astype(int).tolist()
            if not any(bits):
                bits[0] = 1
            att = T.PendingAttestation(
                aggregation_bits=bits,
                data=AttestationData(
                    slot=slot,
                    index=index,
                    beacon_block_root=phase0.get_block_root_at_slot(
                        state, slot, preset
                    ),
                    source=source,
                    target=Checkpoint(epoch=epoch, root=target_root),
                ),
                inclusion_delay=int(rng.integers(1, 4)),
                proposer_index=0,
            )
            dest.append(att)
