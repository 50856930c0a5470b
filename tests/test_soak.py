"""Multi-epoch soak rig unit tier (`testing/soak.py` at small N).

Covers the continuation seams the soak composes, at small N so the whole
file runs in seconds:

  * validator-churn re-key: `ValidatorPubkeyCache.rekey_for_churn` drops
    exited validators' `bls.PK_CACHE` Montgomery-limb entries (both the
    SoA ndarray fast path and the AoS fallback), idempotently;
  * aggregation-tier slot pruning on a CHURNED registry, with max-cover
    packing still working over the surviving entries;
  * backfill-vs-live store write interleaving: a checkpoint-synced
    second node backfills history on a thread (slowed by the
    `backfill.replay` failpoint) while live blocks feed it, and the
    payload-pruned replay of the raced window matches the serving
    chain's stored state root byte-for-byte;
  * the phased fault schedule: `parse_schedule` validation, window
    arm/disarm through `PhaseSchedule.enter`, and seeded determinism
    (the LTPU_FAILPOINTS_SEED contract — `PhaseSchedule(seed=...)` is
    the programmatic spelling of that env knob);
  * the soak block-production seams themselves: pinned anchor
    checkpoints let the head advance, `force_reorg` flips it, and block
    production keeps working across `apply_churn` (the frozen-header
    regression).

Run under LTPU_LOCK_WITNESS=1 the file doubles as a lock-order check on
the racer's store interleaving (conftest fails the session on cycles).
"""

from types import SimpleNamespace

import numpy as np
import pytest

from lighthouse_tpu.beacon.chain import BeaconChain
from lighthouse_tpu.beacon.validator_pubkey_cache import ValidatorPubkeyCache
from lighthouse_tpu.crypto.backend import SignatureVerifier
from lighthouse_tpu.crypto.tpu import bls as tb
from lighthouse_tpu.operation_pool import OperationPool
from lighthouse_tpu.ssz import hash_tree_root
from lighthouse_tpu.state_processing import phase0
from lighthouse_tpu.testing import scale, soak
from lighthouse_tpu.types import ChainSpec, MinimalPreset
from lighthouse_tpu.types.containers import AttestationData, Checkpoint
from lighthouse_tpu.types.state import state_types
from lighthouse_tpu.utils import failpoints

SPEC = ChainSpec(preset=MinimalPreset, altair_fork_epoch=0)
PRESET = SPEC.preset
SPE = PRESET.slots_per_epoch
T = state_types(PRESET)
FAR_FUTURE = 2**64 - 1


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.reset()
    yield
    failpoints.reset()


@pytest.fixture(scope="module")
def pk_pool():
    return scale.make_pubkey_pool(16)


@pytest.fixture(scope="module")
def sig_pool():
    return scale.make_signature_pool(32)


def _boot_chain(pk_pool, n=64, epoch=1, seed=0):
    state = scale.make_scaled_state(
        n, SPEC, epoch=epoch, seed=seed, pubkey_pool=pk_pool, fork="altair"
    )
    soak.pin_anchor_checkpoints(state, PRESET)
    return BeaconChain(state, SPEC, verifier=SignatureVerifier("fake"))


def _advance(chain, sig_pool, n_slots):
    """Produce + import + head-recompute `n_slots` consecutive blocks."""
    blocks = []
    start = int(chain.head_state.slot)
    for slot in range(start + 1, start + 1 + n_slots):
        chain.on_tick(slot)
        blk = soak.produce_block(chain, slot, sig_pool, si=slot)
        root = chain.process_block(blk)
        chain.recompute_head()
        assert chain.head_root == root, f"head did not advance to slot {slot}"
        blocks.append((slot, root, blk))
    return blocks


# --------------------------------------------------------------- churn re-key


def test_rekey_for_churn_drops_stale_limbs_soa(pk_pool):
    """SoA ndarray fast path: exited validators' PK_CACHE limb entries
    are invalidated exactly once (idempotent on the second call)."""
    vpc = ValidatorPubkeyCache(validate="host")
    vpc.import_new_pubkeys([bytes(pk_pool[i]) for i in range(8)])

    # seed the limb cache with every validator's point, as batch staging
    # would (the fake verify backend never touches PK_CACHE on its own)
    for i in range(8):
        tb.PK_CACHE.limbs(vpc.get(i))
    before = len(tb.PK_CACHE)

    exit_epoch = np.full(8, FAR_FUTURE, dtype=np.uint64)
    exit_epoch[1] = 2
    exit_epoch[3] = 5
    state = SimpleNamespace(validators=_SoARegistry(exit_epoch))

    n_exited, dropped = vpc.rekey_for_churn(state, current_epoch=5)
    assert (n_exited, dropped) == (2, 2)
    assert len(tb.PK_CACHE) == before - 2
    for i in (1, 3):
        assert tb.PK_CACHE.key_of(vpc.get(i)) not in tb.PK_CACHE._entries

    # idempotent: the retired set remembers both indices
    assert vpc.rekey_for_churn(state, current_epoch=6) == (0, 0)


class _SoARegistry:
    """Just the surface rekey_for_churn touches on a scaled registry."""

    def __init__(self, exit_epoch):
        self.exit_epoch = np.asarray(exit_epoch, dtype=np.uint64)

    def __len__(self):
        return len(self.exit_epoch)


def test_rekey_for_churn_aos_fallback(pk_pool):
    """Container registries without the ndarray sidecar take the
    per-validator fallback and agree with the fast path."""
    vpc = ValidatorPubkeyCache(validate="host")
    vpc.import_new_pubkeys([bytes(pk_pool[i]) for i in range(8, 12)])
    for i in range(4):
        tb.PK_CACHE.limbs(vpc.get(i))
    before = len(tb.PK_CACHE)

    reg = [SimpleNamespace(exit_epoch=FAR_FUTURE) for _ in range(4)]
    reg[2].exit_epoch = 1
    state = SimpleNamespace(validators=reg)

    assert vpc.rekey_for_churn(state, current_epoch=3) == (1, 1)
    assert len(tb.PK_CACHE) == before - 1
    assert vpc.rekey_for_churn(state, current_epoch=3) == (0, 0)


# ------------------------------------------------- aggregation-tier pruning


def _committee_att(state, slot, index, target_epoch, sig, root=b"\x22" * 32):
    committee = phase0.get_beacon_committee(state, slot, index, PRESET)
    return T.Attestation(
        aggregation_bits=[1] * len(committee),
        data=AttestationData(
            slot=slot, index=index, beacon_block_root=root,
            source=state.current_justified_checkpoint,
            target=Checkpoint(epoch=target_epoch, root=root),
        ),
        signature=sig,
    )


def test_aggregation_tier_prunes_churned_committees(pk_pool, sig_pool):
    """Slot pruning on a churned registry: stale-epoch entries drop,
    includable ones survive and still pack through max-cover."""
    state = scale.make_scaled_state(
        64, SPEC, epoch=2, seed=0, pubkey_pool=pk_pool, fork="altair"
    )
    # churn FIRST: the inserted attestations' committees come from the
    # churned shuffle, as they would after an epoch boundary in the soak
    scale.churn_registry(
        state, SPEC, epoch=3, exits=4, deposits=4, pubkey_pool=pk_pool
    )

    pool = OperationPool(SPEC)
    # one entry per epoch: stale (0), prev-epoch includable (1), current (2)
    pool.insert_attestation(
        _committee_att(state, 6, 0, 0, sig_pool[0], root=b"\x30" * 32))
    pool.insert_attestation(
        _committee_att(state, 13, 0, 1, sig_pool[1], root=b"\x31" * 32))
    pool.insert_attestation(
        _committee_att(state, 2 * SPE, 0, 2, sig_pool[2], root=b"\x32" * 32))
    assert pool.aggregation.stats()["data_roots"] == 3

    pool.prune(state, PRESET)  # current epoch 2: keeps target + 1 >= 2
    stats = pool.aggregation.stats()
    assert stats["data_roots"] == 2
    assert stats["pending_contributions"] == 2

    # the surviving prev-epoch aggregate is packable on the churned state
    packed = pool.get_attestations(state, PRESET)
    assert any(int(a.data.target.epoch) == 1 for a in packed)
    assert all(int(a.data.target.epoch) != 0 for a in packed)


# ------------------------------------------- backfill racing live import


def test_backfill_races_live_import_and_replays(pk_pool, sig_pool):
    """The raced store: history backfills on a worker thread (slowed by
    the `backfill.replay` failpoint so live feeds land mid-backfill)
    while live blocks import concurrently; the checkpoint store ends up
    with BOTH windows and the payload-pruned replay of the live window
    reproduces the serving chain's stored state root."""
    chain = _boot_chain(pk_pool)
    history = _advance(chain, sig_pool, SPE - 1)      # slots 9..15

    racer = soak.BackfillRacer(chain, chain.head_state.copy())
    failpoints.configure("backfill.replay", "delay(500)")
    racer.start()

    live = _advance(chain, sig_pool, 4)               # slots 16..19
    alive_during_feed = racer._thread.is_alive()
    for slot, _root, blk in live:
        racer.feed(blk, slot)

    failpoints.configure("backfill.replay", "off")
    res = racer.finish(timeout=60.0)

    # the delay held the backfill open across the live imports
    assert alive_during_feed
    assert res["live_fed"] == 4
    assert res["history_replayed"] == 4
    assert res["replay_root_matches_live"] is True
    assert res["backfilled"] >= len(history) - 1

    # both writers landed in the same store: backfilled history ...
    hist_root = history[0][1]
    assert racer.chain.store.get_block(hist_root) is not None
    # ... and the live-fed window
    for _slot, root, _blk in live:
        assert racer.chain.store.get_block(root) is not None


# ------------------------------------------------- phased fault schedule


def test_parse_schedule_windows_and_validation():
    phases = failpoints.parse_schedule(
        "1:wire.rpc=error(0.4),wire.serve=delay(10);2-3:store.put=delay(2)"
    )
    assert [(p["start"], p["end"]) for p in phases] == [(1, 1), (2, 3)]
    assert phases[0]["points"]["wire.rpc"] == "error(0.4)"

    sched = failpoints.PhaseSchedule(
        "1-3:wire.rpc=error(0.2);2:wire.rpc=error(0.9)"
    )
    # later phases override earlier ones on shared units
    assert sched.settings_at(1)["wire.rpc"] == "error(0.2)"
    assert sched.settings_at(2)["wire.rpc"] == "error(0.9)"
    assert sched.settings_at(4) == {}

    for bad in (
        "1",                          # no window separator
        "x:wire.rpc=error(0.5)",      # non-integer window
        "3-1:wire.rpc=error(0.5)",    # inverted range
        "1:wire.rpc",                 # entry without a spec
        "1:no.such.point=error(0.5)", # undeclared failpoint
        "1:wire.rpc=bogus(1)",        # unknown mode
        "1:,",                        # empty phase body
    ):
        with pytest.raises(ValueError):
            failpoints.parse_schedule(bad)


def test_phase_schedule_arms_and_disarms_per_window():
    sched = failpoints.PhaseSchedule(
        "1-2:wire.rpc=error(1.0);2:wire.serve=delay(1)"
    )
    sched.enter(0)
    failpoints.hit("wire.rpc")                       # not armed yet

    sched.enter(1)
    assert failpoints.is_armed("wire.rpc")
    assert not failpoints.is_armed("wire.serve")
    with pytest.raises(failpoints.FailpointError):
        failpoints.hit("wire.rpc")

    sched.enter(2)
    assert failpoints.is_armed("wire.rpc")
    assert failpoints.is_armed("wire.serve")

    sched.enter(3)                                   # storm over: recovery
    assert not failpoints.is_armed("wire.rpc")
    assert not failpoints.is_armed("wire.serve")
    failpoints.hit("wire.rpc")

    sched.enter(1)
    sched.exit()                                     # exit disarms its arms
    assert not failpoints.is_armed("wire.rpc")


def test_phase_schedule_seeded_determinism():
    """The LTPU_FAILPOINTS_SEED contract: a seeded schedule replays the
    same probabilistic storm, hit for hit."""

    def storm(seed):
        sched = failpoints.PhaseSchedule("0:wire.rpc=error(0.5)", seed=seed)
        sched.enter(0)
        fired = []
        for _ in range(100):
            try:
                failpoints.hit("wire.rpc")
                fired.append(False)
            except failpoints.FailpointError:
                fired.append(True)
        sched.exit()
        return fired

    a, b = storm(42), storm(42)
    assert a == b
    assert 20 < sum(a) < 80
    assert storm(43) != a                # the seed is actually load-bearing


# --------------------------------------------------- soak production seams


def test_pinned_anchor_head_advances_and_reorg_flips(pk_pool, sig_pool):
    chain = _boot_chain(pk_pool)
    anchor = chain.head_root
    blocks = _advance(chain, sig_pool, 3)
    assert chain.head_root != anchor
    assert int(chain.head_state.slot) == SPE + 3

    old, new = soak.force_reorg(chain, sig_pool, si=7)
    assert new != old
    assert chain.head_root == new
    # the fork orphaned the old head: same parent, one slot later
    fork = chain.store.get_block(new)
    assert bytes(fork.message.parent_root) == bytes(
        chain.store.get_block(old).message.parent_root
    )
    assert int(fork.message.slot) == blocks[-1][0] + 1


def test_block_production_survives_churn(pk_pool, sig_pool):
    """The frozen-header regression: churn mutates the stored head state
    out-of-band, and the next produced block must still link (the header
    state_root is frozen to the PRE-churn hash before the mutation)."""
    chain = _boot_chain(pk_pool)
    _advance(chain, sig_pool, 2)
    cache_before = len(chain.pubkey_cache)

    churn = soak.apply_churn(
        chain, epoch=2, exits=4, deposits=4, pubkey_pool=pk_pool, seed=1
    )
    assert len(churn["exited"]) == 4
    assert churn["deposited"] == 4
    assert len(chain.head_state.validators) == 64 + 4
    assert len(chain.pubkey_cache) >= cache_before

    # descendant import works across the out-of-band mutation
    _advance(chain, sig_pool, 2)
