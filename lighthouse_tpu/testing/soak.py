"""Multi-epoch adversarial soak rig — epoch continuation over the scale rig.

`scale.py` builds one epoch of load against a frozen head; production is
hours of churn, reorgs, and sync racing live import.  This module adds
the continuation pieces a multi-epoch soak composes:

  * `produce_block` / `attest_branch` — real block production and
    full-committee branch votes on SCALED states, with every signature a
    valid compressed G2 pool point (the fake-backend contract of every
    scale rig: points must decompress, verdicts are free);
  * `force_reorg` — the late/orphaned competing-block recipe from
    tests/test_reorg.py (fork skips a slot to dodge the equivocation
    filter, committee votes flip the head through fork choice);
  * `apply_churn` — deposits/exits on the live chain's STORED head
    state (re-keying `ValidatorPubkeyCache`, invalidating `bls.PK_CACHE`
    limbs, re-shuffling later committees);
  * `BackfillRacer` — a checkpoint-synced second node whose history
    backfills over req/resp on a second thread while the driver keeps
    feeding it live head blocks: the store-write interleaving race, plus
    the payload-pruned `BlockReplayer` historical-state reconstruction
    check at the end;
  * `FleetHarness` — fleet mode (ISSUE 20): one logical verification
    plane sharded over a coordinator + K fault-isolated ShardWorkers,
    with kill / restart-from-persist / re-join helpers, so the
    simulator's chaos scenarios and the tests build the same fleet the
    same way.

The rig requires the chain's default `MemoryStore` (churn mutates the
stored head state in place — a serializing store would snapshot it).
"""

import threading

from ..ssz import hash_tree_root
from ..state_processing import phase0
from ..state_processing.phase0 import (
    BlockSignatureStrategy,
    per_block_processing,
    process_slots,
)
from ..types.containers import AttestationData, Checkpoint
from ..types.state import state_types
from . import scale

_INFINITY_G2 = b"\xc0" + b"\x00" * 95


def pin_anchor_checkpoints(state, preset):
    """Make a scaled state usable as a live-import anchor.

    `make_scaled_state` builds phase0-realistic LAGGING checkpoints
    (justified N-1, finalized 0) for a state at epoch N, but
    `ForkChoice.from_anchor` seeds its store with the anchor both
    justified and finalized at epoch N — weak-subjectivity semantics: an
    anchor IS a finalized checkpoint.  Descendant blocks inherit the
    state's checkpoint epochs as proto-array node epochs, and a node
    whose justified/finalized epoch sits below the store's is never
    viable for head: the chain imports blocks forever without the head
    ever advancing.  Pin the state's checkpoints to the anchor epoch
    before booting the chain.  Roots are left as-is — they are inert
    until justification genuinely advances past the anchor epoch, at
    which point real imported block roots take over."""
    epoch = int(state.slot) // preset.slots_per_epoch
    state.current_justified_checkpoint = Checkpoint(
        epoch=epoch, root=bytes(state.current_justified_checkpoint.root)
    )
    state.previous_justified_checkpoint = Checkpoint(
        epoch=epoch, root=bytes(state.previous_justified_checkpoint.root)
    )
    state.finalized_checkpoint = Checkpoint(
        epoch=epoch, root=bytes(state.finalized_checkpoint.root)
    )
    return state


def produce_block(chain, slot, sig_pool, *, parent_root=None,
                  attestations=(), pack_pool=None, si=0):
    """A signed block at `slot` on top of `parent_root` (default: the
    current head), with a correct post-state root and pool-point
    signatures throughout.  Mirrors Harness.produce_block without
    per-validator secret keys: the randao reveal, proposer signature,
    and attestation signatures are valid curve points the fake backend
    vacuously accepts, while slots/epoch processing and the state root
    are fully real.  The Altair sync aggregate is the empty-participation
    infinity special case (vacuously valid, produces no signature set)."""
    spec, preset = chain.spec, chain.preset
    T = state_types(preset)
    parent_root = bytes(parent_root or chain.head_root)
    base = chain.store.get_state(parent_root)
    assert base is not None, "parent state not in store"
    state = base.copy()
    if int(state.slot) < slot:
        state = process_slots(state, slot, preset, spec=spec)
    proposer = phase0.get_beacon_proposer_index(state, preset)

    # real production packs the operation pool's aggregates — the path
    # that lets the soak's gossip traffic become on-chain participation,
    # advance justification, and exercise finalized-state pruning
    if pack_pool is not None:
        attestations = pack_pool.get_attestations(state, preset)

    altair = hasattr(state, "previous_epoch_participation")
    body_kwargs = dict(
        randao_reveal=sig_pool[si % len(sig_pool)],
        eth1_data=state.eth1_data,
        attestations=list(attestations),
    )
    if altair:
        body_kwargs["sync_aggregate"] = T.SyncAggregate(
            sync_committee_bits=[0] * preset.sync_committee_size,
            sync_committee_signature=_INFINITY_G2,
        )
        body = T.BeaconBlockBodyAltair(**body_kwargs)
        block_cls, signed_cls = T.BeaconBlockAltair, T.SignedBeaconBlockAltair
    else:
        body = T.BeaconBlockBody(**body_kwargs)
        block_cls, signed_cls = T.BeaconBlock, T.SignedBeaconBlock
    block = block_cls(
        slot=slot,
        proposer_index=proposer,
        parent_root=hash_tree_root(state.latest_block_header),
        state_root=bytes(32),
        body=body,
    )
    tmp = state.copy()
    per_block_processing(
        tmp, signed_cls(message=block), spec,
        signature_strategy=BlockSignatureStrategy.NO_VERIFICATION,
    )
    block.state_root = hash_tree_root(tmp)
    return signed_cls(
        message=block, signature=sig_pool[(si + 1) % len(sig_pool)]
    )


def attest_branch(chain, slot, head_root, sig_pool, *, max_committees=None):
    """Full-participation attestations for every committee at `slot`
    voting `head_root` — the weight that drives a reorg through fork
    choice.  Committees/checkpoints come from the branch head's stored
    post-state (what an honest attester of that branch would see)."""
    preset = chain.preset
    T = state_types(preset)
    state = chain.store.get_state(bytes(head_root))
    assert state is not None, "branch head state not in store"
    epoch = int(slot) // preset.slots_per_epoch
    start_slot = epoch * preset.slots_per_epoch
    if start_slot >= int(state.slot) or start_slot >= slot:
        target_root = bytes(head_root)
    else:
        target_root = phase0.get_block_root_at_slot(state, start_slot, preset)
    out = []
    n_committees = phase0.get_committee_count_per_slot(state, epoch, preset)
    if max_committees is not None:
        n_committees = min(n_committees, max_committees)
    for index in range(n_committees):
        committee = phase0.get_beacon_committee(state, slot, index, preset)
        out.append(T.Attestation(
            aggregation_bits=[1] * len(committee),
            data=AttestationData(
                slot=slot, index=index,
                beacon_block_root=bytes(head_root),
                source=state.current_justified_checkpoint,
                target=Checkpoint(epoch=epoch, root=target_root),
            ),
            signature=sig_pool[index % len(sig_pool)],
        ))
    return out


def force_reorg(chain, sig_pool, *, pack_pool=None, si=0):
    """Orphan the current head: build a competing block off the head's
    PARENT at head_slot + 1 (the skipped slot means a different proposer
    — no equivocation), import it late, vote it with that slot's full
    committees, and tick forward so proposer boost expires.  Returns
    (old_head, new_head); a successful forced reorg has new == fork and
    new != old."""
    old_head = chain.head_root
    head_block = chain.store.get_block(old_head)
    assert head_block is not None
    parent_root = bytes(head_block.message.parent_root)
    fork_slot = int(head_block.message.slot) + 1
    chain.on_tick(fork_slot)
    fork_block = produce_block(
        chain, fork_slot, sig_pool, parent_root=parent_root,
        pack_pool=pack_pool, si=si,
    )
    fork_root = chain.process_block(fork_block)
    atts = attest_branch(chain, fork_slot, fork_root, sig_pool)
    chain.batch_verify_unaggregated_attestations(atts)
    chain.on_tick(fork_slot + 1)
    new_head = chain.recompute_head()
    return old_head, new_head


def apply_churn(chain, *, epoch, exits, deposits, pubkey_pool, seed=0):
    """Validator churn on the live chain between epochs: mutate the
    STORED head state (the next block's parent state must see it), then
    refresh the head snapshot, import the deposit pubkeys into the
    `ValidatorPubkeyCache`, and re-key the exited validators out of
    `bls.PK_CACHE`.  Returns {"exited", "deposited", "limbs_dropped"}."""
    stored = chain.store.get_state(chain.head_root)
    assert stored is not None
    # Freeze the parent linkage BEFORE mutating: the head post-state's
    # header still has a zeroed state_root that the next process_slot
    # fills by hashing the state — if that hash ran after churn, the
    # derived parent root would no longer be the committed block root
    # and every descendant would be an "unknown parent".  Filling it
    # with the pre-churn hash is exactly what process_slot would have
    # done had a block landed before the churn.
    hdr = stored.latest_block_header
    if bytes(hdr.state_root) == bytes(32):
        hdr.state_root = hash_tree_root(stored)
    exited, new_range = scale.churn_registry(
        stored, chain.spec, epoch=epoch, exits=exits, deposits=deposits,
        pubkey_pool=pubkey_pool, seed=seed,
    )
    # the head snapshot is a copy (recompute_head) — refresh it so every
    # head_state reader sees the churned registry
    chain._head = (chain.head_root, stored.copy())
    chain._import_new_pubkeys(stored)
    _, dropped = chain.pubkey_cache.rekey_for_churn(stored, epoch)
    return {
        "exited": exited,
        "deposited": len(new_range),
        "limbs_dropped": dropped,
    }


class FleetHarness:
    """One fleet-sharded logical node, in-process (ISSUE 20).

    K `ShardWorker`s (each its own chainless WireNode + local
    VerificationService on the fake/chosen backend) behind one
    `ShardCoordinator` (its own WireNode + WireTransport), with a
    consuming `VerificationService` whose remote tier IS the
    coordinator — the exact shape a sharded node builds via
    LTPU_SHARD_ROLE, minus the chain.  Worker ids double as wire peer
    ids and telemetry digest keys (the supervision join).

    Failure drills: `kill(name)` is the SIGKILL stand-in (wire sockets
    die mid-whatever, persist dict survives), `restart(name)` builds a
    fresh worker over the SAME persist dict and re-joins it through
    the coordinator's generation bump."""

    def __init__(self, k=2, backend="fake", heartbeat_budget_s=1.0,
                 rpc_timeout=2.0, breaker_threshold=2,
                 breaker_cooldown=0.5, audit_rate=0.0,
                 quarantine_cooldown=30.0, incidents=None, persist=None):
        from ..crypto.backend import SignatureVerifier
        from ..fleet.coordinator import ShardCoordinator
        from ..fleet.worker import ShardWorker
        from ..network.wire import WireNode
        from ..verify_service import VerificationService

        self.backend = backend
        self.persist = persist if persist is not None else {}
        self.workers = {}
        for i in range(k):
            name = f"shardw{i}"
            self.workers[name] = ShardWorker(
                name, backend=backend,
                persist=self.persist.setdefault(name, {}),
            )
        self.coordinator_wire = WireNode(
            None, accept_any_fork=True, peer_id="shard-coord"
        )
        self.coordinator = ShardCoordinator(
            self.coordinator_wire,
            [(name, w.address) for name, w in self.workers.items()],
            audit_verifier=SignatureVerifier(backend),
            audit_rate=audit_rate,
            incidents=incidents,
            heartbeat_budget_s=heartbeat_budget_s,
            rpc_timeout=rpc_timeout,
            breaker_threshold=breaker_threshold,
            breaker_cooldown=breaker_cooldown,
            quarantine_cooldown=quarantine_cooldown,
        )
        self.service = VerificationService(SignatureVerifier(backend))
        self.service.attach_remote(self.coordinator)
        self._keypairs = None

    # ---------------------------------------------------------- plumbing

    def probe_sets(self, n=8, tag=1):
        """Honestly signed sets with per-set DISTINCT messages, so one
        batch spreads over the bucket space (and thus the workers)
        instead of collapsing into a single committee bucket."""
        from ..crypto.ref import bls
        from ..state_processing.genesis import interop_keypairs

        if self._keypairs is None:
            self._keypairs = interop_keypairs(16)
        out = []
        for i in range(n):
            sk, pk = self._keypairs[i % len(self._keypairs)]
            msg = bytes([tag & 0xFF, i & 0xFF]) * 16
            out.append(bls.SignatureSet(bls.sign(sk, msg), [pk], msg))
        return out

    def submit(self, sets, priority="attestation"):
        """Async submit through the consuming service (the path import
        work rides); returns the VerifyFuture."""
        return self.service.submit(sets, priority=priority,
                                   want_per_set=True)

    def beat_all(self):
        """One heartbeat from every live worker into the coordinator's
        fleet table (the driver's stand-in for beat_forever)."""
        for w in self.workers.values():
            try:
                w.beat("shard-coord")
            except Exception:  # noqa: BLE001 — silence IS the signal
                pass

    # ---------------------------------------------------- failure drills

    def kill(self, name):
        """SIGKILL stand-in: the worker's wire sockets and service die
        mid-whatever; its persist dict survives for `restart`."""
        w = self.workers.pop(name)
        w.stop()
        return w

    def restart(self, name):
        """Crash recovery: a fresh worker over the SAME persist dict
        (resumes generation/ranges from the snapshot), re-joined
        through the coordinator's generation bump.  Returns
        (worker, generation)."""
        from ..fleet.worker import ShardWorker

        w = ShardWorker(
            name, backend=self.backend, persist=self.persist[name]
        )
        self.workers[name] = w
        gen = self.coordinator.rejoin(name, w.address)
        return w, gen

    def stop(self):
        self.coordinator.stop()
        self.service.stop()
        self.coordinator_wire.stop()
        for w in self.workers.values():
            w.stop()


class BackfillRacer:
    """Checkpoint-sync + historical backfill racing live import.

    Boots a second `BeaconChain` from the serving chain's current head
    state (the weak-subjectivity anchor of tests/test_checkpoint_sync),
    then `start()` runs `Router.backfill_from` on a worker thread —
    batched backwards history writes into the checkpoint node's store —
    while the driver keeps calling `feed(block, slot)` with each freshly
    imported live block on its own thread: the two sides interleave
    writes to the same store.  `finish()` joins the thread and replays
    the backfilled range through the payload-pruned `BlockReplayer`
    (optimistic mode) from `origin_state`, pinning the reconstruction to
    the anchor's state root."""

    def __init__(self, full_chain, origin_state, *, peer_id="soak-cp",
                 serve_peer="soak-full", bus=None, reqresp=None):
        from ..beacon.beacon_processor import BeaconProcessor
        from ..beacon.chain import BeaconChain
        from ..crypto.backend import SignatureVerifier
        from ..network.gossip import GossipBus, ReqResp
        from ..network.router import Router

        self.serve_peer = serve_peer
        self.full_chain = full_chain
        self.origin_state = origin_state
        self.anchor_root = full_chain.head_root
        bus = bus or GossipBus()
        reqresp = reqresp or ReqResp()
        self.full_router = Router(
            serve_peer, full_chain, BeaconProcessor(full_chain), bus, reqresp
        )
        self.chain = BeaconChain(
            full_chain.head_state.copy(), full_chain.spec,
            verifier=SignatureVerifier("fake"),
        )
        self.router = Router(
            peer_id, self.chain, BeaconProcessor(self.chain), bus, reqresp
        )
        # checkpoint sync ships the anchor BLOCK with the anchor state;
        # without it the first live feed races the backfill's by-root
        # fetch and gossip rejects it as an unknown parent
        anchor_block = full_chain.store.get_block(self.anchor_root)
        if anchor_block is not None:
            self.chain.store.put_block(self.anchor_root, anchor_block)
        self._thread = None
        self.backfilled = 0
        self.fed = 0
        self.last_fed_root = None
        self.error = None

    def _run(self):
        try:
            self.backfilled = self.router.backfill_from(self.serve_peer)
        except Exception as e:  # noqa: BLE001 — surfaced via finish()
            self.error = e

    def start(self):
        self._thread = threading.Thread(
            target=self._run, name="soak-backfill", daemon=True
        )
        self._thread.start()
        return self

    def feed(self, signed_block, slot):
        """Live import into the checkpoint node, racing the backfill."""
        self.chain.on_tick(slot)
        self.last_fed_root = self.chain.process_block(signed_block)
        self.fed += 1

    def finish(self, timeout=300.0):
        """Join the backfill thread and verify the race's outcome: the
        live-fed window is parent-linked in the checkpoint store down to
        the anchor, and the payload-pruned replay of that window from
        the origin (anchor) state reproduces the serving chain's stored
        post-state root byte-for-byte — churn is applied between soak
        epochs, never inside the raced window, so a pure-STF replay must
        agree exactly.  Returns a result dict (raises if the backfill
        thread errored)."""
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise TimeoutError("backfill thread still running")
        if self.error is not None:
            raise self.error

        # walk the live-fed window's ancestry out of the checkpoint
        # store (orphaned fork blocks are fed too but drop off the walk)
        top = self.last_fed_root or self.anchor_root
        blocks = []
        root = top
        while True:
            b = self.chain.store.get_block(root)
            if b is None or int(b.message.slot) <= int(self.origin_state.slot):
                break
            blocks.append(b)
            root = bytes(b.message.parent_root)
        blocks.reverse()

        from ..state_processing.block_replayer import BlockReplayer

        replayed = (
            BlockReplayer(self.origin_state.copy(), self.full_chain.spec)
            .with_payload_verification(False)
            .with_state_root_verification(True)
            .apply_blocks(blocks)
        )
        replay_root = hash_tree_root(replayed)
        expected = self.full_chain.store.get_state(top)
        return {
            "backfilled": self.backfilled,
            "live_fed": self.fed,
            "history_replayed": len(blocks),
            "replay_root_matches_live": bool(
                expected is not None
                and replay_root == hash_tree_root(expected)
            ),
        }
