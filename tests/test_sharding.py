"""Multi-device sharding tests for the batched BLS verify kernel.

Runs `batched_verify_kernel` under explicit `NamedSharding` layouts on the
8-virtual-device CPU mesh (conftest forces
``--xla_force_host_platform_device_count=8``) and asserts the verdict is
identical to the unsharded run.  This is the dp (set-axis) × mp (pubkey-axis)
layout that `__graft_entry__.dryrun_multichip` exercises and that SURVEY.md
§7 step 6 calls for: XLA inserts the cross-device collectives for the
pubkey-aggregation tree (mp axis psum-style reduction) and the blinded
signature accumulation / multi-pairing product (dp axis reduction).
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from lighthouse_tpu.crypto.constants import DST_POP
from lighthouse_tpu.crypto.ref import bls as RB
from lighthouse_tpu.crypto.tpu import bls as tb

pytestmark = pytest.mark.slow  # compiles the pairing graph


@pytest.fixture(scope="module")
def batch8x2():
    """8 sets x 2 pubkeys with one deterministic rand draw, plus the
    unsharded reference verdict."""
    rng = random.Random(11)
    sks = [rng.randrange(1, 2**250) for _ in range(2)]
    pks = [RB.sk_to_pk(sk) for sk in sks]
    sets = []
    for i in range(8):
        msg = i.to_bytes(32, "big")
        sig = RB.aggregate([RB.sign(sk, msg) for sk in sks])
        sets.append(RB.SignatureSet(sig, pks, msg))
    _, n_pad, pk, sig, u0, u1 = tb._prepare(sets, DST_POP)
    draws = iter([rng.randrange(1, 2**64) for _ in range(n_pad)])
    rands = tb._rand_scalars(n_pad, rng=lambda: next(draws))
    baseline = bool(tb._jit_batched(pk, sig, u0, u1, rands))
    assert baseline is True
    return pk, sig, u0, u1, rands, baseline


def _shard_and_run(mesh, pk_spec, set_spec, args):
    pk, sig, u0, u1, rands, baseline = args
    pk_s = NamedSharding(mesh, pk_spec)
    set_s = NamedSharding(mesh, set_spec)
    jitted = jax.jit(
        tb.batched_verify_kernel,
        in_shardings=(
            jax.tree_util.tree_map(lambda _: pk_s, pk),
            jax.tree_util.tree_map(lambda _: set_s, sig),
            jax.tree_util.tree_map(lambda _: set_s, u0),
            jax.tree_util.tree_map(lambda _: set_s, u1),
            set_s,
        ),
    )
    return bool(jitted(pk, sig, u0, u1, rands))


def test_mesh_available():
    assert len(jax.devices()) == 8, jax.devices()


def test_dp8_sharded_verdict_matches(batch8x2):
    """Pure data-parallel: the set axis split across all 8 devices."""
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
    # pk leaves: (limb, set, pubkey); sig/u leaves: (limb, set); rands (2, set)
    ok = _shard_and_run(mesh, PS(None, "dp", None), PS(None, "dp"), batch8x2)
    assert ok == batch8x2[-1]


def test_dp4_mp2_sharded_verdict_matches(batch8x2):
    """dp=4 × mp=2: set axis over dp, pubkey axis over mp — the layout
    dryrun_multichip uses (pubkey aggregation tree reduces across mp)."""
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("dp", "mp"))
    ok = _shard_and_run(mesh, PS(None, "dp", "mp"), PS(None, "dp"), batch8x2)
    assert ok == batch8x2[-1]


def test_dp2_mp4_invalid_batch_rejected(batch8x2):
    """A tampered batch must fail identically under sharding (flip one
    message's hash-to-field input)."""
    pk, sig, u0, u1, rands, _ = batch8x2
    c0, c1 = u0
    u0_bad = (c0.at[0, 3].set((c0[0, 3] + 1) & 255), c1)
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "mp"))
    ok = _shard_and_run(
        mesh, PS(None, "dp", None), PS(None, "dp"),
        (pk, sig, u0_bad, u1, rands, None),
    )
    assert ok is False


def _oracle_sets(n, poison_at=None):
    """n valid 2-pubkey sets; `poison_at` tampers that set's message so
    its signature no longer verifies."""
    rng = random.Random(23)
    sks = [rng.randrange(1, 2**250) for _ in range(2)]
    pks = [RB.sk_to_pk(sk) for sk in sks]
    sets = []
    for i in range(n):
        msg = i.to_bytes(32, "big")
        sig = RB.aggregate([RB.sign(sk, msg) for sk in sks])
        if i == poison_at:
            msg = b"\xff" * 32
        sets.append(RB.SignatureSet(sig, pks, msg))
    return sets


def _pipeline_verdicts(sets, seed):
    """Run the PRODUCTION plan_pipeline -> prepare_chunk ->
    execute_chunk path with a deterministic blinding-scalar stream."""
    draws = random.Random(seed)
    plan = tb.plan_pipeline(sets, DST_POP,
                            rng=lambda: draws.randrange(1, 2**64))
    assert plan is not None
    chunks, prepare, execute = plan
    return [bool(execute(prepare(c))) for c in chunks]


@pytest.mark.parametrize("mesh", ["dp=4", "dp=8"])
def test_production_pipeline_sharded_verdicts_match(monkeypatch, mesh):
    """The FULL production path (plan_pipeline -> prepare_chunk ->
    execute_chunk, mesh placement inside the device stage) yields
    identical chunk verdicts with and without sharding for the same
    seeded batch, at the four-chip host's dp=4 and at dp=8."""
    monkeypatch.setenv("LTPU_MAX_SETS_BUCKET", "8")
    monkeypatch.delenv("LTPU_MESH", raising=False)
    sets = _oracle_sets(16)
    base = _pipeline_verdicts(sets, seed=7)
    assert base == [True, True]
    monkeypatch.setenv("LTPU_MESH", mesh)
    from lighthouse_tpu.crypto.tpu import sharding

    before = sharding.launch_counts()["sharded"]
    sharded = _pipeline_verdicts(sets, seed=7)
    assert sharded == base
    # the sharded runs actually went through mesh placement
    assert sharding.launch_counts()["sharded"] == before + 2


@pytest.mark.parametrize("mesh", ["dp=4", "dp=8"])
def test_production_per_set_poison_attribution_sharded(monkeypatch, mesh):
    """Poisoned-set attribution on a sharded batch: the per-set verdict
    vector is identical to the unsharded one, False exactly at the
    poisoned index."""
    monkeypatch.setenv("LTPU_MAX_SETS_BUCKET", "8")
    poison = 12                         # second chunk under bucket 8
    sets = _oracle_sets(16, poison_at=poison)
    monkeypatch.delenv("LTPU_MESH", raising=False)
    base = tb.verify_signature_sets_per_set(sets)
    monkeypatch.setenv("LTPU_MESH", mesh)
    sharded = tb.verify_signature_sets_per_set(sets)
    want = [i != poison for i in range(len(sets))]
    assert base == want
    assert sharded == want


def test_per_set_kernel_dp8_sharded(batch8x2):
    """Per-set verdict kernel under dp sharding: verdicts match unsharded."""
    pk, sig, u0, u1, _, _ = batch8x2
    n = sig[0][0].shape[1]
    real = jnp.ones((n,), bool)
    ref_all, ref = tb._jit_per_set(pk, sig, u0, u1, real)
    ref = np.asarray(ref)
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
    pk_s = NamedSharding(mesh, PS(None, "dp", None))
    set_s = NamedSharding(mesh, PS(None, "dp"))
    jitted = jax.jit(
        tb.per_set_verify_kernel,
        in_shardings=(
            jax.tree_util.tree_map(lambda _: pk_s, pk),
            jax.tree_util.tree_map(lambda _: set_s, sig),
            jax.tree_util.tree_map(lambda _: set_s, u0),
            jax.tree_util.tree_map(lambda _: set_s, u1),
            NamedSharding(mesh, PS("dp")),
        ),
    )
    got_all, got = jitted(pk, sig, u0, u1, real)
    got = np.asarray(got)
    assert (got == ref).all()
    assert ref.all()
    assert bool(got_all) is True and bool(ref_all) is True


def _one_pubkey_sets(n, foreign_at=None):
    """n gossip-shaped sets, one pubkey each over its own message;
    `foreign_at` carries the next set's signature instead of its own (a
    point of G2, valid for another key and message)."""
    rng = random.Random(31)
    sets = []
    for i in range(n):
        sk = rng.randrange(1, 2**250)
        msg = (1000 + i).to_bytes(32, "big")
        sets.append(RB.SignatureSet(RB.sign(sk, msg), [RB.sk_to_pk(sk)], msg))
    if foreign_at is not None:
        s, other = sets[foreign_at], sets[(foreign_at + 1) % n]
        sets[foreign_at] = RB.SignatureSet(other.signature, s.pubkeys,
                                           s.message)
    return sets


class _NoHost:
    """host_verifier that refuses: every verdict comes from the device
    path."""

    backend = "host"

    def verify_signature_sets(self, sets, priority=None):
        raise RuntimeError("verification was routed to the host path")

    verify_signature_sets_per_set = verify_signature_sets


def test_service_dp4_one_pubkey_requests_match_the_reference(monkeypatch):
    """The four-chip gossip deployment's path at a small size: two
    requests of 16 one-pubkey sets through VerificationService over
    SignatureVerifier("tpu", fallback=False) under LTPU_MESH=dp=4, each
    chunk of 8 sets split 2 to a device.  One request is clean; the
    other carries a foreign signature in the last lane of shard 3 of its
    second chunk.  Batch verdicts and per-set vectors equal crypto/ref's."""
    from lighthouse_tpu.crypto import backend
    from lighthouse_tpu.crypto.tpu import sharding
    from lighthouse_tpu.verify_service import VerificationService

    monkeypatch.setenv("LTPU_MAX_SETS_BUCKET", "8")
    monkeypatch.setenv("LTPU_MESH", "dp=4")
    monkeypatch.delenv("LTPU_MESH_DISABLE", raising=False)
    # the CPU's virtual devices stand in for the host's four chips
    monkeypatch.setattr(backend, "_device_platform", lambda: "tpu")
    foreign = 8 + 7                     # chunk 1, lane 7: shard 3's last
    requests = [_one_pubkey_sets(16), _one_pubkey_sets(16, foreign)]
    svc = VerificationService(backend.SignatureVerifier("tpu",
                                                        fallback=False),
                              host_verifier=_NoHost())
    assert svc.mesh_devices == 4
    before = sharding.launch_counts()
    try:
        for sets in requests:
            want = [RB.verify_signature_sets([s]) for s in sets]
            assert svc.submit(sets).result(timeout=3600) is \
                RB.verify_signature_sets(sets)
            assert svc.submit(sets, want_per_set=True).result(
                timeout=3600) == want
    finally:
        svc.stop()
    assert want == [i != foreign for i in range(16)]
    after = sharding.launch_counts()
    assert after["sharded"] > before["sharded"]
    assert after["single"] == before["single"]
