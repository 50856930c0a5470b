"""Differential tests: native C++ BLS backend vs the Python oracle.

The native engine (csrc/blsnative.cpp) fills the blst slot
(/root/reference/crypto/bls/src/impls/blst.rs) — every layer here is
checked against the already-trusted oracle: full pairings, hash-to-G2,
batch verification semantics (valid/tampered/multi-pubkey/non-subgroup/
infinity classes), per-set fallback verdicts, and the frozen BLS
known-answer vectors.
"""

import json
import os
import random

import pytest

from lighthouse_tpu.crypto import native_bls
from lighthouse_tpu.crypto.constants import DST_POP
from lighthouse_tpu.crypto.ref import bls as RB
from lighthouse_tpu.crypto.ref import curves as C
from lighthouse_tpu.crypto.ref import fields as F
from lighthouse_tpu.crypto.ref import pairing as PR

pytestmark = pytest.mark.skipif(
    not native_bls.available(), reason="native BLS backend unavailable"
)

rng = random.Random(0x4A7)


def _roll():
    state = [17]

    def draw():
        state[0] = (state[0] * 6364136223846793005 + 1442695040888963407) % 2**64
        return state[0]

    return draw


def _mk_sets(spec):
    sets = []
    for n_pk, valid in spec:
        sks = [rng.randrange(1, 2**200) for _ in range(n_pk)]
        msg = bytes(rng.randrange(256) for _ in range(32))
        pks = [RB.sk_to_pk(sk) for sk in sks]
        sig = RB.aggregate([RB.sign(sk, msg) for sk in sks])
        if not valid:
            sig = C.g2_mul(sig, 7)
        sets.append(RB.SignatureSet(sig, pks, msg))
    return sets


# ------------------------------------------------------------ primitives


def test_pairing_matches_oracle():
    import ctypes

    lib = native_bls._get()
    lib.blsn_pairing.argtypes = [ctypes.c_char_p] * 3
    lib.blsn_pairing.restype = ctypes.c_int
    sk = rng.randrange(1, 2**200)
    pk = RB.sk_to_pk(sk)
    msg = b"\x42" * 32
    sig = RB.sign(sk, msg)
    g2b = native_bls._g2_bytes(sig)
    g1b = native_bls._be48(pk[0]) + native_bls._be48(pk[1])
    out = ctypes.create_string_buffer(576)
    assert lib.blsn_pairing(g1b, g2b, out) == 0
    cs = []
    for k in range(6):
        off = k * 96
        cs.append((int.from_bytes(out.raw[off:off + 48], "big"),
                   int.from_bytes(out.raw[off + 48:off + 96], "big")))
    got = F.f12_from_coeffs(cs)
    assert F.f12_eq(got, PR.pairing(pk, sig))


def test_hash_to_g2_matches_oracle():
    import ctypes

    from lighthouse_tpu.crypto.ref.hash_to_curve import hash_to_g2

    lib = native_bls._get()
    lib.blsn_hash_to_g2.argtypes = [
        ctypes.c_char_p, ctypes.c_uint32, ctypes.c_char_p, ctypes.c_uint32,
        ctypes.c_char_p,
    ]
    lib.blsn_hash_to_g2.restype = ctypes.c_int
    for msg in (b"", b"x", b"\x99" * 32, b"lighthouse_tpu" * 9):
        out = ctypes.create_string_buffer(192)
        assert lib.blsn_hash_to_g2(msg, len(msg), DST_POP, len(DST_POP), out) == 0
        got = (
            (int.from_bytes(out.raw[0:48], "big"),
             int.from_bytes(out.raw[48:96], "big")),
            (int.from_bytes(out.raw[96:144], "big"),
             int.from_bytes(out.raw[144:192], "big")),
        )
        assert got == hash_to_g2(msg), msg


# ---------------------------------------------------------- batch verify


def test_valid_batches_match_oracle():
    for spec in ([(1, True)], [(1, True), (3, True), (2, True)],
                 [(2, True)] * 5):
        sets = _mk_sets(spec)
        assert RB.verify_signature_sets(sets, rng=_roll()) is True
        assert native_bls.verify_signature_sets(sets, rng=_roll()) is True


def test_tampered_batches_match_oracle():
    sets = _mk_sets([(1, True), (2, False), (1, True)])
    assert RB.verify_signature_sets(sets, rng=_roll()) is False
    assert native_bls.verify_signature_sets(sets, rng=_roll()) is False


def test_per_set_verdicts():
    sets = _mk_sets([(1, True), (2, False), (3, True), (1, False)])
    assert native_bls.verify_signature_sets_per_set(sets) == [
        True, False, True, False,
    ]


def test_structural_rejects_match_oracle():
    good = _mk_sets([(2, True)])[0]
    # empty input
    assert native_bls.verify_signature_sets([]) is False
    # infinity signature
    s = RB.SignatureSet(None, good.pubkeys, good.message)
    assert native_bls.verify_signature_sets([s]) is False
    assert RB.verify_signature_sets([s]) is False
    # no pubkeys
    s = RB.SignatureSet(good.signature, [], good.message)
    assert native_bls.verify_signature_sets([s]) is False
    # infinity pubkey
    s = RB.SignatureSet(good.signature, [good.pubkeys[0], None], good.message)
    assert native_bls.verify_signature_sets([s]) is False
    assert RB.verify_signature_sets([s]) is False
    per = native_bls.verify_signature_sets_per_set([good, s])
    assert per == [True, False]


def test_non_subgroup_signature_rejected():
    from lighthouse_tpu.crypto.ref.hash_to_curve import (
        hash_to_field_fp2,
        map_to_curve_g2,
    )

    raw = map_to_curve_g2(hash_to_field_fp2(b"non-subgroup", 2)[0])
    assert not C.g2_in_subgroup(raw)
    good = _mk_sets([(1, True)])[0]
    bad = RB.SignatureSet(raw, good.pubkeys, good.message)
    assert native_bls.verify_signature_sets([bad]) is False
    assert RB.verify_signature_sets([bad]) is False


def test_wrong_message_rejected():
    sets = _mk_sets([(1, True), (1, True)])
    sets[1] = RB.SignatureSet(sets[1].signature, sets[1].pubkeys, b"\xaa" * 32)
    assert native_bls.verify_signature_sets(sets) is False


def test_swapped_signatures_rejected():
    a, b = _mk_sets([(1, True), (1, True)])
    swapped = [RB.SignatureSet(b.signature, a.pubkeys, a.message),
               RB.SignatureSet(a.signature, b.pubkeys, b.message)]
    assert native_bls.verify_signature_sets(swapped) is False


# --------------------------------------------------------- frozen vectors


VEC = os.path.join(os.path.dirname(__file__), "vectors", "bls_batch_verify.json")


def _load_sets(case):
    sets = []
    for s in case["sets"]:
        sig = (
            None
            if s["signature"] == C.g2_compress(None).hex()
            else C.g2_decompress(bytes.fromhex(s["signature"]), subgroup_check=False)
        )
        pks = [
            None
            if pk == C.g1_compress(None).hex()
            else C.g1_decompress(bytes.fromhex(pk), subgroup_check=False)
            for pk in s["pubkeys"]
        ]
        sets.append(RB.SignatureSet(sig, pks, bytes.fromhex(s["message"])))
    return sets


def _case_ids():
    with open(VEC) as f:
        return [c["name"] for c in json.load(f)["cases"]]


@pytest.fixture(scope="module")
def vectors():
    with open(VEC) as f:
        return json.load(f)


@pytest.mark.parametrize("name", _case_ids())
def test_native_matches_frozen(vectors, name):
    case = next(c for c in vectors["cases"] if c["name"] == name)
    sets = _load_sets(case)
    if not sets:
        assert native_bls.verify_signature_sets(sets) is False
        return
    got = native_bls.verify_signature_sets(sets, rng=_roll())
    assert got is case["expect"], f"{name}: native={got}"
    per = native_bls.verify_signature_sets_per_set(sets)
    assert per == case["per_set"], f"{name}: native per-set={per}"


def test_threaded_batch_matches_single(monkeypatch):
    """The rayon-role thread fan-out (LTPU_NATIVE_THREADS) must agree
    with the single-thread path on valid, poisoned, and per-set batches
    across odd chunkings."""
    import os

    sets = _mk_sets([(1, True)] * 11 + [(2, True)])
    bad = list(sets)
    bad[5] = RB.SignatureSet(C.g2_mul(bad[5].signature, 3),
                             bad[5].pubkeys, bad[5].message)
    monkeypatch.setenv("LTPU_NATIVE_THREADS", "5")
    try:
        assert native_bls.verify_signature_sets(sets, rng=_roll()) is True
        assert native_bls.verify_signature_sets(bad, rng=_roll()) is False
        per = native_bls.verify_signature_sets_per_set(bad)
        assert per == [i != 5 for i in range(12)]
    finally:
        monkeypatch.delenv("LTPU_NATIVE_THREADS", raising=False)


# ------------------------------------------------------- backend fallback


def test_backend_seam_prefers_native_on_device_failure(monkeypatch):
    from lighthouse_tpu.crypto.backend import SignatureVerifier

    sets = _mk_sets([(1, True)])
    v = SignatureVerifier("native")
    assert v.verify_signature_sets(sets) is True
    bad = _mk_sets([(1, False)])
    assert v.verify_signature_sets(bad) is False
    assert v.verify_signature_sets_per_set(sets + bad) == [True, False]


def test_chain_imports_signed_blocks_through_native_backend():
    """End-to-end: a BeaconChain with the NATIVE verifier imports fully
    signed blocks (proposal + randao + attestation signature sets all
    through csrc/blsnative.cpp) and rejects a tampered one — the
    production CPU path the auto backend selects on accelerator-less
    hosts."""
    from lighthouse_tpu.beacon.chain import BeaconChain, BlockError
    from lighthouse_tpu.crypto.backend import SignatureVerifier
    from lighthouse_tpu.testing.harness import Harness
    from lighthouse_tpu.types import ChainSpec, MinimalPreset

    spec = ChainSpec(preset=MinimalPreset)
    h = Harness(8, spec)
    chain = BeaconChain(h.state.copy(), spec,
                        verifier=SignatureVerifier("native", fallback=False))
    pending = []
    for slot in range(1, 4):
        block = h.produce_block(slot, attestations=pending)
        h.process_block(block, strategy="no_verification")
        chain.on_tick(slot)
        root = chain.process_block(block)
        from lighthouse_tpu.ssz import hash_tree_root

        assert bytes(root) == bytes(hash_tree_root(block.message))
        pending = h.attest_slot(h.state, slot, root)
    assert int(chain.head_state.slot) == 3
    # a forged proposer signature must be rejected by the same path
    bad = h.produce_block(4, attestations=pending)
    bad.signature = bytes([bad.signature[0] ^ 1]) + bytes(bad.signature[1:])
    chain.on_tick(4)
    with pytest.raises(BlockError):
        chain.process_block(bad)


class _FakeDevice:
    def __init__(self, platform):
        self.platform = platform


def test_auto_backend_resolution_logic(monkeypatch):
    """"auto" picks the device only when JAX's default device, read in
    this process, is a TPU; a cpu (or any other) platform resolves to
    the native engine (whose CPU throughput is ~1000x the device
    kernel's CPU emulation).  `jax.devices` is stubbed — real device
    state must not decide a unit test."""
    import jax

    import lighthouse_tpu.crypto.backend as B

    def run_with_platform(platform):
        monkeypatch.setattr(jax, "devices", lambda *a: [_FakeDevice(platform)])
        B._AUTO_RESOLVED = None
        try:
            return B.SignatureVerifier("auto").backend
        finally:
            B._AUTO_RESOLVED = None

    old = B._AUTO_RESOLVED
    try:
        assert run_with_platform("tpu") == "tpu"
        assert run_with_platform("cpu") == "native"
        assert run_with_platform("gpu") == "native"
        # and the resolved native verifier actually verifies
        monkeypatch.setattr(jax, "devices", lambda *a: [_FakeDevice("cpu")])
        B._AUTO_RESOLVED = None
        v = B.SignatureVerifier("auto")
        assert v.verify_signature_sets(_mk_sets([(1, True)])) is True
    finally:
        B._AUTO_RESOLVED = old


@pytest.mark.parametrize("entry", ["verifier", "node_builder"])
def test_explicit_tpu_backend_without_tpu_raises(entry):
    """`SignatureVerifier("tpu")`, and so a node built with
    `--crypto-backend tpu`, on a host whose JAX sees no TPU fails at
    construction — it never resolves to the native engine."""
    import lighthouse_tpu.crypto.backend as B

    with pytest.raises(RuntimeError, match="needs a TPU"):
        if entry == "verifier":
            B.SignatureVerifier("tpu")
        else:
            from lighthouse_tpu.beacon.node import ClientBuilder
            from lighthouse_tpu.state_processing.genesis import (
                interop_genesis_state,
                interop_keypairs,
            )
            from lighthouse_tpu.types import ChainSpec, MinimalPreset

            spec = ChainSpec(preset=MinimalPreset)
            state = interop_genesis_state(interop_keypairs(4), 0, spec)
            ClientBuilder(spec).crypto_backend("tpu").genesis_state(
                state).build()
