#!/usr/bin/env python3
"""Chip smoke: batched BLS verification on the chip, through the node and
the verification service, at mainnet widths.

    python chip_smoke.py              one chip: phases P0-P4
    python chip_smoke.py --chips 4    four chips: P0, then P2 and P4 under
                                      the auto mesh (dp=4) and under the
                                      single-device plan (LTPU_MESH=1),
                                      compared

One chip:
  P0 device   JAX's default device, read in this process, is a TPU.
  P0 mont_mul the base-field multiply on the device is exact: its
              constant-operand dots against the host's convolution, and
              mont_mul over extreme and random limbs against host
              big-integer Montgomery products (phase_mont_mul).
  warm-up     the three verify programs: one 32x512 chunk and the 32x1
              per-set program in threads of their own, the 32x1 batched
              one in the node's prewarm (which also asks for the per-set
              one).  They lower one at a time and compile side by side.
  P1 node     a node built as `cli.py bn` builds it
              (ClientBuilder(...).crypto_backend("tpu")) imports one
              harness-signed block carrying attestations; the head moves
              and every signature set of the block runs on the device.
  P2 gossip   512 unaggregated-attestation sets (1 pubkey each, distinct
              messages; 16 chunks of 32x1) through a VerificationService
              over SignatureVerifier("tpu", fallback=False): True, and
              equal to the native engine.
  P3 block    128 aggregate sets of 488 pubkeys (a mainnet committee at 1M
              validators, padded to 512; 4 chunks of 32x512): True, = native.
  P4 negative the P2 batch with one signature swapped, and with one
              non-subgroup G2 signature: the batch is False and the per-set
              verdicts (32x1 per-set program) flag exactly that index.

After every phase the device and host fallback counters have not moved, no
breaker opened and no host verifier ran; P2-P4 compile nothing.  Every line
but the last is a phase record; the last line is the contract line
{"ok": true, "device": {"platform", "kind", "count"}}.  Any failed check
exits 1 before that line; a checkout without the package exits 2; the run
exits 3 when it outlives its deadline.

Signatures are real: pubkey i is (i+1)·G, so every secret is known.
"""

import argparse
import hashlib
import json
import os
import sys
import threading
import time

import numpy as np

# The program set: every chunk pads to the 32-set bucket and every pubkey
# axis to 1 or 512, so the verify programs are 32x1 batched, 32x1 per-set
# and 32x512 batched.  main() sets these before the planner reads them.
PROGRAM_ENV = {
    "LTPU_SHAPE_SETS_MENU": "32",
    "LTPU_SHAPE_PKS_MENU": "1,512",
    "LTPU_PREWARM_SHAPES": "32x1",
}
BUCKET = 32
GOSSIP_SETS = 512
BLOCK_SETS = 128
COMMITTEE = 488
COMMITTEE_PAD = 512
NODE_VALIDATORS = 64
POISON_SWAP = 137          # P4: this set carries its neighbour's signature
POISON_NONSUB = 300        # P4: this set carries a non-subgroup G2 point
SEED = 21
DEADLINE_S = 1140.0        # inside the 1200 s the driver allows
RESULT_TIMEOUT_S = 600.0


class SmokeError(Exception):
    pass


def check(cond, what):
    if not cond:
        raise SmokeError(what)


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


class NoHost:
    """host_verifier of the smoke's service: the device path is the only
    path, so a batch routed to the host fails the run."""

    backend = "host"

    def verify_signature_sets(self, sets, priority=None):
        raise SmokeError("verification was routed to the host path")

    verify_signature_sets_per_set = verify_signature_sets


class Compiles:
    """Every XLA backend compile in this process, named by the jitted
    function (JAX's own compile event: a scalar when it starts, a
    duration when it ends)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.events = []
        self.verify_started = 0
        self._lock = threading.Condition()
        monitoring.register_event_duration_secs_listener(self._on_event)
        monitoring.register_scalar_listener(self._on_start)

    def _on_event(self, event, secs, **kw):
        if event == self.EVENT:
            with self._lock:
                self.events.append((kw.get("fun_name", "?"), round(secs, 3)))

    def _on_start(self, event, value, **kw):
        if event == self.EVENT and "verify_kernel" in kw.get("fun_name", ""):
            with self._lock:
                self.verify_started += 1
                self._lock.notify_all()

    def start_in_order(self, jobs):
        """Run each (name, target, kwargs) job in its own thread, starting
        the next once this one's verify program has reached XLA's backend
        compile (which runs without the GIL) or the job has ended: the
        lowerings, which hold the GIL, then run one at a time and the
        first program reaches the compiler first, instead of all of them
        reaching it together at the end."""
        threads = []
        for name, target, kwargs in jobs:
            with self._lock:
                seen = self.verify_started
            t = threading.Thread(target=target, kwargs=kwargs,
                                 name=f"warm_{name}", daemon=True)
            t.start()
            with self._lock:
                while self.verify_started == seen and t.is_alive():
                    self._lock.wait(0.5)
            threads.append(t)
        return threads

    def mark(self):
        with self._lock:
            return len(self.events)

    def since(self, mark):
        with self._lock:
            return list(self.events[mark:])


class Guard:
    """The checks that follow every phase: no device or host fallback,
    no breaker trip, no host verifier ever built or used."""

    def __init__(self, compiles):
        from lighthouse_tpu.utils import metrics

        self.metrics = metrics
        self.compiles = compiles
        self.services = []
        self.base = self._counters()

    def _counters(self):
        return {
            "device_fallbacks": self.metrics.DEVICE_FALLBACKS.value,
            "host_fallbacks": self.metrics.HOST_BACKEND_FALLBACKS.value,
        }

    def after(self, phase, t0, compile_mark=None, **fields):
        counters = self._counters()
        trips = sum(s.breaker.trips for s in self.services)
        host_built = sum(
            s._host_verifier is not None and not isinstance(
                s._host_verifier, NoHost)
            for s in self.services
        )
        new = None if compile_mark is None else self.compiles.since(
            compile_mark)
        emit(phase, wall_s=round(time.monotonic() - t0, 3), **fields,
             **counters, breaker_trips=trips,
             compiles_in_phase=new if new is None else len(new))
        check(counters == self.base,
              f"{phase}: fallback counters moved {self.base} -> {counters}")
        check(trips == 0, f"{phase}: a service breaker opened")
        check(host_built == 0, f"{phase}: a host verifier was built")
        check(not new, f"{phase}: compiled after warm-up: {new}")


# ------------------------------------------------------ device arithmetic


def _limb_kinds(rng):
    """Lane contents for P0_mont_mul: (NLIMB,) int32 limbs at both ends
    of mont_mul's admitted input range (|limbs| < 2^22), random inside
    it, and canonical values."""
    from lighthouse_tpu.crypto.constants import P
    from lighthouse_tpu.crypto.tpu import fp

    top = (1 << 22) - 1
    n = fp.NLIMB
    return [
        np.full(n, top), np.full(n, -top),
        np.where(np.arange(n) % 2 == 0, top, -top),
        rng.integers(-top, top + 1, n), np.full(n, 255),
        fp.int_to_limbs(P - 1), fp.int_to_limbs(int(rng.integers(1 << 62))),
        np.zeros(n, np.int64),
    ]


def phase_mont_mul():
    """P0_mont_mul: the device's Fp multiply is exact.  Its constant
    products (`fp._mul_const_cols`, one f32 dot each) equal the host's
    integer convolution at both ends of their admitted limb range, and
    `fp.mont_mul` over extreme and random limbs equals the host's
    big-integer Montgomery product, within its limb bounds.  A dot that
    the device rounds shows here: the CPU's dots are exact at any
    precision setting."""
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.crypto.constants import P
    from lighthouse_tpu.crypto.tpu import fp

    t0 = time.monotonic()
    rng = np.random.default_rng(SEED)
    # constant products: lanes all -1024, all 1024, all -1, all 257
    # (the ends of the admitted range, and of mont_mul's use of it), random
    ends = [-1024, 1024, -1, 257]
    x = np.concatenate([np.full((fp.NLIMB, len(ends)), ends),
                        rng.integers(-1024, 1025, (fp.NLIMB, 60))], axis=1)
    x = x.astype(np.int32)
    for name, t, c in (("T_NP", fp.T_NP, fp.NPRIME_LIMBS),
                       ("T_P", fp.T_P, fp.P_LIMBS)):
        got = np.asarray(jax.jit(lambda v, t=t: fp._mul_const_cols(v, t))(
            jnp.asarray(x)))
        want = np.zeros(got.shape, np.int64)
        for j in range(x.shape[1]):       # 2N-1 columns, then a zero
            full = np.convolve(x[:, j].astype(np.int64), c.astype(np.int64))
            want[:, j] = np.append(full, 0)[:len(want)]
        bad = int((got != want).sum())
        check(bad == 0, f"P0_mont_mul: {bad} wrong columns of x·{name}")
    # mont_mul: every pair of lane kinds
    kinds = _limb_kinds(rng)
    a = np.stack([ka for ka in kinds for _ in kinds], axis=1)
    b = np.stack([kb for _ in kinds for kb in kinds], axis=1)
    a, b = a.astype(np.int32), b.astype(np.int32)
    out = np.asarray(jax.jit(fp.mont_mul)(jnp.asarray(a), jnp.asarray(b)))
    r_inv = pow(fp.R_INT, -1, P)
    want = [x * y * r_inv % P for x, y in zip(fp.array_to_ints(a),
                                              fp.array_to_ints(b))]
    got = [v % P for v in fp.array_to_ints(out)]
    wrong = sum(g != w for g, w in zip(got, want))
    emit("P0_mont_mul", wall_s=round(time.monotonic() - t0, 3),
         const_lanes=x.shape[1], mont_lanes=a.shape[1], wrong=wrong)
    check(wrong == 0, f"P0_mont_mul: {wrong} of {a.shape[1]} products wrong")
    check(out[:-1].min() >= -1 and out[:-1].max() <= 257
          and out[-1].min() >= -3 and out[-1].max() <= 260,
          "P0_mont_mul: output limbs out of bounds")


# ------------------------------------------------------------------ data


def key_points(n):
    """Affine (i+1)·G for i < n: pubkeys whose secrets are i+1."""
    from lighthouse_tpu.crypto.ref.curves import G1_GEN, g1_add

    out, p = [], G1_GEN
    for _ in range(n):
        out.append(p)
        p = g1_add(p, G1_GEN)
    return out


def message(kind, i):
    return hashlib.sha256(f"{SEED}:{kind}:{i}".encode()).digest()


def gossip_sets(keys):
    """P2: one pubkey per set, distinct messages."""
    from lighthouse_tpu.crypto.ref import bls as RB

    return [
        RB.SignatureSet(RB.sign(i + 1, message("gossip", i)), [keys[i]],
                        message("gossip", i))
        for i in range(GOSSIP_SETS)
    ]


def block_sets(keys):
    """P3: committee j signs its attestation root with COMMITTEE distinct
    keys; the aggregate signature is (sum of their secrets)·H(m)."""
    from lighthouse_tpu.crypto.ref import bls as RB

    out = []
    for j in range(BLOCK_SETS):
        lo = j * COMMITTEE
        secret = sum(range(lo + 1, lo + COMMITTEE + 1))
        msg = message("committee", j)
        out.append(RB.SignatureSet(RB.sign(secret, msg),
                                   keys[lo:lo + COMMITTEE], msg))
    return out


def poisoned(sets):
    """P4: (name, sets, bad index) for the swapped and the non-subgroup
    signature."""
    from lighthouse_tpu.crypto.ref import bls as RB
    from lighthouse_tpu.crypto.ref.curves import g2_in_subgroup
    from lighthouse_tpu.crypto.ref.hash_to_curve import (
        hash_to_field_fp2,
        map_to_curve_g2,
    )

    k = POISON_SWAP
    swapped = list(sets)
    swapped[k] = RB.SignatureSet(sets[k + 1].signature, sets[k].pubkeys,
                                 sets[k].message)
    raw = map_to_curve_g2(hash_to_field_fp2(message("nonsub", 0), 2)[0])
    check(not g2_in_subgroup(raw), "the non-subgroup point is in G2")
    j = POISON_NONSUB
    nonsub = list(sets)
    nonsub[j] = RB.SignatureSet(raw, sets[j].pubkeys, sets[j].message)
    return [("swapped", swapped, k), ("non_subgroup", nonsub, j)]


# ------------------------------------------------------------- machinery


def device_service():
    from lighthouse_tpu.crypto.backend import SignatureVerifier
    from lighthouse_tpu.verify_service import VerificationService

    return VerificationService(
        SignatureVerifier("tpu", fallback=False), host_verifier=NoHost()
    )


def native_reference():
    from lighthouse_tpu.crypto import native_bls

    check(native_bls.available(), "the native engine did not build")
    return native_bls


def device_sets():
    """Real sets the verify kernels have carried, per kernel (the kernel
    profile registry's pad accounting)."""
    from lighthouse_tpu.crypto.tpu import profile

    out = {}
    for row in profile.get_registry().rows():
        out[row["kernel"]] = out.get(row["kernel"], 0) + row["pad_sets"]
    return out


def program_report():
    """Per verify program: compiled or deserialized, and its seconds."""
    from lighthouse_tpu.crypto.tpu import compile_cache as cc

    stats = cc.get_cache().stats()
    return stats["dir"], {
        key: {"source": e["source"], "s": round(e["ms"] / 1e3, 3)}
        for key, e in sorted(stats["loaded"].items())
    }


def emit_kernel_profile():
    """Per (kernel, shape, topology): launches and mean wall per launch,
    block_until_ready included (the kernel profile registry)."""
    from lighthouse_tpu.crypto.tpu import profile

    emit("kernels", rows=[
        {k: row.get(k) for k in ("kernel", "shape", "topology", "launches",
                                 "mean_ms", "min_ms", "pad_sets",
                                 "pad_lanes")}
        for row in profile.get_registry().rows() if row["launches"]
    ])


def verdicts(svc, sets, per_set=False):
    return svc.submit(sets, want_per_set=per_set).result(RESULT_TIMEOUT_S)


# ---------------------------------------------------------------- phases


def start_node():
    """P1 set-up: the node `cli.py bn` would build, started (its prewarm
    compiles the 32x1 programs in the background)."""
    from lighthouse_tpu.beacon.node import ClientBuilder
    from lighthouse_tpu.testing.harness import Harness
    from lighthouse_tpu.types import ChainSpec, MinimalPreset

    spec = ChainSpec(preset=MinimalPreset)
    genesis_time = int(time.time()) - 3 * spec.seconds_per_slot
    harness = Harness(NODE_VALIDATORS, spec, genesis_time=genesis_time)
    node = (
        ClientBuilder(spec)
        .crypto_backend("tpu")
        .genesis_state(harness.state.copy())
        .memory_store()
        .http_api(0)
        .build()
    )
    node.start()
    return harness, node


def wait_warm(node, threads):
    for t in threads:
        t.join()
    svc = node.chain.verifier
    while node.prewarm_started is not None or not svc.device_ready:
        time.sleep(0.25)
    stats = node.prewarm_stats
    check(stats is not None, "node prewarm failed")
    check(stats["programs"] == 2, f"node prewarm menu: {stats['shapes']}")


def phase_node(harness, node, guard):
    from lighthouse_tpu.ssz import hash_tree_root
    from lighthouse_tpu.state_processing.phase0 import process_slots

    t0 = time.monotonic()
    mark = guard.compiles.mark()
    chain = node.chain
    spec = harness.spec
    genesis_root = chain.head_root
    state1 = process_slots(harness.state.copy(), 1, spec.preset, spec=spec)
    atts = harness.attest_slot(state1, 1, genesis_root)
    block = harness.produce_block(2, attestations=atts)
    before_dev = sum(device_sets().values())
    before_seen = guard.metrics.SIGNATURE_SETS_VERIFIED.value
    chain.process_block(block)
    root = hash_tree_root(block.message)
    on_device = sum(device_sets().values()) - before_dev
    seen = guard.metrics.SIGNATURE_SETS_VERIFIED.value - before_seen
    check(chain.head_root == root, "the head did not move to the block")
    # gossip proposal check + the block's bulk batch (proposal, randao,
    # one aggregate per attestation)
    check(on_device == seen and on_device >= 2 + len(atts),
          f"block sets: {seen} verified, {on_device} on the device")
    guard.after("P1_node", t0, mark, head_slot=int(block.message.slot),
                attestations=len(atts), sets_verified=seen,
                sets_on_device=on_device)


def phase_gossip(svc, sets, want, guard, mark, label="P2_gossip"):
    t0 = time.monotonic()
    got = verdicts(svc, sets)
    guard.after(label, t0, mark, sets=len(sets), verdict=got, native=want)
    check(got is True and want is True, f"{label}: {got} vs native {want}")
    return got


def phase_block(svc, sets, want, guard, mark):
    t0 = time.monotonic()
    got = verdicts(svc, sets)
    guard.after("P3_block", t0, mark, sets=len(sets),
                pubkeys_per_set=COMMITTEE, verdict=got, native=want)
    check(got is True and want is True, f"P3: {got} vs native {want}")


def phase_negatives(svc, cases, guard, mark, label="P4_negative",
                    batch_call=True):
    """Each case as a batch (verdict False) and per set (exactly the bad
    index flagged, equal to native).  Without `batch_call` the batch
    verdict is the service's own: a per-set request is attributed only
    after its batch failed (`verify_service_poisoned_batches_total`)."""
    from lighthouse_tpu.verify_service import metrics as VM

    out = {}
    for name, sets, bad, want in cases:
        t0 = time.monotonic()
        poisoned0 = VM.POISONED_BATCHES.value
        batch = verdicts(svc, sets) if batch_call else None
        per_set = list(verdicts(svc, sets, per_set=True))
        if not batch_call:
            batch = VM.POISONED_BATCHES.value - poisoned0 != 1
        flagged = [i for i, v in enumerate(per_set) if not v]
        guard.after(f"{label}.{name}", t0, mark, batch=batch,
                    flagged=flagged, native_flagged=[
                        i for i, v in enumerate(want) if not v])
        check(batch is False, f"{name}: batch verdict {batch}")
        check(flagged == [bad], f"{name}: flagged {flagged}, want [{bad}]")
        check(per_set == list(want), f"{name}: per-set differs from native")
        out[name] = (batch, per_set)
    return out


def native_cases(native, sets):
    """P4's cases with the native engine's per-set verdicts attached."""
    return [(name, bad_sets, bad, native.verify_signature_sets_per_set(
        bad_sets)) for name, bad_sets, bad in poisoned(sets)]


def run_one_chip(compiles):
    # import everything the warm-up thread touches first: two threads
    # importing one module graph at once can see a module half-built
    import lighthouse_tpu.beacon.node  # noqa: F401
    from lighthouse_tpu.crypto.tpu import bls  # noqa: F401
    from lighthouse_tpu.crypto.tpu import compile_cache as cc

    guard = Guard(compiles)
    t0 = time.monotonic()
    # the 32x512 chunk and the 32x1 per-set program compile while the node
    # builds; its prewarm compiles the 32x1 batched program and finds the
    # per-set one already loading (the cache builds each program once)
    per_set = [s for s in bls.kernel_specs(BUCKET, 1)
               if s[0] == "bls_per_set_verify"]
    warm = compiles.start_in_order([
        ("32x512", cc.prewarm,
         {"shapes": [(BUCKET, COMMITTEE_PAD)], "per_set": False}),
        ("32x1_per_set", cc.load_programs, {"specs": per_set}),
    ])
    harness, node = start_node()
    try:
        native = native_reference()
        guard.services.append(node.chain.verifier)
        keys = key_points(BLOCK_SETS * COMMITTEE)
        gossip = gossip_sets(keys)
        block = block_sets(keys)
        want_p2 = native.verify_signature_sets(gossip)
        want_p3 = native.verify_signature_sets(block)
        cases = native_cases(native, gossip)
        t_data = time.monotonic() - t0
        wait_warm(node, warm)
        cache_dir, programs = program_report()
        guard.after("warm_up", t0, data_s=round(t_data, 3),
                    aot_cache_dir=cache_dir, programs=programs,
                    xla_compiles=compiles.since(0))
        check(len(programs) == 3, f"verify programs: {sorted(programs)}")

        phase_node(harness, node, guard)

        svc = device_service()
        guard.services.append(svc)
        mark = compiles.mark()
        phase_gossip(svc, gossip, want_p2, guard, mark)
        phase_block(svc, block, want_p3, guard, mark)
        phase_negatives(svc, cases, guard, mark)
        svc.stop()
        emit_kernel_profile()
    finally:
        node.stop()


def run_four_chips(compiles):
    """P2 and P4 under the auto mesh (all devices on dp) and under the
    single-device plan, in this one process; verdicts and per-set vectors
    must be identical.  The four programs lower one after another
    (single-device ones first) and compile side by side; the
    single-device leg runs while the mesh programs are still in XLA's
    backend compile, after every lowering has ended.  The no-compile
    check is the cache's: each of the four programs is loaded exactly
    once."""
    import jax

    from lighthouse_tpu.crypto.tpu import bls as tb
    from lighthouse_tpu.crypto.tpu import compile_cache as cc
    from lighthouse_tpu.crypto.tpu import sharding

    guard = Guard(compiles)
    cache = cc.get_cache()
    t0 = time.monotonic()
    mesh_env = os.environ.get("LTPU_MESH")      # unset: the auto plan
    plan = sharding.get_mesh_plan()
    check(plan.sharded and plan.dp == len(jax.devices()) == 4,
          f"auto mesh: {plan.describe()}")
    # the single-device programs take unplaced arguments, exactly as a
    # LTPU_MESH=1 launch leaves them; the mesh ones are placed on dp
    label = f"{BUCKET}x1"
    single_b, single_p = tb.example_chunk_args(BUCKET, 1)
    warm = {
        "single": [
            ("bls_batched_verify", tb.batched_verify_kernel, single_b, label),
            ("bls_per_set_verify", tb.per_set_verify_kernel, single_p, label),
        ],
        "mesh": [(n, f, a, f"{lb}/dp{plan.dp}")
                 for n, f, a, lb in tb.kernel_specs(BUCKET, 1)],
    }
    jobs = [(f"{leg}_{spec[0]}", cc.load_programs, {"specs": [spec]})
            for leg, specs in warm.items() for spec in specs]
    started = compiles.start_in_order(jobs)
    threads = {"single": started[:2], "mesh": started[2:]}
    native = native_reference()
    gossip = gossip_sets(key_points(GOSSIP_SETS))
    want_p2 = native.verify_signature_sets(gossip)
    cases = native_cases(native, gossip)

    results = {}
    mark = compiles.mark()
    for name, value in (("single", "1"), ("mesh", mesh_env)):
        for t in threads[name]:
            t.join()
        guard.after(f"warm_up.{name}", t0, programs=program_report()[1])
        _set_mesh(value)
        plan = sharding.get_mesh_plan()
        prepared = tb.prepare_chunk(gossip[:BUCKET], min_sets=BUCKET)
        placed, shards = plan.place_verify_args(prepared.args, count=False)
        placement = sorted({
            (str(getattr(a.sharding, "spec", "single")),
             tuple(sorted(d.id for d in a.sharding.device_set)))
            for a in jax.tree_util.tree_leaves(placed)
        })
        emit(f"placement.{name}", plan=plan.describe()["reason"],
             shards=shards,
             shardings=[{"spec": s, "devices": list(d)} for s, d in placement])
        want = 4 if name == "mesh" else 1
        check(all(len(d) == want for _, d in placement),
              f"{name}: placement {placement}")
        svc = device_service()
        guard.services.append(svc)
        check(svc.mesh_devices == want,
              f"{name}: service mesh_devices {svc.mesh_devices}")
        p2 = phase_gossip(svc, gossip, want_p2, guard, None,
                          label=f"P2_gossip.{name}")
        p4 = phase_negatives(svc, cases, guard, None,
                             label=f"P4_negative.{name}", batch_call=False)
        svc.stop()
        results[name] = (p2, p4)
        emit(f"launches.{name}", **sharding.launch_counts())
    loads = cache.hits + cache.misses
    check(loads == 4, f"{loads} program loads, want the 4 warm-up ones")
    eager = [e for e in compiles.since(mark) if "verify_kernel" not in e[0]]
    check(not eager, f"compiled during the legs: {eager}")
    check(results["mesh"] == results["single"],
          "mesh and single-device verdicts differ")
    emit("compare", identical=True, program_loads=loads)
    emit_kernel_profile()


def _set_mesh(value):
    if value is None:
        os.environ.pop("LTPU_MESH", None)
    else:
        os.environ["LTPU_MESH"] = value


# ------------------------------------------------------------------ main


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    os.environ.update(PROGRAM_ENV)
    deadline = threading.Timer(DEADLINE_S, _out_of_time)
    deadline.daemon = True
    deadline.start()
    try:
        from lighthouse_tpu.utils import xla_cache
    except ImportError as e:
        print(f"chip_smoke: the lighthouse_tpu package is not here ({e})",
              file=sys.stderr)
        return 2
    import jax

    # P0: the device, as this process sees it
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's default device is {dev.platform!r}, "
              "not a TPU", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    emit("P0_device", **device)
    xla_cache.configure()
    from lighthouse_tpu.crypto.tpu import profile

    profile.set_registry(profile.ProfileRegistry())   # this run's launches
    compiles = Compiles()
    try:
        phase_mont_mul()
        if args.chips == 4:
            check(len(devices) == 4, f"--chips 4 sees {len(devices)} devices")
            run_four_chips(compiles)
        else:
            run_one_chip(compiles)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def _out_of_time():
    print(f"chip_smoke: FAILED: still running after {DEADLINE_S:.0f} s",
          file=sys.stderr, flush=True)
    os._exit(3)


if __name__ == "__main__":
    sys.exit(main())
