"""Spans at the verify path's boundaries (utils/tracing.py live spans):
`prep` per chunk staged, `prep_wait` wherever the dispatcher waits for
one, `place` per chunk's mesh placement, `launch` per kernel call
through CachedKernel, each on the batch's `verify_batch` trace with the
enclosing span of its thread as `parent`, and each under a profiler
annotation of the same name."""

import threading
import time
from types import SimpleNamespace

import jax.numpy as jnp
import pytest

from lighthouse_tpu.crypto.tpu import bls
from lighthouse_tpu.crypto.tpu import compile_cache as cc
from lighthouse_tpu.crypto.tpu import profile
from lighthouse_tpu.utils import tracing
from lighthouse_tpu.verify_service import VerificationService


@pytest.fixture
def annotations(monkeypatch):
    """(name, kwargs, thread name) of every profiler annotation opened,
    in order (stands in for jax.profiler.TraceAnnotation)."""
    seen = []

    class Recorder:
        def __init__(self, name, **kwargs):
            self.entry = (name, kwargs, threading.current_thread().name)

        def __enter__(self):
            seen.append(self.entry)
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracing, "_ANNOTATION", Recorder)
    return seen


@pytest.fixture
def kernel(tmp_path):
    """A tiny CachedKernel on a fresh profile registry (plain jit path)."""
    old_cache, old_reg = cc.get_cache(), profile.get_registry()
    cc.set_cache(cc.CompileCache(cache_dir=str(tmp_path / "aot"),
                                 enabled=False))
    reg = profile.ProfileRegistry(str(tmp_path / "kernel_profile.json"))
    profile.set_registry(reg)
    k = cc.CachedKernel("span_probe", lambda x: x * 2 + 1)
    k.registry = reg
    try:
        yield k
    finally:
        cc.set_cache(old_cache)
        profile.set_registry(old_reg)


class KernelStub:
    """Two-stage backend double: `prepare` sleeps, `execute` launches a
    CachedKernel; a chunk holding a poisoned set verifies False."""

    backend = "stub"

    def __init__(self, kernel, chunk=4, prep_s=0.02):
        self.kernel = kernel
        self.chunk = chunk
        self.prep_s = prep_s
        self.on_device_fallback = None

    def plan_pipeline(self, sets):
        sets = list(sets)
        if len(sets) <= self.chunk:
            return None
        chunks = [sets[i:i + self.chunk]
                  for i in range(0, len(sets), self.chunk)]

        def prepare(chunk):
            time.sleep(self.prep_s)
            return chunk

        def execute(prepared, overlap_ratio=None):
            self.kernel(jnp.ones((len(prepared), 2), jnp.int32))
            return not any(s.poison for s in prepared)

        return chunks, prepare, execute

    def verify_signature_sets(self, sets, priority=None):
        return not any(s.poison for s in sets)

    def verify_signature_sets_per_set(self, sets, priority=None):
        return [not s.poison for s in sets]


def _sets(n, poison_at=None):
    return [SimpleNamespace(poison=(i == poison_at)) for i in range(n)]


def _batch_trace(n_sets):
    got = [t for t in tracing.recent()
           if t["kind"] == "verify_batch"
           and t["attrs"].get("backend") == "stub"
           and t["attrs"].get("sets") == n_sets]
    assert len(got) == 1, got
    return got[0]


def _named(trace, name):
    return [s for s in trace["spans"] if s["name"] == name]


def _run_batch(kernel, sets):
    tracing.clear()
    svc = VerificationService(KernelStub(kernel), target_batch=len(sets))
    try:
        verdict = svc.submit(sets).result(timeout=30.0)
    finally:
        svc.stop()
    return verdict, _batch_trace(len(sets))


def test_pipelined_batch_spans_prep_wait_and_launch(kernel, annotations):
    ok, tr = _run_batch(kernel, _sets(12))
    assert ok is True
    preps = _named(tr, "prep")
    assert [s["attrs"]["chunk"] for s in preps] == [0, 1, 2]
    assert all(s["attrs"] == {"parent": None, "chunk": s["attrs"]["chunk"],
                              "sets": 4} for s in preps)
    assert all(s["duration_ms"] >= 15.0 for s in preps)    # the sleep
    waits = _named(tr, "prep_wait")
    assert [s["attrs"]["chunk"] for s in waits] == [0, 1, 2]
    assert all(s["attrs"]["parent"] == "kernel"
               and s["attrs"]["drain"] is False for s in waits)
    launches = _named(tr, "launch")
    assert len(launches) == 3
    assert all(s["attrs"] == {"parent": "kernel", "kernel": "span_probe",
                              "shape": "2", "source": "jit"}
               for s in launches)
    # launches nest inside the kernel span
    (k,) = _named(tr, "kernel")
    for s in launches:
        assert k["start_ms"] <= s["start_ms"]
        assert s["start_ms"] + s["duration_ms"] <= (
            k["start_ms"] + k["duration_ms"] + 1e-3)
    # prep ran on the prep thread, the waits and launches on the
    # dispatcher; each annotation carries the batch trace's id
    by_name = {}
    for name, kw, thread in annotations:
        if kw.get("trace_id") == tr["trace_id"]:
            by_name.setdefault(name, set()).add(thread)
    assert by_name["prep"] == {"verify_service_prep"}
    assert by_name["prep_wait"] == by_name["launch"] == {"verify_service"}
    assert by_name["kernel"] == {"verify_service"}


def test_first_chunk_failure_drains_preps_after_one_launch(kernel):
    ok, tr = _run_batch(kernel, _sets(12, poison_at=1))
    assert ok is False
    assert [s["attrs"]["chunk"] for s in _named(tr, "prep")] == [0, 1, 2]
    assert len(_named(tr, "launch")) == 1
    waits = _named(tr, "prep_wait")
    assert [(s["attrs"]["chunk"], s["attrs"]["drain"]) for s in waits] == [
        (0, False), (1, True), (2, True)]


def test_cached_kernel_launch_span_needs_a_current_trace(kernel,
                                                         annotations):
    x = jnp.ones((3, 2), jnp.int32)
    tr = tracing.start_trace("unit")
    with tracing.use(tr):
        kernel(x)
    (span,) = [s for s in tr.snapshot_spans() if s[0] == "launch"]
    name, start, end, attrs = span
    assert attrs == {"parent": None, "kernel": "span_probe", "shape": "2",
                     "source": "jit"}
    # one measurement, two sinks: the registry holds the same duration
    (row,) = kernel.registry.rows()
    assert row["launches"] == 1
    assert row["total_ms"] == pytest.approx((end - start) * 1e3, abs=0.01)
    n_spans = len(tr.snapshot_spans())
    kernel(x)                                   # no current trace
    assert len(tr.snapshot_spans()) == n_spans
    assert kernel.registry.rows()[0]["launches"] == 2
    # the annotation opens either way; only a traced one has an id
    launch_kw = [kw for n, kw, _ in annotations if n == "launch"]
    assert launch_kw == [{"trace_id": tr.trace_id}, {}]


def test_span_names_and_parents_reach_the_annotation(annotations):
    tr = tracing.start_trace("unit")
    with tr.span("outer", k=1):
        with tracing.span("inner", tr):
            pass
        with tracing.region("marked", tr):
            with tracing.span("leaf", tr):
                pass
    with tracing.span("untraced"):              # no current trace
        pass
    spans = {s[0]: s[3] for s in tr.snapshot_spans()}
    assert spans == {"inner": {"parent": "outer"},
                     "leaf": {"parent": "marked"},
                     "outer": {"parent": None, "k": 1}}
    assert [(n, kw) for n, kw, _ in annotations] == [
        ("outer", {"trace_id": tr.trace_id}),
        ("inner", {"trace_id": tr.trace_id}),
        ("marked", {"trace_id": tr.trace_id}),
        ("leaf", {"trace_id": tr.trace_id}),
        ("untraced", {}),
    ]


def test_to_dict_carries_the_monotonic_start():
    tr = tracing.start_trace("unit")
    d = tr.to_dict()
    assert d["mono_start"] == pytest.approx(tr.t_start, abs=1e-6)
    assert d["mono_start"] <= time.monotonic()


def test_serial_path_prep_nests_in_prep_wait(monkeypatch, annotations):
    """The serial chunk loop stages each chunk inline: its `prep` sits
    inside the `prep_wait` of the calling thread, and a failing chunk
    ends the staging."""
    staged = []

    def prepare_chunk(sets, *a, **kw):
        staged.append(len(sets))
        return sets

    monkeypatch.setattr(bls, "prepare_chunk", prepare_chunk)
    monkeypatch.setattr(bls, "execute_chunk",
                        lambda sets: not any(s.poison for s in sets))
    b = bls._bucket_sets()
    sets = [SimpleNamespace(signature=1, pubkeys=[1], poison=False)
            for _ in range(3 * b)]
    sets[b].poison = True                       # the second chunk fails
    tr = tracing.start_trace("unit")
    with tracing.use(tr):
        assert bls.verify_signature_sets(sets) is False
    assert staged == [b, b]
    spans = [(n, a) for n, _, _, a in tr.snapshot_spans()]
    assert spans == [
        ("prep", {"parent": "prep_wait", "chunk": 0, "sets": b}),
        ("prep_wait", {"parent": None, "chunk": 0, "drain": False}),
        ("prep", {"parent": "prep_wait", "chunk": 1, "sets": b}),
        ("prep_wait", {"parent": None, "chunk": 1, "drain": False}),
    ]
    threads = {t for n, _, t in annotations}
    assert threads == {threading.current_thread().name}


def _placeable(n, nlimb=2):
    """A chunk's argument pytree at the ranks `prepare_chunk` stages
    (pubkeys (limb, set, pk), signature and hash-to-field leaves
    (limb, set), blinding scalars (2, set)), at a tiny limb count."""
    def limbs():
        return jnp.zeros((nlimb, n), jnp.int32)

    pk = (jnp.zeros((nlimb, n, 1), jnp.int32),) * 3
    sig = ((limbs(), limbs()),) * 3
    return pk, sig, (limbs(), limbs()), (limbs(), limbs())


class PlacingStub(KernelStub):
    """Backend double over the real device stage: `execute` is
    `bls.execute_chunk`, so each chunk goes through the mesh placement
    and a CachedKernel launch (bls._jit_batched, patched to a probe)."""

    def plan_pipeline(self, sets):
        sets = list(sets)
        chunks = [sets[i:i + self.chunk]
                  for i in range(0, len(sets), self.chunk)]
        index = {id(c): i for i, c in enumerate(chunks)}

        def prepare(chunk):
            c = bls.PreparedChunk()
            c.chunk, c.invalid = index[id(chunk)], False
            c.n_sets = c.n_pad = len(chunk)
            c.args = _placeable(len(chunk)) + (
                jnp.ones((2, len(chunk)), jnp.uint32),)
            c.t_prep0 = c.t_prep1 = time.monotonic()
            return c

        return chunks, prepare, bls.execute_chunk


@pytest.fixture
def mesh_env(monkeypatch, request):
    """LTPU_MESH=dp=4 on the 8 virtual CPU devices, or the mesh
    disabled; the shards a placed chunk should report."""
    monkeypatch.delenv("LTPU_MESH_DISABLE", raising=False)
    monkeypatch.delenv("LTPU_MESH", raising=False)
    if request.param == "dp=4":
        monkeypatch.setenv("LTPU_MESH", "dp=4")
        return 4
    monkeypatch.setenv("LTPU_MESH_DISABLE", "1")
    return 1


@pytest.mark.parametrize("mesh_env", ["dp=4", "disabled"], indirect=True)
def test_place_span_between_prep_wait_and_launch(kernel, monkeypatch,
                                                 annotations, mesh_env):
    probe = cc.CachedKernel(
        "place_probe", lambda pk, sig, u0, u1, rands: jnp.all(rands > 0))
    probe.registry = kernel.registry
    monkeypatch.setattr(bls, "_jit_batched", probe)
    tracing.clear()
    sets = _sets(24)
    svc = VerificationService(PlacingStub(kernel, chunk=8),
                              target_batch=len(sets))
    try:
        assert svc.submit(sets).result(timeout=60.0) is True
    finally:
        svc.stop()
    tr = _batch_trace(len(sets))
    places = _named(tr, "place")
    assert [s["attrs"]["chunk"] for s in places] == [0, 1, 2]
    for s in places:
        a = s["attrs"]
        assert a["parent"] == "kernel" and a["per_set"] is False
        assert a["shards"] == mesh_env
        assert a["bytes"] > 0
    waits = [s for s in _named(tr, "prep_wait") if not s["attrs"]["drain"]]
    launches = _named(tr, "launch")
    assert len(waits) == len(launches) == 3
    for w, p, la in zip(waits, places, launches):
        assert w["start_ms"] + w["duration_ms"] <= p["start_ms"] + 1e-3
        assert p["start_ms"] + p["duration_ms"] <= la["start_ms"] + 1e-3
    threads = {t for n, kw, t in annotations
               if n == "place" and kw.get("trace_id") == tr["trace_id"]}
    assert threads == {"verify_service"}


@pytest.mark.parametrize("mesh_env", ["dp=4", "disabled"], indirect=True)
def test_per_set_chunk_opens_a_place_span(kernel, monkeypatch, mesh_env):
    probe = cc.CachedKernel(
        "place_probe_per_set",
        lambda pk, sig, u0, u1, real: (jnp.all(real), real))
    probe.registry = kernel.registry
    monkeypatch.setattr(bls, "_jit_per_set", probe)
    monkeypatch.setattr(bls, "_prepare", lambda sets, dst, *a: (
        sets, 8) + _placeable(8))
    tr = tracing.start_trace("unit")
    with tracing.use(tr):
        got = bls._per_set_chunk([object()] * 6, None, chunk=2)
    assert got == [True] * 6
    spans = [(n, a) for n, _, _, a in tr.snapshot_spans()]
    assert [n for n, _ in spans] == ["prep", "prep_wait", "place", "launch",
                                     "device_chunk"]
    place = spans[2][1]
    assert place["chunk"] == 2 and place["per_set"] is True
    assert place["shards"] == mesh_env and place["bytes"] > 0
