import json
import os
from collections import Counter

import numpy as np
import pytest

from harness import cells, traffic
from reference import bls12_381 as B
from reference import pool as message_pool
from reference.verdicts import Reference

from conftest import BENCH_DIR

BIG_SEED = 2**31 + 12345


class _Set:
    def __init__(self, signature, pubkeys, message):
        self.signature = signature
        self.pubkeys = pubkeys
        self.message = message


def _config(name, **preset):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        cfg = json.load(f)
    if preset:
        cfg["active_validators"] = preset.pop("validators")
        cfg["preset"] = dict(cfg["preset"], **preset)
    return cfg


def _traffic(name):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def _cell(config, mix):
    return cells.Cell("test", 1, config, mix, [], [], BENCH_DIR)


TINY = dict(validators=4096, SLOTS_PER_EPOCH=4, MAX_COMMITTEES_PER_SLOT=4,
            TARGET_COMMITTEE_SIZE=16)


@pytest.fixture(scope="module")
def pool():
    return message_pool.load()


def test_mainnet_slot_has_64_committees_of_488_and_489():
    dep = traffic.Deployment(_config("att_gossip_1m"), BIG_SEED)
    sizes = Counter(len(c) for c in dep.committees(0))
    assert sizes == {489: 18, 488: 46}
    members = np.concatenate(dep.committees(0) + dep.committees(1))
    assert len(set(members.tolist())) == 62500


def test_gossip_requests_are_valid_except_the_planted_ones(pool):
    tr = _traffic("saturate")
    tr["loop"]["pool_requests"] = 6
    tr["request"]["count"] = 16
    plan = traffic.build(_cell(_config("att_gossip_1m", **TINY), tr),
                         BIG_SEED, pool, 4, _Set)
    assert len(plan.warmup) == 1 and len(plan.window) == 6
    ref = Reference(pool)
    for req in plan.warmup + plan.window:
        got = ref.request(req)
        want = [i not in req.invalid for i in range(len(req.sets))]
        assert got == want
        for s, m in zip(req.sets, req.meta):
            assert sum(1 for _ in s.pubkeys) == 1
            assert s.pubkeys[0] == B.g1_mul(B.G1, m.secret)
            assert s.message == pool[m.msg][0]
    # every third window request: in its first device chunk of 4 sets,
    # then in its last
    assert [bool(r.invalid) for r in plan.window] == [
        False, False, True, False, False, True]
    assert 0 <= plan.window[2].invalid[0] < 4
    assert 12 <= plan.window[5].invalid[0] < 16


def test_block_requests_carry_proposal_randao_aggregates_sync(pool):
    cfg = _config("block_1m", SYNC_COMMITTEE_SIZE=32, **TINY)
    tr = _traffic("import")
    tr["request"]["ring_slots"] = 4
    plan = traffic.build(_cell(cfg, tr), BIG_SEED, pool, 4, _Set)
    block = plan.window[0]
    # 2 single-key sets, 2 slots x 4 aggregates, the sync aggregate
    assert [len(s.pubkeys) for s in block.sets[:2]] == [1, 1]
    assert len(block.sets) == 2 + 8 + 1
    sizes = [len(s.pubkeys) for s in block.sets[2:10]]
    assert all(240 <= n <= 256 for n in sizes)
    assert len(block.sets[-1].pubkeys) == round(32 * 0.95)
    ref = Reference(pool)
    for req in plan.window:
        assert ref.request(req) == [i not in req.invalid
                                    for i in range(len(req.sets))]
    # 11 sets in chunks of 4: the first chunk, then the last (8-10)
    assert 0 <= plan.window[2].invalid[0] < 4
    assert 8 <= plan.window[5].invalid[0] < 11
    m = block.meta[2]
    agg = None
    for pk in block.sets[2].pubkeys:
        agg = B.g1_add(agg, pk)
    assert agg == B.g1_mul(B.G1, m.secret)
    # consecutive blocks share one slot's committees
    nxt = plan.window[1]
    assert block.sets[2].pubkeys == nxt.sets[6].pubkeys


def test_the_invalid_chunks_do_not_depend_on_the_seed(pool):
    """The seed moves an invalid set inside its chunk, never to another
    chunk: the service stops at a request's first failing chunk, so the
    chunk is the work."""
    sizes = [256] * 32
    runs = [traffic.invalid_positions({"every": 3,
                                       "chunks": ["first", "last"]},
                                      s, sizes, 32)
            for s in (1, 7, BIG_SEED)]
    for r in runs:
        assert sorted(r) == list(range(2, 32, 3))
        assert [p[0] // 32 for _, p in sorted(r.items())] == [0, 7] * 5
    assert runs[0] != runs[1] != runs[2]


def test_the_same_seed_gives_the_same_inputs(pool):
    cfg = _config("att_gossip_1m", **TINY)
    tr = _traffic("saturate")
    tr["loop"]["pool_requests"] = 2
    a = traffic.build(_cell(cfg, tr), BIG_SEED, pool, 32, _Set)
    b = traffic.build(_cell(cfg, tr), BIG_SEED, pool, 32, _Set)
    sig = [[s.signature for s in r.sets] for r in a.window]
    assert sig == [[s.signature for s in r.sets] for r in b.window]


@pytest.mark.parametrize("where,key", [
    (None, "arrival"), ("loop", "arrival"), ("request", "rate_per_s"),
    ("warmup", "together"), ("invalid", "per")])
def test_a_mix_key_that_nothing_reads_is_refused(where, key):
    tr = _traffic("saturate")
    (tr if where is None else tr[where])[key] = "poisson"
    with pytest.raises(cells.CellError, match=key):
        _cell(_config("att_gossip_1m"), tr)


def test_units_and_loops_are_found_by_name():
    tr = _traffic("saturate")
    tr["loop"]["kind"] = "burst"
    with pytest.raises(cells.CellError, match="traffic/loops/burst.py"):
        _cell(_config("att_gossip_1m"), tr)
