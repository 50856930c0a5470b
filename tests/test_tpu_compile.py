"""Describe-only v5e compiles (on-chip-measurement guide §2): programs of
the verify path at real widths, compiled for a chip that is described and
not attached.  A compile that passes is not a chip run: nothing executes,
so these say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every xdist worker
imports this file.  The persistent compilation cache is off around the
compiles — an entry written for a described chip cannot be read back.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from lighthouse_tpu.crypto.tpu import bls as tb
from lighthouse_tpu.crypto.tpu import fp, sharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _limbs(n, sharding_):
    return jax.ShapeDtypeStruct((fp.NLIMB, n), jnp.int32, sharding=sharding_)


def _described(tree, sharding_of):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=sharding_of(a)),
        tree,
    )


def test_mont_mul_compiles_for_v5e(one_chip, no_persistent_cache):
    x = _limbs(65536, one_chip)
    compiled = jax.jit(fp.mont_mul).lower(x, x).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.slow
def test_batched_verify_32x1_compiles_for_v5e(one_chip, no_persistent_cache):
    """The gossip-bucket program of chip_smoke.py (~230 s here)."""
    args, _ = tb.example_chunk_args(32, 1)
    compiled = jax.jit(tb.batched_verify_kernel).lower(
        *_described(args, lambda a: one_chip)
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30


@pytest.mark.slow
def test_batched_verify_32x1_compiles_on_v5e_2x2_mesh(topo,
                                                      no_persistent_cache):
    """The dp=4 program of `chip_smoke.py --chips 4`: the set axis split
    over the four chips as MeshPlan places it, with the cross-device
    reduction the compiler inserts."""
    plan = sharding.MeshPlan(list(topo.devices), 4, 1, "described v5e:2x2")
    args, _ = tb.example_chunk_args(32, 1)
    compiled = jax.jit(tb.batched_verify_kernel).lower(
        *_described(args, lambda a: NamedSharding(
            plan.mesh, plan._verify_spec(a)))
    ).compile()
    hlo = compiled.as_text()
    assert "all-reduce" in hlo or "all-gather" in hlo
