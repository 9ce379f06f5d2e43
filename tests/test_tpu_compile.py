"""Compile guards: main-path kernels, at real widths, for a TPU v5e.

Each test compiles one kernel for a described (not attached) v5e chip with
the TPU compiler that ships with jaxlib: a shape or op the chip's compiler
refuses fails here, on the CPU, instead of on the chip. Nothing runs, so
these say nothing about results or speed.

The topology is described inside a module fixture — never at import — so
that every test worker collects the same tests and only the worker that
runs this file loads the TPU compiler. The persistent compile cache is off
around these compiles: a cache entry written for a chip cannot be read
back without one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

LANES = 32  # the BLS device chunk (crypto.bls.DEFAULT_DEVICE_CHUNK)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def test_sha256_pairs_1m_leaves(one_chip):
    from lighthouse_tpu.ops.sha256 import sha256_pairs

    compiled = sha256_pairs.lower(_spec(one_chip, (1 << 19, 16), jnp.uint32)).compile()
    assert compiled.memory_analysis() is not None


def test_mont_mul(one_chip):
    from lighthouse_tpu.ops.bls381 import mont_mul

    limbs = _spec(one_chip, (LANES, 48))
    _compile(mont_mul, limbs, limbs)


@pytest.mark.parametrize("field", ["fq", "fq2"])
def test_pt_double(one_chip, field):
    from lighthouse_tpu.ops.bls381 import DevFq, DevFq2, pt_double

    F, shape = (DevFq, (LANES, 48)) if field == "fq" else (DevFq2, (LANES, 2, 48))
    c = _spec(one_chip, shape)
    _compile(lambda x, y, z: pt_double(F, (x, y, z)), c, c, c)


@pytest.mark.parametrize("keys", [16, 512])
def test_committee_aggregation_chunk(one_chip, keys):
    """The device verifier's aggregation program at both committee
    buckets the node sends: single keys (16) and ~486-key aggregates and
    the sync aggregate (512)."""
    from lighthouse_tpu.ops.bls381_verify import _aggregate

    pts = _spec(one_chip, (LANES, keys, 48))
    _aggregate.lower((pts, pts, pts), _spec(one_chip, ())).compile()


def test_message_groups_chunk(one_chip):
    from lighthouse_tpu.ops.bls381_verify import _message_groups

    pts = _spec(one_chip, (LANES, 48))
    idx = _spec(one_chip, (LANES, LANES))
    _message_groups.lower((pts, pts, pts), idx, _spec(one_chip, ())).compile()


def test_pairing_inputs_chunk(one_chip):
    """The verifier's shared inversion, affine conversions and Miller-lane
    padding. The chunk's ladders, hash to G2 and Miller loop are left out:
    each takes from 49 s to 2.5 minutes to compile on a CPU host."""
    from lighthouse_tpu.ops.bls381_verify import _pairing_inputs

    g1 = _spec(one_chip, (LANES, 48))
    sig = _spec(one_chip, (1, 2, 48))
    hm = _spec(one_chip, (LANES, 2, 48))
    compiled = _pairing_inputs.lower(
        (g1, g1, g1), (sig, sig, sig), (hm, hm, hm), _spec(one_chip, ())
    ).compile()
    assert compiled.out_info[0].shape == (LANES + 1, 48)


def test_fr_mul_blob_width(one_chip):
    from lighthouse_tpu.ops.fr import fr_mul

    limbs = _spec(one_chip, (4096, 32))
    _compile(fr_mul, limbs, limbs)


def test_epoch_sweep_1m_under_x64(one_chip):
    from lighthouse_tpu.ops.epoch_sweep import _epoch_sweep

    n = 1 << 20
    with jax.enable_x64(True):
        u64 = _spec(one_chip, (n,), jnp.uint64)
        args = (
            u64,
            _spec(one_chip, (n,), jnp.bool_),
            u64,
            u64,
            u64,
            _spec(one_chip, (n,), jnp.uint8),
            u64,
            u64,
            _spec(one_chip, (9,), jnp.uint64),
        )
        compiled = _epoch_sweep.lower(*args).compile()
    assert not jax.config.jax_enable_x64
    assert np.prod(compiled.out_info[0].shape) == n
