"""Multi-device sharding tests on the 8-device virtual CPU mesh.

conftest.py provisions 8 virtual CPU devices via
--xla_force_host_platform_device_count (SURVEY.md §2.9: ICI batch sharding
is the TPU-native analog of the reference's rayon batch parallelism); on
chips, `python chip_smoke.py --four-chips` runs the same kernels."""

import hashlib

import jax
import numpy as np
import pytest

from lighthouse_tpu.ops.merkle_sharded import build_sharded_merkle
from lighthouse_tpu.ops.sha256 import bytes_to_words, words_to_bytes

# every test in this file is tier-2: 8-device mesh kernels: slow XLA-CPU compiles.
# tests/conftest.py enforces this marker at collection time.
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def eight_devices():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return jax.devices()[:8]


def _host_merkle_root(data: bytes) -> bytes:
    nodes = [data[i : i + 32] for i in range(0, len(data), 32)]
    while len(nodes) > 1:
        nodes = [
            hashlib.sha256(nodes[i] + nodes[i + 1]).digest()
            for i in range(0, len(nodes), 2)
        ]
    return nodes[0]


def test_sharded_merkle_root_matches_host(eight_devices):
    n_devices, per_device = 8, 16
    mesh, fn, sharding = build_sharded_merkle(n_devices, per_device)
    rng = np.random.default_rng(3)
    data = rng.integers(
        0, 256, size=n_devices * per_device * 32, dtype=np.uint8
    ).tobytes()
    leaves = jax.device_put(bytes_to_words(data), sharding)
    root = words_to_bytes(fn(leaves))
    assert root == _host_merkle_root(data)


def test_sharded_merkle_input_actually_sharded(eight_devices):
    n_devices, per_device = 8, 8
    mesh, fn, sharding = build_sharded_merkle(n_devices, per_device)
    rng = np.random.default_rng(4)
    data = rng.integers(
        0, 256, size=n_devices * per_device * 32, dtype=np.uint8
    ).tobytes()
    leaves = jax.device_put(bytes_to_words(data), sharding)
    # the leaf buffer must be split over all 8 devices, not replicated
    assert len(leaves.sharding.device_set) == n_devices


def test_dryrun_multichip_entrypoint(eight_devices):
    """The multi-chip dry-run entry is green end-to-end, in process."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from __graft_entry__ import dryrun_multichip

    dryrun_multichip(8)


@pytest.mark.slow
def test_sharded_rlc_bls_matches_host(eight_devices):
    """The sharded BLS batch step (per-device RLC scalar-mul shards + ICI
    point-sum reduction) matches the host bigint oracle — the multichip
    half of batch signature verification (SURVEY §2.9)."""
    from lighthouse_tpu.ops.bls381_sharded import build_sharded_bls, dryrun_sharded_bls

    mesh, fn, sharding = build_sharded_bls(8)
    dryrun_sharded_bls(mesh)  # asserts vs host internally
