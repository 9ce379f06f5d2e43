"""Device epoch sweep parity (single_pass.rs:20 on device, SURVEY §7.3).

The fused jitted rewards/inactivity pass must be BIT-EXACT against the
numpy reference sweep. The sweep scopes x64 to its own call, so both
sides run in this process."""

from dataclasses import replace

import pytest

from lighthouse_tpu.beacon_chain.harness import BeaconChainHarness
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.state_processing import per_slot_processing
from lighthouse_tpu.state_processing.altair import (
    EpochArrays,
    _device_sweep_applicable,
    _device_sweep_enabled,
)
from lighthouse_tpu.types.chain_spec import minimal_spec
from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

# every test in this file is tier-2: device sweep — slow XLA-CPU compile.
# tests/conftest.py enforces this marker at collection time.
pytestmark = pytest.mark.slow


def _cross_epoch(st, spec):
    target = (st.slot // E.SLOTS_PER_EPOCH + 1) * E.SLOTS_PER_EPOCH
    while st.slot < target:
        per_slot_processing(st, spec, E)
    return st


def test_device_sweep_bit_exact_vs_numpy(monkeypatch):
    bls.set_backend("fake_crypto")
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    h = BeaconChainHarness(spec, E, validator_count=16)
    h.extend_chain(3 * E.SLOTS_PER_EPOCH)  # real participation + a miss mix
    base = h.chain.head_state

    monkeypatch.delenv("LIGHTHOUSE_TPU_DEVICE_EPOCH_SWEEP", raising=False)
    oracle = _cross_epoch(base.copy(), spec)  # numpy sweep

    monkeypatch.setenv("LIGHTHOUSE_TPU_DEVICE_EPOCH_SWEEP", "1")
    assert _device_sweep_enabled()
    st = base.copy()
    # the device path must ACTUALLY run — a vacuous numpy-vs-numpy pass
    # would hide real divergence
    assert _device_sweep_applicable(st, EpochArrays(st, E), spec, E)
    dev = _cross_epoch(st, spec)

    assert dev.hash_tree_root() == oracle.hash_tree_root()
    assert list(dev.balances) == list(oracle.balances)
    assert list(dev.inactivity_scores) == list(oracle.inactivity_scores)
