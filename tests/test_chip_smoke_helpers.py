"""chip_smoke.py's input builders, on the CPU.

chip_smoke.py runs on the chip only, so its phases are not exercised
here; the two builders that make its inputs are: the 1M-validator epoch
state (here at 64 validators) and the cached aggregate-signature sets of
the bls phase (here 3 sets over 4 keys, cached under a temporary path).
"""

import pytest

import chip_smoke
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.metrics import REGISTRY
from lighthouse_tpu.state_processing.registry_columns import registry_columns_for


@pytest.fixture
def restore_backend():
    prior = bls.backend_name()
    yield
    bls.set_backend(prior)


def test_build_epoch_state_resident_at_boundary(restore_backend):
    state, spec, E = chip_smoke._build_epoch_state(64, resident=True)
    assert len(state.validators) == len(state.balances) == 64
    # one slot before an epoch boundary, past genesis
    assert (int(state.slot) + 1) % E.SLOTS_PER_EPOCH == 0
    assert int(state.slot) // E.SLOTS_PER_EPOCH >= 2
    # resident columns attached and already refreshed: a second refresh
    # rebuilds no column
    cols = state.__dict__.get("_registry_columns")
    assert cols is not None and registry_columns_for(state) is cols
    assert cols.validator_count == 64
    assert [int(b) for b in cols.balances] == [int(b) for b in state.balances]
    rebuilds = REGISTRY.counter("registry_columns_rebuilds_total")
    fields = ("validators", "balances", "inactivity_scores")
    before = {f: rebuilds.value(field=f) for f in fields}
    cols.refresh(state)
    assert {f: rebuilds.value(field=f) for f in fields} == before
    # balances inside the hysteresis band: no effective-balance churn
    step = E.EFFECTIVE_BALANCE_INCREMENT // E.HYSTERESIS_QUOTIENT
    down = step * E.HYSTERESIS_DOWNWARD_MULTIPLIER
    up = step * E.HYSTERESIS_UPWARD_MULTIPLIER
    for v, b in zip(state.validators, state.balances):
        eff = int(v.effective_balance)
        assert eff - down <= int(b) <= eff + up


def test_make_sets_verify_and_cache(tmp_path, monkeypatch, restore_backend):
    bls.set_backend("host")
    host = bls.get_backend()
    cache = tmp_path / "sets" / "sets_v1_3x4.pkl"
    sets = chip_smoke._make_sets(bls, 3, 4, cache=str(cache))
    assert cache.exists()
    assert len(sets) == 3 and all(len(s.pubkeys) == 4 for s in sets)
    assert host.verify_signature_sets(sets) is True
    swapped = [bls.SignatureSet(sets[1].signature, sets[0].pubkeys, sets[0].message)]
    assert host.verify_signature_sets(swapped + sets[1:]) is False

    # the second call reads the cache: it signs nothing
    def no_signing(*args, **kwargs):
        raise AssertionError("signed instead of reading the cache")

    monkeypatch.setattr(bls.SecretKey, "sign", no_signing)
    again = chip_smoke._make_sets(bls, 3, 4, cache=str(cache))
    assert [s.signature.to_bytes() for s in again] == [
        s.signature.to_bytes() for s in sets
    ]
    assert [s.message for s in again] == [s.message for s in sets]
