"""Runtime sanitizer (LIGHTHOUSE_TPU_SANITIZE=1): write-guarded views,
wide-dtype overflow checks, stale-read audits, and the slow-marked
block-import + epoch-transition soak (differential vs. the oracles).

The headline regression test: an escaped writeable `load_array` view —
the exact bug class that silently corrupts state roots — must raise a
counted `SanitizerError` at the write site under sanitize mode. The
all-modes freezes (committee slices, EpochArrays / RegistryColumns
column views) are asserted without the env flag: those invariants hold
unconditionally."""

import random

import numpy as np
import pytest

from lighthouse_tpu.analysis import sanitizer
from lighthouse_tpu.analysis.sanitizer import SanitizerError
from lighthouse_tpu.beacon_chain.chain import _make_persistent
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.metrics import REGISTRY
from lighthouse_tpu.ssz.persistent import PersistentByteList, PersistentList
from lighthouse_tpu.state_processing.per_epoch import process_epoch
from lighthouse_tpu.state_processing.registry_columns import (
    registry_columns_for,
)
from lighthouse_tpu.types.chain_spec import ForkName
from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E
from lighthouse_tpu.utils import safe_arith
from lighthouse_tpu.utils.safe_arith import ArithError

import test_registry_columns as trc


def _viol(rule: str) -> float:
    return REGISTRY.counter("sanitizer_violations_total").value(rule=rule)


@pytest.fixture
def sanitize(monkeypatch):
    monkeypatch.setenv(sanitizer.ENV_VAR, "1")


# ---------------------------------------------------------------------------
# cow-write: guarded load_array views
# ---------------------------------------------------------------------------


def test_escaped_load_array_view_is_caught(sanitize):
    """THE regression test: a consumer that keeps a load_array view and
    writes it (instead of committing via store_array) is caught at the
    write site with a counted violation."""
    lst = PersistentList(range(100))
    arr = lst.load_array()
    assert not arr.flags.writeable
    before = _viol("cow-write")
    with pytest.raises(SanitizerError, match="cow-write"):
        arr[3] = 42
    assert _viol("cow-write") == before + 1
    # the escape hatch is also guarded
    with pytest.raises(SanitizerError, match="cow-write"):
        arr.setflags(write=True)
    assert _viol("cow-write") == before + 2
    # the list itself never saw the write
    assert lst[3] == 3
    # byte lists share the contract
    bl = PersistentByteList(bytes(64))
    barr = bl.load_array()
    with pytest.raises(SanitizerError, match="cow-write"):
        barr[0] = 1


def test_sanctioned_store_array_still_works(sanitize):
    lst = PersistentList(range(100))
    staged = lst.load_array().copy()  # copies of guarded views are writable
    staged[7] = 1234
    assert lst.store_array(staged) == 1
    assert lst[7] == 1234
    _, dirty = lst.drain_dirty()
    assert dirty == {7}


def test_load_array_stays_writable_off_mode(monkeypatch):
    """No behavior change with the sanitizer off (production mode)."""
    monkeypatch.delenv(sanitizer.ENV_VAR, raising=False)
    lst = PersistentList(range(10))
    arr = lst.load_array()
    assert arr.flags.writeable


# ---------------------------------------------------------------------------
# all-modes freezes (no env flag)
# ---------------------------------------------------------------------------


def test_committee_slices_frozen_in_all_modes():
    from lighthouse_tpu.state_processing.accessors import committee_cache_at

    state, _spec = trc._base_state(ForkName.ALTAIR, 320, 7)
    cc = committee_cache_at(state, 3, E)
    committee = cc.committee_array(state.slot, 0)
    assert not committee.flags.writeable
    with pytest.raises(ValueError):
        committee[0] = 1
    # list materialization (the SSZ/dict-key surface) is unaffected
    assert committee.tolist() == list(committee)


def test_epoch_arrays_views_frozen_in_all_modes():
    from lighthouse_tpu.state_processing.altair import EpochArrays

    # a persistent state with no columns given: the legacy snapshot path
    state, _spec = trc._base_state(ForkName.ALTAIR, 200, 9)
    _make_persistent(state)
    arrays = EpochArrays(state, E)
    assert arrays.columns is None
    for name in ("effective_balance", "exit_epoch", "slashed"):
        view = getattr(arrays, name)
        assert not view.flags.writeable, name
    with pytest.raises(ValueError):
        arrays.effective_balance[0] = 1
    # the sanctioned writer updates the base the views read
    arrays.write_snapshot_rows("effective_balance", [0], [123])
    assert int(arrays.effective_balance[0]) == 123


def test_writable_window_refreezes_even_on_exception():
    """The guarded re-enable: writes succeed inside the window, the
    buffer is frozen again on exit — including an exceptional one."""
    arr = np.arange(8, dtype=np.uint64)
    arr.setflags(write=False)
    with sanitizer.writable_window(arr) as buf:
        buf[0] = 99
    assert not arr.flags.writeable
    assert arr[0] == 99
    with pytest.raises(RuntimeError):
        with sanitizer.writable_window(arr):
            raise RuntimeError("mid-window failure")
    assert not arr.flags.writeable


def test_registry_column_views_frozen_in_all_modes():
    state, _spec = trc._base_state(ForkName.ALTAIR, 200, 13)
    _make_persistent(state)
    cols = registry_columns_for(state)
    cols.refresh(state)
    assert not cols.effective_balance.flags.writeable
    assert not cols.balances.flags.writeable
    # ValueError from a plain frozen view; SanitizerError (counted) when
    # the suite itself runs under LIGHTHOUSE_TPU_SANITIZE=1
    with pytest.raises((ValueError, SanitizerError)):
        cols.balances[0] = 1
    # the sanctioned writer path commits to the list AND the column
    new = cols.balances.copy()
    new[0] += 5
    assert cols.write_balances(state, new) == 1
    assert state.balances[0] == int(new[0])


# ---------------------------------------------------------------------------
# u64-wrap: wide-dtype checks on the vectorized helpers
# ---------------------------------------------------------------------------


def test_vectorized_wrap_checks_fire_under_sanitize(sanitize):
    big = np.array([2**63, 5], dtype=np.uint64)
    before = _viol("u64-wrap")
    with pytest.raises(SanitizerError, match="u64-wrap"):
        safe_arith.add_u64(big, big)
    with pytest.raises(SanitizerError, match="u64-wrap"):
        safe_arith.mul_u64(big, np.uint64(3))
    with pytest.raises(SanitizerError, match="u64-wrap"):
        safe_arith.sub_u64(np.array([1], dtype=np.uint64), np.uint64(2))
    with pytest.raises(SanitizerError, match="u64-wrap"):
        safe_arith.div_u64(big, np.array([1, 0], dtype=np.uint64))
    assert _viol("u64-wrap") == before + 4
    # exact lanes pass
    assert safe_arith.add_u64(big, np.uint64(1))[1] == 6
    assert (
        safe_arith.sub_u64_saturating(
            np.array([1], dtype=np.uint64), np.uint64(2)
        )[0]
        == 0
    )


def test_vectorized_helpers_are_plain_ops_off_mode(monkeypatch):
    monkeypatch.delenv(sanitizer.ENV_VAR, raising=False)
    big = np.array([2**63], dtype=np.uint64)
    assert safe_arith.add_u64(big, big)[0] == 0  # wraps silently, as numpy


def test_scalar_checked_helpers_always_raise():
    assert safe_arith.safe_add(1, 2) == 3
    assert safe_arith.saturating_sub(3, 5) == 0
    assert safe_arith.saturating_add(2**64 - 1, 9) == 2**64 - 1
    with pytest.raises(ArithError):
        safe_arith.safe_add(2**64 - 1, 1)
    with pytest.raises(ArithError):
        safe_arith.safe_sub(3, 5)
    with pytest.raises(ArithError):
        safe_arith.safe_mul(2**33, 2**33)
    with pytest.raises(ArithError):
        safe_arith.safe_div(1, 0)


# ---------------------------------------------------------------------------
# stale-read: columns consumed while their source holds undrained dirt
# ---------------------------------------------------------------------------


def test_stale_column_read_is_audited(sanitize):
    state, _spec = trc._base_state(ForkName.ALTAIR, 200, 17)
    _make_persistent(state)
    cols = registry_columns_for(state)
    cols.refresh(state)
    _ = cols.balances  # fresh: clean read
    state.balances[0] = state.balances[0] + 5  # object-path write
    before = _viol("stale-read")
    with pytest.raises(SanitizerError, match="stale-read"):
        _ = cols.balances
    assert _viol("stale-read") == before + 1
    cols.refresh(state)  # drain → reads are clean again
    assert int(cols.balances[0]) == state.balances[0]


# ---------------------------------------------------------------------------
# soak: block ops + epoch transitions under the sanitizer, vs oracles
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("fork", [ForkName.ALTAIR, ForkName.ELECTRA])
def test_sanitize_soak_block_import_epoch_roundtrip(fork, monkeypatch):
    """Drive the real pipelines — columnar attestation batches, then a
    full epoch transition — with every sanitizer guard armed, and prove
    the result bit-identical to the scalar oracle run WITHOUT the
    sanitizer: the guards must catch nothing (zero violations) and
    change nothing (fingerprint equality). This is how a CoW regression
    gets caught before it reaches a 1M-validator node."""
    import test_attestation_batch as tab

    from lighthouse_tpu.state_processing import attestation_batch
    from lighthouse_tpu.state_processing.attestation_batch import (
        process_attestations,
        process_attestations_reference,
    )
    from lighthouse_tpu.state_processing.per_block import ConsensusContext

    bls.set_backend("fake_crypto")
    monkeypatch.setattr(attestation_batch, "_SMALL_BATCH_ROWS", 0)
    counters_before = {r: _viol(r) for r in sanitizer.RULES}

    rng = random.Random(41)
    subject, spec = tab._att_state(fork, 520, 41)
    oracle, _ = tab._att_state(fork, 520, 41)
    atts = tab._make_attestations(subject, fork, rng, 24)

    # subject: persistent representation + resident columns + sanitizer
    monkeypatch.setenv(sanitizer.ENV_VAR, "1")
    _make_persistent(subject)
    registry_columns_for(subject).refresh(subject)
    process_attestations(
        subject, atts, spec, E, False, ConsensusContext(subject.slot), fork
    )
    subject.slot = (
        subject.slot // E.SLOTS_PER_EPOCH + 1
    ) * E.SLOTS_PER_EPOCH - 1
    process_epoch(subject, spec, E)
    # a CoW branch taken mid-soak must keep its own root
    branch = subject.copy()
    branch_root = branch.hash_tree_root()

    # oracle: plain lists, scalar loops, sanitizer OFF
    monkeypatch.delenv(sanitizer.ENV_VAR)
    process_attestations_reference(
        oracle, atts, spec, E, False, ConsensusContext(oracle.slot), fork
    )
    oracle.slot = subject.slot
    assert registry_columns_for(oracle) is None  # the legacy snapshot path
    process_epoch(oracle, spec, E)

    got = trc._state_fingerprint(subject)
    want = trc._state_fingerprint(oracle)
    for key in want:
        assert got[key] == want[key], f"{fork}: '{key}' diverged under sanitize"
    assert branch.hash_tree_root() == branch_root
    for rule, before in counters_before.items():
        assert _viol(rule) == before, f"sanitizer flagged {rule} on clean flows"
