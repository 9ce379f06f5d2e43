"""Columnar attestation pipeline: differential fuzz vs the scalar oracle,
reject parity, participation-column residency/aliasing, and the satellite
fast paths (eth1 vote tally, batched sync-committee sampling, bitmask
max-cover, phase0 validate-then-mutate).

Contract (attestation_batch.py): the batched path must leave the state
bit-identical to `process_attestations_reference` — participation bytes,
balances (proposer reward floors!), and the state root — across forks,
randomized committees, sparse/full/duplicate aggregation patterns and
already-set flags; and a rejected batch must leave NO partial writes.
"""

import random
from dataclasses import replace

import numpy as np
import pytest

from lighthouse_tpu.beacon_chain.chain import _make_persistent
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.metrics import REGISTRY
from lighthouse_tpu.ssz.persistent import PersistentByteList
from lighthouse_tpu.state_processing import interop_genesis_state
from lighthouse_tpu.state_processing.accessors import (
    committee_cache_at,
    get_attesting_indices,
    get_current_epoch,
    get_previous_epoch,
)
from lighthouse_tpu.state_processing import attestation_batch
from lighthouse_tpu.state_processing.attestation_batch import (
    process_attestations,
    process_attestations_reference,
)

# the real calibrated threshold, captured before the force-columnar
# fixture zeroes it for the differential tests
_REAL_SMALL_BATCH_ROWS = attestation_batch._SMALL_BATCH_ROWS
from lighthouse_tpu.state_processing.per_block import (
    BlockProcessingError,
    ConsensusContext,
)
from lighthouse_tpu.state_processing.registry_columns import (
    registry_columns_for,
)
from lighthouse_tpu.types.chain_spec import ForkName, minimal_spec
from lighthouse_tpu.types.containers import build_types
from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

T = build_types(E)

_FORK_OVERRIDES = {
    ForkName.ALTAIR: dict(altair_fork_epoch=0),
    ForkName.DENEB: dict(
        altair_fork_epoch=0,
        bellatrix_fork_epoch=0,
        capella_fork_epoch=0,
        deneb_fork_epoch=0,
    ),
    ForkName.ELECTRA: dict(
        altair_fork_epoch=0,
        bellatrix_fork_epoch=0,
        capella_fork_epoch=0,
        deneb_fork_epoch=0,
        electra_fork_epoch=0,
    ),
}


@pytest.fixture(autouse=True)
def fake_crypto():
    old = bls.backend_name()
    bls.set_backend("fake_crypto")
    yield
    bls.set_backend(old)


@pytest.fixture(autouse=True)
def force_columnar(monkeypatch):
    """Zero the small-batch dispatch threshold so the minimal-preset
    fixtures exercise the columnar fold (the dispatch itself is covered
    by test_small_batch_dispatch)."""
    monkeypatch.setattr(attestation_batch, "_SMALL_BATCH_ROWS", 0)


def _att_state(fork: ForkName, n: int, seed: int):
    """A mid-epoch state with randomized participation (some flags
    already set) and non-trivial block roots, positioned so both
    previous- and current-epoch attestations are includable."""
    rng = random.Random(seed)
    spec = replace(minimal_spec(), **_FORK_OVERRIDES[fork])
    state = interop_genesis_state(
        bls.interop_keypairs(8), 1_600_000_000, b"\x42" * 32, spec, E
    )
    v0 = state.validators[0]
    vs, bal = [], []
    for i in range(n):
        v = v0.copy()
        v.withdrawal_credentials = i.to_bytes(32, "little")
        v.effective_balance = rng.choice(
            [32_000_000_000, 31_000_000_000, 16_000_000_000]
        )
        vs.append(v)
        bal.append(32_000_000_000)
    state.validators = vs
    state.balances = bal
    state.previous_epoch_participation = bytearray(
        rng.randrange(8) for _ in range(n)
    )
    state.current_epoch_participation = bytearray(
        rng.randrange(8) for _ in range(n)
    )
    state.inactivity_scores = [0] * n
    for s in range(len(state.block_roots)):
        state.block_roots[s] = bytes([s % 251]) * 32
    state.slot = 3 * E.SLOTS_PER_EPOCH + E.SLOTS_PER_EPOCH // 2
    return state, spec


def _make_attestations(state, fork, rng, count):
    """`count` valid attestations over random includable (slot, committee)
    pairs: random sparse/full bits, deliberate duplicates, and a mix of
    matching/missing head roots."""
    from lighthouse_tpu.state_processing.accessors import (
        get_block_root,
        get_block_root_at_slot,
    )

    current = get_current_epoch(state, E)
    lo = (
        current * E.SLOTS_PER_EPOCH - E.SLOTS_PER_EPOCH
        if fork >= ForkName.DENEB
        else state.slot - E.SLOTS_PER_EPOCH
    )
    hi = state.slot - E.MIN_ATTESTATION_INCLUSION_DELAY
    atts = []
    while len(atts) < count:
        slot = rng.randrange(lo, hi + 1)
        epoch = slot // E.SLOTS_PER_EPOCH
        cc = committee_cache_at(state, epoch, E)
        index = rng.randrange(cc.committees_per_slot)
        committee = cc.committee_array(slot, index)
        density = rng.choice([0.05, 0.5, 1.0])
        bits = [rng.random() < density for _ in range(committee.size)]
        if not any(bits):
            bits[rng.randrange(len(bits))] = True
        source = (
            state.current_justified_checkpoint
            if epoch == current
            else state.previous_justified_checkpoint
        )
        head = (
            get_block_root_at_slot(state, slot, E)
            if rng.random() < 0.7
            else b"\x99" * 32
        )
        att = T.Attestation(
            aggregation_bits=bits,
            data=T.AttestationData(
                slot=slot,
                index=index,
                beacon_block_root=head,
                source=source,
                target=T.Checkpoint(
                    epoch=epoch, root=get_block_root(state, epoch, E)
                ),
            ),
            signature=b"\x00" * 96,
        )
        atts.append(att)
        if rng.random() < 0.4 and len(atts) < count:
            # deliberate duplicate committee, different pattern: the
            # first-occurrence reward attribution fold must handle it
            bits2 = [b or (rng.random() < 0.3) for b in bits]
            atts.append(
                T.Attestation(
                    aggregation_bits=bits2,
                    data=att.data,
                    signature=b"\x00" * 96,
                )
            )
    return atts


def _ctxt(state):
    c = ConsensusContext(state.slot)
    c.set_proposer_index(0)
    return c


def _run_both(state, spec, fork, atts):
    """(batched-resident, scalar-plain) end states for the same input."""
    batched = state.copy()
    _make_persistent(batched)
    registry_columns_for(batched).refresh(batched)
    process_attestations(batched, atts, spec, E, False, _ctxt(batched), fork)
    oracle = state.copy()
    process_attestations_reference(
        oracle, atts, spec, E, False, _ctxt(oracle), fork
    )
    return batched, oracle


@pytest.mark.parametrize(
    "fork", [ForkName.ALTAIR, ForkName.DENEB, ForkName.ELECTRA]
)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_batch_vs_reference_differential(fork, seed):
    rng = random.Random(100 + seed)
    state, spec = _att_state(fork, 192, seed)
    atts = _make_attestations(state, fork, rng, 24)
    batched, oracle = _run_both(state, spec, fork, atts)
    assert bytes(batched.previous_epoch_participation) == bytes(
        oracle.previous_epoch_participation
    )
    assert bytes(batched.current_epoch_participation) == bytes(
        oracle.current_epoch_participation
    )
    assert list(batched.balances) == list(oracle.balances)
    # representation-independent: the state roots agree too
    assert batched.hash_tree_root() == oracle.hash_tree_root()


def test_batch_matches_reference_on_already_set_flags():
    """A second identical batch earns the proposer nothing on either path."""
    fork = ForkName.ALTAIR
    rng = random.Random(7)
    state, spec = _att_state(fork, 128, 7)
    atts = _make_attestations(state, fork, rng, 12)
    batched, oracle = _run_both(state, spec, fork, atts)
    b2, o2 = batched.copy(), oracle.copy()
    process_attestations(b2, atts, spec, E, False, _ctxt(b2), fork)
    process_attestations_reference(o2, atts, spec, E, False, _ctxt(o2), fork)
    assert list(b2.balances) == list(o2.balances)
    assert list(b2.balances) == list(batched.balances)  # no new rewards
    assert bytes(b2.current_epoch_participation) == bytes(
        batched.current_epoch_participation
    )


@pytest.mark.parametrize("message", ["source", "target"])
def test_reject_parity_and_no_partial_writes(message):
    """Both paths reject the same malformed attestation, and the batched
    path leaves NO partial writes even when a LATER attestation in the
    block is the bad one (the scalar loop would have already mutated)."""
    fork = ForkName.ALTAIR
    rng = random.Random(21)
    state, spec = _att_state(fork, 128, 21)
    atts = _make_attestations(state, fork, rng, 6)
    bad = atts[-1]
    if message == "source":
        wrong = T.Checkpoint(
            epoch=bad.data.source.epoch, root=b"\x55" * 32
        )
        bad_data = T.AttestationData(
            slot=bad.data.slot,
            index=bad.data.index,
            beacon_block_root=bad.data.beacon_block_root,
            source=wrong,
            target=bad.data.target,
        )
        atts[-1] = T.Attestation(
            aggregation_bits=bad.aggregation_bits,
            data=bad_data,
            signature=b"\x00" * 96,
        )
        expect = "source checkpoint mismatch"
    else:
        bad_data = T.AttestationData(
            slot=bad.data.slot,
            index=bad.data.index,
            beacon_block_root=bad.data.beacon_block_root,
            source=bad.data.source,
            target=T.Checkpoint(
                epoch=bad.data.target.epoch + 5, root=bad.data.target.root
            ),
        )
        atts[-1] = T.Attestation(
            aggregation_bits=bad.aggregation_bits,
            data=bad_data,
            signature=b"\x00" * 96,
        )
        expect = "target"

    batched = state.copy()
    _make_persistent(batched)
    before_prev = bytes(batched.previous_epoch_participation)
    before_cur = bytes(batched.current_epoch_participation)
    before_bal = list(batched.balances)
    with pytest.raises(BlockProcessingError, match=expect):
        process_attestations(
            batched, atts, spec, E, False, _ctxt(batched), fork
        )
    assert bytes(batched.previous_epoch_participation) == before_prev
    assert bytes(batched.current_epoch_participation) == before_cur
    assert list(batched.balances) == before_bal

    oracle = state.copy()
    with pytest.raises(BlockProcessingError):
        process_attestations_reference(
            oracle, atts, spec, E, False, _ctxt(oracle), fork
        )


def test_reject_empty_bits_and_bad_length():
    fork = ForkName.ALTAIR
    rng = random.Random(33)
    state, spec = _att_state(fork, 128, 33)
    good = _make_attestations(state, fork, rng, 1)[0]
    empty = T.Attestation(
        aggregation_bits=[False] * len(good.aggregation_bits),
        data=good.data,
        signature=b"\x00" * 96,
    )
    with pytest.raises(BlockProcessingError, match="invalid indexed"):
        process_attestations(
            state.copy(), [empty], spec, E, False, _ctxt(state), fork
        )
    short = T.Attestation(
        aggregation_bits=good.aggregation_bits[:-1],
        data=good.data,
        signature=b"\x00" * 96,
    )
    with pytest.raises(BlockProcessingError, match="bitfield length"):
        process_attestations(
            state.copy(), [short], spec, E, False, _ctxt(state), fork
        )


def test_kill_switch_runs_scalar_path():
    """The scalar oracle and the columnar pipeline give the same
    participation and balances for the same block."""
    fork = ForkName.ALTAIR
    rng = random.Random(5)
    state, spec = _att_state(fork, 96, 5)
    atts = _make_attestations(state, fork, rng, 4)
    c = REGISTRY.counter("attestation_batch_total")
    before = c.value(path="columnar")
    off = state.copy()
    process_attestations_reference(off, atts, spec, E, False, _ctxt(off), fork)
    assert c.value(path="columnar") == before
    on = state.copy()
    process_attestations(on, atts, spec, E, False, _ctxt(on), fork)
    assert c.value(path="columnar") == before + 1
    assert bytes(off.current_epoch_participation) == bytes(
        on.current_epoch_participation
    )
    assert list(off.balances) == list(on.balances)


def test_indexed_attestations_shared_with_context():
    """The batch pipeline's columnar assembly must be what fork choice /
    the slasher / signature sets see: memoized on the context, sorted,
    and SSZ-identical to a field-machinery construction."""
    fork = ForkName.ALTAIR
    rng = random.Random(9)
    state, spec = _att_state(fork, 96, 9)
    atts = _make_attestations(state, fork, rng, 3)
    st = state.copy()
    _make_persistent(st)
    ctxt = _ctxt(st)
    process_attestations(st, atts, spec, E, False, ctxt, fork)
    for att in atts:
        indexed = ctxt.peek_indexed_attestation(att)
        assert indexed is not None
        expect = get_attesting_indices(st, att.data, att.aggregation_bits, E)
        assert list(indexed.attesting_indices) == expect
        rebuilt = T.IndexedAttestation(
            attesting_indices=expect,
            data=att.data,
            signature=att.signature,
        )
        assert indexed.hash_tree_root() == rebuilt.hash_tree_root()
        assert indexed.serialize() == rebuilt.serialize()


# --- participation columns: residency, aliasing, rotation -------------------


def test_participation_copy_aliasing_isolation():
    fork = ForkName.ALTAIR
    rng = random.Random(13)
    state, spec = _att_state(fork, 128, 13)
    _make_persistent(state)
    registry_columns_for(state).refresh(state)
    frozen = state.copy()
    frozen_bytes = bytes(frozen.current_epoch_participation)
    frozen_root = frozen.hash_tree_root()
    atts = _make_attestations(state, fork, rng, 8)
    process_attestations(state, atts, spec, E, False, _ctxt(state), fork)
    assert bytes(state.current_epoch_participation) != frozen_bytes or bytes(
        state.previous_epoch_participation
    ) != bytes(frozen.previous_epoch_participation)
    # the copy saw none of it — list contents, resident columns, root
    assert bytes(frozen.current_epoch_participation) == frozen_bytes
    cols = registry_columns_for(frozen)
    cols.refresh(frozen)
    assert cols.current_epoch_participation.tobytes() == frozen_bytes
    assert frozen.hash_tree_root() == frozen_root


def test_participation_rotation_keeps_residency():
    """process_participation_flag_updates on the persistent representation
    must rotate the columns and hash caches along: zero column rebuilds
    and matching roots afterwards."""
    from lighthouse_tpu.state_processing.altair import (
        process_participation_flag_updates,
    )

    fork = ForkName.ALTAIR
    state, spec = _att_state(fork, 128, 17)
    _make_persistent(state)
    cols = registry_columns_for(state)
    cols.refresh(state)
    state.hash_tree_root()  # warm the per-field caches
    prev_cur = bytes(state.current_epoch_participation)
    c = REGISTRY.counter("registry_columns_rebuilds_total")
    before = {
        f: c.value(field=f)
        for f in (
            "previous_epoch_participation",
            "current_epoch_participation",
        )
    }
    process_participation_flag_updates(state, E)
    cols.refresh(state)
    assert isinstance(state.previous_epoch_participation, PersistentByteList)
    assert bytes(state.previous_epoch_participation) == prev_cur
    assert bytes(state.current_epoch_participation) == bytes(
        len(state.validators)
    )
    for f, v in before.items():
        assert c.value(field=f) == v, f"rotation rebuilt {f}"
    assert cols.previous_epoch_participation.tobytes() == prev_cur
    assert not cols.current_epoch_participation.any()
    # root parity with a plain recompute after rotation
    assert state.hash_tree_root() == type(state).hash_tree_root_of(state)


@pytest.mark.perf_smoke
def test_happy_path_zero_scalar_fallbacks(monkeypatch):
    """Any real-shaped batch must engage the columnar fold."""
    monkeypatch.setattr(
        attestation_batch, "_SMALL_BATCH_ROWS", _REAL_SMALL_BATCH_ROWS
    )
    c = REGISTRY.counter("attestation_batch_total")

    # a block whose row count clears the dispatch threshold goes columnar
    fork = ForkName.ALTAIR
    state, aspec = _att_state(fork, 2048, 41)
    rng = random.Random(41)
    atts = _make_attestations(state, fork, rng, 12)
    while sum(len(a.aggregation_bits) for a in atts) < 2 * _REAL_SMALL_BATCH_ROWS:
        atts += _make_attestations(state, fork, rng, 4)
    before_columnar = c.value(path="columnar")
    st = state.copy()
    _make_persistent(st)
    process_attestations(st, atts, aspec, E, False, _ctxt(st), fork)
    assert c.value(path="columnar") == before_columnar + 1


def test_small_batch_dispatch(monkeypatch):
    """Blocks under the row threshold take the (cheaper) scalar loop,
    counted separately from the kill-switch path — and produce the same
    state as the forced columnar fold."""
    monkeypatch.setattr(
        attestation_batch, "_SMALL_BATCH_ROWS", _REAL_SMALL_BATCH_ROWS
    )
    fork = ForkName.ALTAIR
    state, spec = _att_state(fork, 96, 19)
    rng = random.Random(19)
    atts = _make_attestations(state, fork, rng, 3)
    assert sum(len(a.aggregation_bits) for a in atts) < _REAL_SMALL_BATCH_ROWS
    c = REGISTRY.counter("attestation_batch_total")
    before = {p: c.value(path=p) for p in ("columnar", "scalar", "scalar_small")}
    small = state.copy()
    process_attestations(small, atts, spec, E, False, _ctxt(small), fork)
    assert c.value(path="scalar_small") == before["scalar_small"] + 1
    assert c.value(path="columnar") == before["columnar"]
    monkeypatch.setattr(attestation_batch, "_SMALL_BATCH_ROWS", 0)
    forced = state.copy()
    process_attestations(forced, atts, spec, E, False, _ctxt(forced), fork)
    assert bytes(small.current_epoch_participation) == bytes(
        forced.current_epoch_participation
    )
    assert bytes(small.previous_epoch_participation) == bytes(
        forced.previous_epoch_participation
    )
    assert list(small.balances) == list(forced.balances)


# --- PersistentByteList -----------------------------------------------------


def test_persistent_byte_list_matches_bytearray_root():
    from lighthouse_tpu.ssz.core import ParticipationList

    rng = random.Random(3)
    data = bytes(rng.randrange(8) for _ in range(10_000))
    plist_t = ParticipationList[1 << 20]
    assert plist_t.hash_tree_root_of(
        PersistentByteList(data)
    ) == plist_t.hash_tree_root_of(bytearray(data))
    assert bytes(PersistentByteList(data)) == data


def test_persistent_byte_list_cow_and_dirty_channels():
    lst = PersistentByteList(bytes(9000))
    cp = lst.copy()
    assert lst.shared_block_count(cp) == 2
    lst[5] = 7
    lst[8500] = 3
    lst.append(9)
    assert cp[5] == 0 and len(cp) == 9000
    base, dirty = lst.drain_dirty()
    assert dirty == {5, 8500, 9000}
    # unchanged-value writes don't mark
    lst[5] = 7
    _, dirty2 = lst.drain_dirty()
    assert dirty2 == set()
    # store_array marks exactly the changed rows in the named channel
    # (stage into a copy: load_array views are read-only under beacon-san)
    arr = lst.load_array().copy()
    arr[100] = 42
    lst.channel("columns")
    lst.store_array(arr)
    _, hash_dirty = lst.drain_dirty()
    _, col_dirty = lst.drain_dirty("columns")
    assert hash_dirty == {100}
    # the columns channel was created after the earlier writes, so it
    # only ever saw the store_array mark
    assert col_dirty == {100}


def test_persistent_byte_list_sparse_reroot_exact():
    from lighthouse_tpu.ssz.cached_tree_hash import ByteListCache
    from lighthouse_tpu.ssz.core import ParticipationList

    rng = random.Random(4)
    plist_t = ParticipationList[1 << 16]
    lst = PersistentByteList(bytes(rng.randrange(8) for _ in range(20_000)))
    cache = ByteListCache(plist_t.chunk_count())
    cache.root(lst)  # commit the baseline (full extract)
    for _ in range(50):
        lst[rng.randrange(len(lst))] = rng.randrange(8)
    lst.append(5)
    root1 = cache.root(lst)
    fresh = ByteListCache(plist_t.chunk_count())
    assert root1 == fresh.root(lst)


# --- satellites -------------------------------------------------------------


def test_eth1_tally_matches_scan():
    from lighthouse_tpu.state_processing.per_block import (
        eth1_data_vote_count_scan,
        process_eth1_data,
    )

    rng = random.Random(6)
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    state = interop_genesis_state(
        bls.interop_keypairs(8), 1_600_000_000, b"\x42" * 32, spec, E
    )
    period = E.slots_per_eth1_voting_period()
    choices = [
        T.Eth1Data(
            deposit_root=bytes([i]) * 32, deposit_count=8, block_hash=b"\x01" * 32
        )
        for i in range(3)
    ]
    for step in range(3 * period):
        vote = rng.choice(choices)
        pre_scan = eth1_data_vote_count_scan(state, vote) + 1
        process_eth1_data(state, vote, E)
        assert eth1_data_vote_count_scan(state, vote) == pre_scan
        tally = state.__dict__["_lh_eth1_tally"]
        assert tally["counts"][vote.serialize()] == pre_scan
        if pre_scan * 2 > period:
            assert state.eth1_data == vote
        if (step + 1) % period == 0:
            # period boundary: the epoch reset replaces the list (the
            # tally keys on the list object's identity and rebuilds)
            state.eth1_data_votes = []
            assert eth1_data_vote_count_scan(state, vote) == 0
            process_eth1_data(state, vote, E)
            assert eth1_data_vote_count_scan(state, vote) == 1


def test_sync_committee_indices_batched_matches_reference():
    from lighthouse_tpu.state_processing.altair import (
        get_next_sync_committee_indices,
        get_next_sync_committee_indices_reference,
    )

    for seed in (1, 2):
        state, spec = _att_state(ForkName.ALTAIR, 100 + seed * 37, seed)
        ref = get_next_sync_committee_indices_reference(state, E)
        fast = get_next_sync_committee_indices(state, E)
        assert fast == ref
        # resident-columns path agrees too
        st = state.copy()
        _make_persistent(st)
        registry_columns_for(st).refresh(st)
        assert get_next_sync_committee_indices(st, E) == ref


def test_phase0_attestation_validates_before_mutating():
    spec = minimal_spec()
    state = interop_genesis_state(
        bls.interop_keypairs(16), 1_600_000_000, b"\x42" * 32, spec, E
    )
    state.slot = 2 * E.SLOTS_PER_EPOCH + 2
    for s in range(len(state.block_roots)):
        state.block_roots[s] = bytes([s % 251]) * 32
    from lighthouse_tpu.state_processing.accessors import (
        get_beacon_committee,
        get_block_root,
    )
    from lighthouse_tpu.state_processing.per_block import process_attestation

    current = get_current_epoch(state, E)
    slot = state.slot - 1
    committee = get_beacon_committee(state, slot, 0, E)
    att = T.Attestation(
        aggregation_bits=[False] * len(committee),  # empty => invalid indexed
        data=T.AttestationData(
            slot=slot,
            index=0,
            beacon_block_root=get_block_root(state, current, E),
            source=state.current_justified_checkpoint,
            target=T.Checkpoint(
                epoch=current, root=get_block_root(state, current, E)
            ),
        ),
        signature=b"\x00" * 96,
    )
    before = len(state.current_epoch_attestations)
    with pytest.raises(BlockProcessingError, match="invalid indexed"):
        process_attestation(state, att, spec, E, False, _ctxt(state))
    # the old order appended the PendingAttestation before validating
    assert len(state.current_epoch_attestations) == before
    assert len(state.previous_epoch_attestations) == 0


def test_op_pool_bitmask_max_cover():
    """The numpy coverage sets must reproduce greedy max-cover exactly:
    biggest uncovered gain first, ties to insertion order, zero-gain
    candidates dropped."""
    from lighthouse_tpu.beacon_chain.op_pool import OperationPool

    spec = replace(minimal_spec(), altair_fork_epoch=0)
    state = interop_genesis_state(
        bls.interop_keypairs(16), 1_600_000_000, b"\x42" * 32, spec, E
    )
    state.slot = E.SLOTS_PER_EPOCH + 2
    pool = OperationPool(spec, E)
    current = get_current_epoch(state, E)
    slot = state.slot - 1
    cc = committee_cache_at(state, current, E)
    committee = cc.committee_array(slot, 0)
    k = committee.size

    def att(bits):
        return T.Attestation(
            aggregation_bits=bits,
            data=T.AttestationData(
                slot=slot,
                index=0,
                beacon_block_root=b"\x00" * 32,
                source=state.current_justified_checkpoint,
                target=T.Checkpoint(epoch=current, root=b"\x00" * 32),
            ),
            signature=b"\x00" * 96,
        )

    full = [True] * k
    half = [i < k // 2 for i in range(k)]
    other = [i >= k // 2 for i in range(k)]
    for a in (att(half), att(other), att(full)):
        # bypass the insert-time disjoint merge: max-cover must see the
        # exact aggregation patterns, not their union
        pool._add_unmerged(a)
    chosen = pool.get_attestations_for_block(state)
    # full covers everything; half/other add nothing afterwards
    assert len(chosen) == 1
    assert list(chosen[0].aggregation_bits) == full
    # the retained rescan walk packs the identical set
    assert pool.get_attestations_for_block_reference(state) == chosen
