"""Slasher detection: double votes, surround votes, double proposals,
pruning — and the produced slashings actually apply in the state
transition (slasher/src/slasher.rs test surface).

Every engine-generic test runs against BOTH engines: the columnar
min/max-span subsystem (default) and the retained scalar reference
(`slasher/reference.py`) — same detections, same emission order."""

from dataclasses import replace

import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.slasher import Slasher, SlasherConfig
from lighthouse_tpu.slasher.columnar import ColumnarSlasher
from lighthouse_tpu.slasher.reference import ReferenceSlasher
from lighthouse_tpu.types.containers import build_types
from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

T = build_types(E)

ENGINES = {"columnar": ColumnarSlasher, "reference": ReferenceSlasher}


@pytest.fixture(params=sorted(ENGINES))
def engine(request):
    return ENGINES[request.param]


def _att(indices, source, target, root=b"\x01" * 32, head=b"\x02" * 32):
    return T.IndexedAttestation(
        attesting_indices=indices,
        data=T.AttestationData(
            slot=target * E.SLOTS_PER_EPOCH,
            index=0,
            beacon_block_root=head,
            source=T.Checkpoint(epoch=source, root=root),
            target=T.Checkpoint(epoch=target, root=root),
        ),
        signature=b"\x00" * 96,
    )


def _header(proposer, slot, state_root=b"\x00" * 32):
    return T.SignedBeaconBlockHeader(
        message=T.BeaconBlockHeader(
            slot=slot,
            proposer_index=proposer,
            parent_root=b"\x11" * 32,
            state_root=state_root,
            body_root=b"\x22" * 32,
        ),
        signature=b"\x00" * 96,
    )


def test_double_vote_detected(engine):
    s = engine(E)
    s.accept_attestation(_att([1, 2], 0, 5, head=b"\x02" * 32))
    s.accept_attestation(_att([2, 3], 0, 5, head=b"\x03" * 32))  # same target, diff data
    out = s.process_queued(current_epoch=6)
    assert out["attester_slashings"] >= 1
    atts, _ = s.drain_slashings()
    assert atts
    sl = atts[0]
    assert sl.attestation_1.data.target.epoch == 5
    assert sl.attestation_1.data.hash_tree_root() != sl.attestation_2.data.hash_tree_root()


def test_duplicate_attestation_not_slashable(engine):
    s = engine(E)
    a = _att([1], 0, 5)
    s.accept_attestation(a)
    s.accept_attestation(_att([1], 0, 5))  # identical data
    out = s.process_queued(6)
    assert out["attester_slashings"] == 0


def test_surround_both_directions(engine):
    from lighthouse_tpu.state_processing.accessors import (
        is_slashable_attestation_data,
    )

    s = engine(E)
    s.accept_attestation(_att([7], 2, 3))
    s.process_queued(4)
    # new surrounds old: (1, 5) ⊃ (2, 3)
    s.accept_attestation(_att([7], 1, 5))
    assert s.process_queued(6)["attester_slashings"] == 1
    sl, _ = s.drain_slashings()
    # emitted order must satisfy the spec predicate (data_1 surrounds data_2)
    assert is_slashable_attestation_data(sl[0].attestation_1.data, sl[0].attestation_2.data)

    s2 = engine(E)
    s2.accept_attestation(_att([9], 1, 6))
    s2.process_queued(7)
    # old surrounds new: (2, 4) ⊂ (1, 6)
    s2.accept_attestation(_att([9], 2, 4))
    assert s2.process_queued(7)["attester_slashings"] == 1
    sl2, _ = s2.drain_slashings()
    assert is_slashable_attestation_data(
        sl2[0].attestation_1.data, sl2[0].attestation_2.data
    )


def test_double_proposal_detected(engine):
    s = engine(E)
    s.accept_block_header(_header(4, 32, state_root=b"\xaa" * 32))
    s.accept_block_header(_header(4, 32, state_root=b"\xbb" * 32))
    s.accept_block_header(_header(4, 33, state_root=b"\xcc" * 32))  # different slot ok
    out = s.process_queued(5)
    assert out["proposer_slashings"] == 1
    _, props = s.drain_slashings()
    assert props[0].signed_header_1.message.slot == 32


def test_double_proposal_not_reemitted_on_relay(engine):
    """Regression: the same equivocating header pair is re-gossiped by
    every peer; a re-seen pair must not re-emit another ProposerSlashing
    (one emission per equivocation, dedup keyed (proposer, slot, roots))."""
    s = engine(E)
    h1 = _header(4, 32, state_root=b"\xaa" * 32)
    h2 = _header(4, 32, state_root=b"\xbb" * 32)
    s.accept_block_header(h1)
    s.accept_block_header(h2)
    assert s.process_queued(5)["proposer_slashings"] == 1
    # the pair re-arrives (relay storm), same cycle AND a later cycle
    s.accept_block_header(h1)
    s.accept_block_header(h2)
    s.accept_block_header(h2)
    assert s.process_queued(5)["proposer_slashings"] == 0
    s.accept_block_header(h2)
    assert s.process_queued(6)["proposer_slashings"] == 0
    _, props = s.drain_slashings()
    assert len(props) == 1
    # a THIRD conflicting header is a new pair: emitted once
    s.accept_block_header(_header(4, 32, state_root=b"\xcc" * 32))
    assert s.process_queued(6)["proposer_slashings"] == 1


def test_pruning_bounds_history(engine):
    s = engine(E, SlasherConfig(history_length=4))
    s.accept_attestation(_att([1], 0, 1))
    s.process_queued(1)
    assert s.has_attestation_record(1, 1)
    s.process_queued(100)  # far future: epoch-1 record pruned
    assert not s.has_attestation_record(1, 1)
    assert s.attestation_record_count() == 0


def test_detected_slashing_applies_in_state_transition(engine):
    """End-to-end: the slasher's output feeds process_attester_slashing and
    the offender gets slashed (the slasher/service → op-pool → block path)."""
    from lighthouse_tpu.state_processing import interop_genesis_state
    from lighthouse_tpu.state_processing.per_block import process_attester_slashing
    from lighthouse_tpu.types.chain_spec import minimal_spec

    bls.set_backend("fake_crypto")
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    kps = bls.interop_keypairs(8)
    state = interop_genesis_state(kps, 1_600_000_000, b"\x42" * 32, spec, E)
    state.slot = 6 * E.SLOTS_PER_EPOCH

    s = engine(E)
    s.accept_attestation(_att([3], 0, 5, head=b"\x02" * 32))
    s.accept_attestation(_att([3], 0, 5, head=b"\x03" * 32))
    s.process_queued(6)
    slashings, _ = s.drain_slashings()
    assert slashings
    process_attester_slashing(state, slashings[0], spec, E, verify_signatures=False)
    assert state.validators[3].slashed


def test_slasher_service_end_to_end():
    """SlasherService (slasher/service analog): a double vote observed on
    the live chain is detected at the epoch tick and the slashing lands
    in the op pool — then in a produced block."""
    from lighthouse_tpu.beacon_chain.harness import BeaconChainHarness
    from lighthouse_tpu.slasher.service import SlasherService
    from lighthouse_tpu.types.chain_spec import minimal_spec

    bls.set_backend("fake_crypto")
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    h = BeaconChainHarness(spec, E, validator_count=16)
    svc = SlasherService(h.chain)
    assert h.chain.slasher_service is svc
    h.extend_chain(2 * E.SLOTS_PER_EPOCH)  # normal life: nothing slashable

    # equivocation: validator 3 votes twice for the same target epoch
    epoch = 1
    a1 = _att([3], 0, epoch, root=b"\x0a" * 32)
    a2 = _att([3], 0, epoch, root=b"\x0b" * 32)
    svc.observe_indexed_attestation(a1)
    svc.observe_indexed_attestation(a2)
    stats = svc.on_slot(h.chain.head_state.slot + E.SLOTS_PER_EPOCH)
    assert stats is not None
    assert h.chain.op_pool._attester_slashings, "slashing not pooled"
    # the produced block carries it
    slot = h.chain.head_state.slot + 1
    h.slot_clock.set_slot(slot)
    block, _ = h.chain.produce_block_on_state(slot, h.randao_reveal(0, slot))
    assert len(block.body.attester_slashings) == 1
    slashed = set(
        block.body.attester_slashings[0].attestation_1.attesting_indices
    ) & set(block.body.attester_slashings[0].attestation_2.attesting_indices)
    assert slashed == {3}


def test_columnar_kill_switch(tmp_path):
    """The factory always builds the columnar subsystem; the retained
    scalar oracle builds from the same arguments."""
    from lighthouse_tpu.store import open_item_store

    assert isinstance(Slasher(E), ColumnarSlasher)
    config = SlasherConfig(history_length=64)
    store = open_item_store(str(tmp_path / "slasher-db"))
    columnar = Slasher(E, config, store)
    oracle = ReferenceSlasher(E, config, store)
    assert isinstance(columnar, ColumnarSlasher)
    assert columnar.config == oracle.config == config


def test_persistence_restart_detects_double_vote(engine, tmp_path):
    """Detection history written through the KV store survives a restart:
    the first vote lands before the 'crash', the conflicting one after."""
    from lighthouse_tpu.store import open_item_store

    from lighthouse_tpu.store.kv import DBColumn

    store = open_item_store(str(tmp_path / "slasher-db"))
    s1 = engine(E, store=store)
    s1.accept_attestation(_att([7, 8], 0, 5, head=b"\x02" * 32))
    s1.accept_block_header(_header(3, 41))
    assert s1.process_queued(current_epoch=6) == {
        "attester_slashings": 0,
        "proposer_slashings": 0,
    }
    # the body is stored ONCE for the 2-index aggregate; records are small
    assert len(store.keys(DBColumn.SLASHER_INDEXED)) == 1
    assert len(store.keys(DBColumn.SLASHER_ATTESTATION)) == 2
    del s1  # no clean shutdown needed — process_queued already flushed

    s2 = engine(E, store=store)
    # records reloaded
    assert s2.has_attestation_record(7, 5) and s2.has_attestation_record(8, 5)
    assert 3 in s2._blocks and 41 in s2._blocks[3]
    # conflicting vote and proposal arriving after restart still slash
    s2.accept_attestation(_att([8], 0, 5, head=b"\x03" * 32))
    s2.accept_block_header(_header(3, 41, state_root=b"\x99" * 32))
    out = s2.process_queued(current_epoch=6)
    assert out["attester_slashings"] == 1
    assert out["proposer_slashings"] == 1
    store.close()


def test_persistence_prunes_on_disk(engine, tmp_path):
    from lighthouse_tpu.store import open_item_store
    from lighthouse_tpu.store.kv import DBColumn

    store = open_item_store(str(tmp_path / "slasher-db"))
    s = engine(E, SlasherConfig(history_length=4), store=store)
    s.accept_attestation(_att([1], 0, 2))
    s.process_queued(current_epoch=3)
    assert store.keys(DBColumn.SLASHER_ATTESTATION)
    s.process_queued(current_epoch=10)  # floor=6 > target 2 → pruned
    assert store.keys(DBColumn.SLASHER_ATTESTATION) == []
    assert store.keys(DBColumn.SLASHER_INDEXED) == []
    # a fresh instance sees the pruned view
    s2 = engine(E, SlasherConfig(history_length=4), store=store)
    assert s2.attestation_record_count() == 0
    store.close()
