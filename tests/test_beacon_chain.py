"""BeaconChain orchestration tests: import pipeline, gossip verification,
attestation batch path, reorgs via fork choice, store persistence, pruning."""

import pytest

from lighthouse_tpu.beacon_chain import (
    AttestationError,
    BeaconChainHarness,
    BlockError,
)
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.store import DBColumn, HotColdDB, MemoryStore, SqliteStore
from lighthouse_tpu.types import MinimalEthSpec, minimal_spec


@pytest.fixture(autouse=True)
def fake_crypto():
    bls.set_backend("fake_crypto")
    yield
    bls.set_backend("host")


@pytest.fixture
def harness():
    return BeaconChainHarness(minimal_spec(), MinimalEthSpec, validator_count=64)


def test_chain_finality(harness):
    harness.extend_chain(8 * 4)
    assert harness.justified_epoch == 3
    assert harness.finalized_epoch == 2
    assert harness.chain.head_state.slot == 32


def test_gossip_block_verification(harness):
    harness.extend_chain(2)
    chain = harness.chain
    slot = chain.head_state.slot + 1
    harness.slot_clock.set_slot(slot)
    import lighthouse_tpu.state_processing as sp

    state = chain.head_state.copy()
    while state.slot < slot:
        sp.per_slot_processing(state, harness.spec, harness.E)
    proposer = sp.get_beacon_proposer_index(state, harness.E)
    block, _ = chain.produce_block_on_state(
        slot, harness.randao_reveal(proposer, slot)
    )
    signed = harness.sign_block(block)
    gossip_verified = chain.verify_block_for_gossip(signed)
    # double-propose detection
    with pytest.raises(BlockError, match="already produced"):
        chain.verify_block_for_gossip(signed)
    chain.process_block(gossip_verified)
    assert chain.head_root == gossip_verified.block_root


def test_future_block_rejected(harness):
    harness.extend_chain(1)
    chain = harness.chain
    slot = chain.head_state.slot + 5
    import lighthouse_tpu.state_processing as sp

    state = chain.head_state.copy()
    while state.slot < slot:
        sp.per_slot_processing(state, harness.spec, harness.E)
    proposer = sp.get_beacon_proposer_index(state, harness.E)
    block, _ = chain.produce_block_on_state(
        slot, harness.randao_reveal(proposer, slot)
    )
    signed = harness.sign_block(block)
    # clock still at slot 1
    with pytest.raises(BlockError, match="future"):
        chain.verify_block_for_gossip(signed)


def test_unknown_parent_rejected(harness):
    harness.extend_chain(1)
    t = harness.chain.types
    orphan = t.SignedBeaconBlock(
        message=t.BeaconBlock(
            slot=2, proposer_index=0, parent_root=b"\x77" * 32
        )
    )
    with pytest.raises(BlockError, match="parent"):
        harness.chain.process_block(orphan)


def test_attestation_gossip_batch(harness):
    harness.extend_chain(2, attest=False)
    chain = harness.chain
    slot = chain.head_state.slot
    atts = harness.make_unaggregated_attestations(slot, chain.head_root)
    assert len(atts) == 8  # 64 validators / 8 slots per epoch
    results = chain.process_attestation_batch(atts)
    assert all(not isinstance(r, Exception) for r in results)
    # duplicates rejected by the observed-attesters cache
    results2 = chain.process_attestation_batch(atts)
    assert all(isinstance(r, AttestationError) for r in results2)


def test_attestation_unknown_block_rejected(harness):
    harness.extend_chain(1)
    atts = harness.make_unaggregated_attestations(1, harness.chain.head_root)
    t = harness.chain.types
    bad = t.Attestation(
        aggregation_bits=atts[0].aggregation_bits,
        data=t.AttestationData(
            slot=atts[0].data.slot,
            index=atts[0].data.index,
            beacon_block_root=b"\x55" * 32,  # unknown
            source=atts[0].data.source,
            target=atts[0].data.target,
        ),
        signature=atts[0].signature,
    )
    with pytest.raises(AttestationError, match="unknown"):
        harness.chain.process_attestation(bad)


def test_reorg_by_weight(harness):
    """Two competing forks; attestations drive the head to the heavier one."""
    harness.extend_chain(2, attest=False)
    chain = harness.chain
    common_root = chain.head_root
    slot_a = chain.head_state.slot + 1
    harness.slot_clock.set_slot(slot_a + 1)

    # fork A at slot_a
    import lighthouse_tpu.state_processing as sp

    state = chain.head_state.copy()
    while state.slot < slot_a:
        sp.per_slot_processing(state, harness.spec, harness.E)
    proposer = sp.get_beacon_proposer_index(state, harness.E)
    block_a, _ = chain.produce_block_on_state(
        slot_a, harness.randao_reveal(proposer, slot_a)
    )
    signed_a = harness.sign_block(block_a)
    root_a = chain.process_block(signed_a)

    # fork B: different graffiti at the same slot (same proposer)
    block_b, _ = chain_produce_on(
        chain, common_root, slot_a, harness, graffiti=b"\x01" * 32
    )
    signed_b = harness.sign_block(block_b)
    root_b = chain.process_block(signed_b)
    assert root_a != root_b

    # all validators attest to fork B
    atts = harness.make_unaggregated_attestations(slot_a, root_b)
    chain.process_attestation_batch(atts)
    head = chain.recompute_head()
    assert head == root_b


def chain_produce_on(chain, parent_root, slot, harness, graffiti):
    """Produce a block on an explicit parent (not the current head)."""
    import lighthouse_tpu.state_processing as sp

    state = chain.state_at_block_root(parent_root).copy()
    while state.slot < slot:
        sp.per_slot_processing(state, harness.spec, harness.E)
    proposer = sp.get_beacon_proposer_index(state, harness.E)
    body = chain.types.BeaconBlockBody(
        randao_reveal=harness.randao_reveal(proposer, slot),
        eth1_data=state.eth1_data,
        graffiti=graffiti,
    )
    block = chain.types.BeaconBlock(
        slot=slot,
        proposer_index=proposer,
        parent_root=parent_root,
        state_root=b"\x00" * 32,
        body=body,
    )
    post = state.copy()
    ctxt = sp.ConsensusContext(slot)
    ctxt.set_proposer_index(proposer)
    sp.per_block_processing(
        post,
        chain.types.SignedBeaconBlock(message=block),
        harness.spec,
        harness.E,
        strategy=sp.BlockSignatureStrategy.NO_VERIFICATION,
        ctxt=ctxt,
        verify_block_root=False,
    )
    block.state_root = post.hash_tree_root()
    return block, post


def test_store_roundtrip(harness):
    harness.extend_chain(3)
    chain = harness.chain
    head_block = chain.head_block()
    stored = chain.store.get_block(chain.head_root)
    assert stored.hash_tree_root() == head_block.hash_tree_root()
    state = chain.state_by_state_root(head_block.message.state_root)
    assert state.slot == chain.head_state.slot
    assert state.hash_tree_root() == chain.head_state.hash_tree_root()


def test_sqlite_store(tmp_path):
    store = SqliteStore(str(tmp_path / "chain.db"))
    store.put(DBColumn.BEACON_BLOCK, b"k1", b"v1")
    store.do_atomically(
        [
            ("put", DBColumn.BEACON_STATE, b"k2", b"v2"),
            ("put", DBColumn.BEACON_BLOCK, b"k3", b"v3"),
            ("delete", DBColumn.BEACON_BLOCK, b"k1"),
        ]
    )
    assert store.get(DBColumn.BEACON_BLOCK, b"k1") is None
    assert store.get(DBColumn.BEACON_STATE, b"k2") == b"v2"
    assert store.get(DBColumn.BEACON_BLOCK, b"k3") == b"v3"
    assert store.keys(DBColumn.BEACON_BLOCK) == [b"k3"]
    store.close()


def test_chain_on_sqlite(tmp_path):
    store = HotColdDB(SqliteStore(str(tmp_path / "hot.db")))
    h = BeaconChainHarness(
        minimal_spec(), MinimalEthSpec, validator_count=64, store=store
    )
    h.extend_chain(8)
    assert h.chain.store.get_block(h.chain.head_root) is not None


def test_finality_prunes_states(harness):
    harness.extend_chain(8 * 5)
    finalized_epoch = harness.finalized_epoch
    assert finalized_epoch >= 3
    # snapshot cache only keeps unfinalized states (+ finalized root)
    finalized_slot = finalized_epoch * 8
    old = [
        r
        for r, s in harness.chain._states.items()
        if s.slot < finalized_slot and r != harness.chain.fork_choice.store.finalized_checkpoint.root
    ]
    assert old == []
    # finalized blocks were migrated to cold
    assert harness.chain.store.split_slot == finalized_slot
