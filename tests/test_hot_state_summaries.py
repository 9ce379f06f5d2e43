"""Hot states as the reference stores them (hot_cold_store.rs
store_hot_state): in full at an epoch boundary, as a HotStateSummary at any
other slot, rebuilt by block replay on read; the import's stage spans;
the blob-proof counter of the gossip sidecar path; and the state an
attestation is indexed on, by its shuffling's decision root."""

import random
from dataclasses import replace

import pytest

from lighthouse_tpu.beacon_chain.chain import BeaconChain
from lighthouse_tpu.beacon_chain.harness import (
    HARNESS_GENESIS_TIME,
    BeaconChainHarness,
)
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.kzg import FR_MODULUS, Kzg, TrustedSetup
from lighthouse_tpu.metrics import REGISTRY
from lighthouse_tpu.metrics.trace_collector import COLLECTOR
from lighthouse_tpu.network import NetworkService
from lighthouse_tpu.ssz.merkle_proof import build_blob_sidecars
from lighthouse_tpu.store import open_hot_cold
from lighthouse_tpu.store.hot_cold import HotStateSummary
from lighthouse_tpu.store.kv import DBColumn
from lighthouse_tpu.types.chain_spec import minimal_spec
from lighthouse_tpu.types.containers import build_types
from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E
from lighthouse_tpu.utils.slot_clock import ManualSlotClock

S = E.SLOTS_PER_EPOCH


def _harness(store=None):
    bls.set_backend("fake_crypto")
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    return BeaconChainHarness(spec, E, validator_count=16, store=store)


def _counter(name):
    return REGISTRY.counter(name).value()


def _stored(chain, root):
    """("full" | "summary" | None) for a state root in the hot DB."""
    if chain.store.hot.get(DBColumn.BEACON_STATE, root) is not None:
        return "full"
    if chain.store.hot.get(DBColumn.HOT_STATE_SUMMARY, root) is not None:
        return "summary"
    return None


def _state_root(chain, block_root):
    return bytes(chain._blocks_by_root[block_root].message.state_root)


def test_summary_below_boundary_full_state_at_boundary():
    h = _harness()
    chain = h.chain
    summaries = _counter("store_hot_state_summaries_total")
    full = _counter("store_hot_states_full_total")
    roots = h.extend_chain(S - 1, attest=False)  # slots 1 .. S-1
    for root in roots:
        assert _stored(chain, _state_root(chain, root)) == "summary"
    assert _counter("store_hot_state_summaries_total") - summaries == S - 1
    assert _counter("store_hot_states_full_total") == full
    (boundary,) = h.extend_chain(1, attest=False)  # slot S
    assert _stored(chain, _state_root(chain, boundary)) == "full"
    assert _counter("store_hot_states_full_total") - full == 1
    # the summary names its block and the boundary state it replays from
    (after,) = h.extend_chain(1, attest=False)
    summary = chain.store.get_hot_state_summary(_state_root(chain, after))
    assert summary == HotStateSummary(S + 1, after, _state_root(chain, boundary))


def test_summarized_state_replays_to_its_root():
    h = _harness()
    chain = h.chain
    roots = h.extend_chain(S + 3)
    target = roots[-2]  # slot S + 2, replayed from the boundary state at S
    state_root = _state_root(chain, target)
    assert _stored(chain, state_root) == "summary"
    assert chain.store.get_state(state_root) is None  # not stored in full
    got = chain.state_by_state_root(state_root)
    assert got.slot == S + 2 and got.hash_tree_root() == state_root
    # the snapshot cache no longer holds it: the chain replays as well
    chain._states.pop(target)
    st = chain.state_for_block_root(target)
    assert st.hash_tree_root() == state_root


def test_skipped_boundary_state_is_stored_in_full():
    h = _harness()
    chain = h.chain
    h.extend_chain(S - 1, attest=False)  # head at slot S-1
    chain.state_advance_cache.invalidate(chain.head_root)
    root, _ = h.add_block_at_slot(S + 1)  # slot S has no block
    post = chain._states[root]
    boundary_root = bytes(post.state_roots[S % len(post.state_roots)])
    assert _stored(chain, boundary_root) == "full"
    assert chain.store.get_state(boundary_root).slot == S
    summary = chain.store.get_hot_state_summary(_state_root(chain, root))
    assert summary.epoch_boundary_state_root == boundary_root
    chain._states.pop(root)
    assert chain.state_for_block_root(root).hash_tree_root() == _state_root(chain, root)


def test_deleting_a_summarized_state_deletes_its_summary():
    h = _harness()
    chain = h.chain
    (root,) = h.extend_chain(1, attest=False)
    state_root = _state_root(chain, root)
    assert _stored(chain, state_root) == "summary"
    chain.store.delete_state(state_root)
    assert _stored(chain, state_root) is None
    assert chain.state_by_state_root(state_root) is None


def test_restart_from_store_with_summaries(tmp_path):
    path = str(tmp_path / "db")
    h = _harness(store=open_hot_cold(path, "sqlite"))
    chain = h.chain
    roots = h.extend_chain(2 * S + 3)
    assert _stored(chain, _state_root(chain, roots[-1])) == "summary"
    clock = ManualSlotClock(
        genesis_time=HARNESS_GENESIS_TIME, seconds_per_slot=h.spec.seconds_per_slot
    )
    clock.set_slot(int(chain.head_state.slot))
    chain2 = BeaconChain.from_store(open_hot_cold(path, "sqlite"), h.spec, E, clock)
    assert chain2.head_root == chain.head_root
    head_state_root = _state_root(chain, chain.head_root)
    assert chain2.store.get_hot_state_summary(head_state_root) is not None
    # a summarized state of the restarted store replays to its root
    mid = roots[S + 2]
    assert chain2.state_by_state_root(_state_root(chain, mid)).hash_tree_root() == _state_root(
        chain, mid
    )


def test_migration_deletes_summaries_below_the_split():
    h = _harness()
    h.extend_chain(5 * S)
    chain = h.chain
    split = chain.store.split_slot
    assert h.finalized_epoch >= 2 and split > 0
    store = chain.store
    summaries = [
        HotStateSummary.from_bytes(store.hot.get(DBColumn.HOT_STATE_SUMMARY, k))
        for k in store.hot.keys(DBColumn.HOT_STATE_SUMMARY)
    ]
    assert summaries and all(s.slot >= split for s in summaries)
    finalized_state_root = _state_root(chain, bytes(chain.finalized_checkpoint.root))
    for root in store.hot.keys(DBColumn.BEACON_STATE):
        st = store.get_state(root)
        assert st.slot >= split or root == finalized_state_root


def test_import_spans_nest_under_block_import():
    h = _harness()
    h.extend_chain(3)
    imports = [r for r in COLLECTOR.recent(limit=64) if r.name == "block_import"]
    assert imports
    root = imports[0]
    children = [c.name for c in root.children]
    for name in ("state_transition", "fork_choice_on_block", "import_store_write",
                 "import_head_update"):
        assert name in children, children
    (transition,) = [c for c in root.children if c.name == "state_transition"]
    inner = [c.name for c in transition.children]
    assert "signature_batch_verify" in inner and "import_state_root" in inner


def _blob(seed):
    rng = random.Random(seed)
    return b"".join(
        rng.randrange(FR_MODULUS).to_bytes(32, "big") for _ in range(E.FIELD_ELEMENTS_PER_BLOB)
    )


def test_gossip_sidecars_count_one_blob_verification_each():
    T = build_types(E)
    kzg = Kzg(TrustedSetup.insecure_dev(E.FIELD_ELEMENTS_PER_BLOB))
    h = _harness()
    h.chain.data_availability_checker.kzg = kzg
    blobs = [_blob(30 + i) for i in range(3)]
    body = T.BeaconBlockBodyDeneb(
        blob_kzg_commitments=[kzg.blob_to_kzg_commitment(b) for b in blobs]
    )
    signed = T.SignedBeaconBlockDeneb(
        message=T.BeaconBlockDeneb(slot=5, proposer_index=0, body=body),
        signature=b"\x00" * 96,
    )
    sidecars = build_blob_sidecars(signed, blobs, kzg, E)
    net = NetworkService(h.chain).start()
    try:
        before = _counter("kzg_blob_verifications_total")
        for sc in sidecars:
            net.gossip._deliver(net.topic_blob_sidecar, sc.serialize(), "peer")
        assert net.processor.drain()
    finally:
        net.stop()
    assert _counter("kzg_blob_verifications_total") - before == len(sidecars)
    checks = [r for r in COLLECTOR.recent(limit=64) if r.name == "blob_sidecar_verify"]
    assert len(checks) >= len(sidecars)
    for check in checks[: len(sidecars)]:
        assert [c.name for c in check.children] == ["blob_header_signature", "blob_kzg_verify"]


@pytest.mark.parametrize("slot", [S - 1, S, S + 1])
def test_summary_bytes_roundtrip(slot):
    s = HotStateSummary(slot, b"\x01" * 32, b"\x02" * 32)
    assert HotStateSummary.from_bytes(s.to_bytes()) == s


def test_late_epoch_start_votes_index_on_the_heads_state(monkeypatch):
    """Votes cast before a late epoch-start block name its parent as their
    target; they share the head's shuffling (same decision root), so
    they are indexed on the head's state and run no epoch transition,
    however many arrive."""
    from lighthouse_tpu.state_processing import per_slot

    h = _harness()
    h.extend_chain(S - 1, attest=False)
    early = h.make_unaggregated_attestations(S, h.chain.head_root)
    h.add_block_at_slot(S)  # the epoch's first block, arriving late
    assert bytes(early[0].data.target.root) != bytes(h.chain.head_root)
    transitions = []
    real = per_slot.process_epoch
    monkeypatch.setattr(
        per_slot, "process_epoch", lambda *a: (transitions.append(1), real(*a))[1]
    )
    results = h.chain.process_attestation_batch(early)
    assert len(results) == len(early) > 1
    assert not [r for r in results if isinstance(r, Exception)]
    assert transitions == []


def test_fork_with_its_own_decision_root_is_indexed_on_its_targets_state(monkeypatch):
    """An attestation whose target lies on a fork that left the head's
    chain before its epoch's decision slot is indexed on the target's
    state at the target epoch, built once per (epoch, decision root)."""
    from types import SimpleNamespace

    from lighthouse_tpu.state_processing.accessors import get_block_root_at_slot

    main = _harness()
    main.extend_chain(2 * S + 1)
    fork = _harness()
    fork_roots = [fork.add_block_at_slot(1)[1], fork.add_block_at_slot(2 * S)[1]]
    chain = main.chain
    for signed in fork_roots:
        if not chain.fork_choice.contains_block(signed.message.hash_tree_root()):
            chain.process_block(signed)
    first, boundary = (bytes(s.message.hash_tree_root()) for s in fork_roots)
    assert chain.head_state.slot == 2 * S + 1
    verifier = chain.attestation_verifier
    head_target = SimpleNamespace(
        target=SimpleNamespace(epoch=2, root=get_block_root_at_slot(chain.head_state, 2 * S, E))
    )
    assert verifier._indexing_state(head_target) is chain.head_state
    loads = []
    real = chain.state_for_block_root
    monkeypatch.setattr(chain, "state_for_block_root", lambda r: (loads.append(r), real(r))[1])
    state = verifier._indexing_state(SimpleNamespace(target=SimpleNamespace(epoch=2, root=boundary)))
    assert state is not chain.head_state and state.slot == 2 * S
    # the fork's decision block (last slot of epoch 0) is its slot-1 block
    assert bytes(get_block_root_at_slot(state, S - 1, E)) == first
    # another target with the same decision root reuses that state
    again = verifier._indexing_state(SimpleNamespace(target=SimpleNamespace(epoch=2, root=first)))
    assert again is state and loads == [boundary]
