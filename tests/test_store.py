"""HotColdDB: fork-tagged SSZ persistence + schema versioning.

Mirrors beacon_node/store tests: states/blocks round-trip as SSZ bytes
across forks, schema mismatches are detected at open
(hot_cold_store.rs:50-55, lib.rs CURRENT_SCHEMA_VERSION)."""

from dataclasses import replace

import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.state_processing import interop_genesis_state, per_slot_processing
from lighthouse_tpu.store import HotColdDB, MemoryStore
from lighthouse_tpu.store.hot_cold import (
    CURRENT_SCHEMA_VERSION,
    SCHEMA_VERSION_KEY,
    SchemaVersionError,
)
from lighthouse_tpu.store.kv import DBColumn, SqliteStore
from lighthouse_tpu.types.chain_spec import minimal_spec
from lighthouse_tpu.types.containers import build_types
from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E


def _genesis(spec):
    bls.set_backend("fake_crypto")
    kps = bls.interop_keypairs(8)
    return interop_genesis_state(kps, 1_600_000_000, b"\x42" * 32, spec, E)


def test_state_roundtrips_as_ssz_across_forks():
    types = build_types(E)
    store = HotColdDB(MemoryStore(), types=types)

    # phase0
    spec = minimal_spec()
    st0 = _genesis(spec)
    root0 = st0.hash_tree_root()
    store.put_state(root0, st0)
    raw = store.hot.get(DBColumn.BEACON_STATE, root0)
    assert raw[0] == 0  # phase0 tag
    assert raw[1:] == st0.serialize()  # SSZ bytes, not pickle
    got = store.get_state(root0)
    assert type(got).__name__ == "BeaconState"
    assert got.hash_tree_root() == root0

    # altair state decodes back to the altair variant
    spec_a = replace(minimal_spec(), altair_fork_epoch=0)
    st_a = _genesis(spec_a)
    root_a = st_a.hash_tree_root()
    store.put_state(root_a, st_a)
    got_a = store.get_state(root_a)
    assert type(got_a).__name__ == "BeaconStateAltair"
    assert got_a.hash_tree_root() == root_a
    assert got_a.inactivity_scores == st_a.inactivity_scores


def test_block_roundtrips_fork_tagged():
    types = build_types(E)
    store = HotColdDB(MemoryStore(), types=types)
    tf = types.types_for_fork(types.fork_of_state(_genesis(minimal_spec())))
    block = tf.BeaconBlock(slot=5, proposer_index=3)
    signed = tf.SignedBeaconBlock(message=block, signature=b"\x00" * 96)
    root = block.hash_tree_root()
    store.put_block(root, signed)
    got = store.get_block(root)
    assert got.message.slot == 5
    assert got.message.hash_tree_root() == root


def test_schema_version_mismatch_detected():
    mem = MemoryStore()
    HotColdDB(mem, types=build_types(E))  # stamps v CURRENT
    assert (
        int.from_bytes(mem.get(DBColumn.BEACON_META, SCHEMA_VERSION_KEY), "little")
        == CURRENT_SCHEMA_VERSION
    )
    mem.put(DBColumn.BEACON_META, SCHEMA_VERSION_KEY, (99).to_bytes(8, "little"))
    with pytest.raises(SchemaVersionError):
        HotColdDB(mem, types=build_types(E))


def test_sqlite_store_persists(tmp_path):
    path = str(tmp_path / "db.sqlite")
    types = build_types(E)
    store = HotColdDB(SqliteStore(path), types=types)
    st = _genesis(minimal_spec())
    # an epoch-boundary state is stored in full (other slots as summaries)
    while st.slot < E.SLOTS_PER_EPOCH:
        per_slot_processing(st, minimal_spec(), E)
    root = st.hash_tree_root()
    store.put_state(root, st)
    store.hot.close()

    store2 = HotColdDB(SqliteStore(path), types=types)
    got = store2.get_state(root)
    assert got is not None and got.slot == st.slot
    assert got.hash_tree_root() == root
    store2.hot.close()
