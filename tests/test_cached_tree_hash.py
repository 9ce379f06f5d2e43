"""Incremental tree-hash cache vs full recompute.

Mirrors the reference's cached_tree_hash tests (cache.rs:203-237): dirty
single leaves, growth, shrink-triggered rebuild, and the state-level cache
staying consistent with the from-scratch SSZ root across realistic
mutations and copies."""

import random

import numpy as np
import pytest

from lighthouse_tpu.ssz.cached_tree_hash import (
    BeaconStateHashCache,
    TreeHashCache,
    cached_state_root,
)
from lighthouse_tpu.ssz.merkle import merkleize


def _rand_leaves(rng, n):
    return np.frombuffer(
        bytes(rng.randrange(256) for _ in range(n * 32)), dtype=np.uint8
    ).reshape(n, 32).copy()


def _full_root(leaves: np.ndarray, limit: int) -> bytes:
    return merkleize(leaves.tobytes(), limit=limit)


def test_tree_hash_cache_matches_merkleize():
    rng = random.Random(0)
    limit = 64
    cache = TreeHashCache(limit)
    leaves = _rand_leaves(rng, 10)
    assert cache.update(leaves) == _full_root(leaves, limit)

    # single dirty leaf
    leaves[3] = _rand_leaves(rng, 1)[0]
    assert cache.update(leaves) == _full_root(leaves, limit)

    # growth within the pow2 block
    leaves = np.vstack([leaves, _rand_leaves(rng, 5)])
    assert cache.update(leaves) == _full_root(leaves, limit)

    # growth crossing pow2 (rebuild path)
    leaves = np.vstack([leaves, _rand_leaves(rng, 8)])
    assert cache.update(leaves) == _full_root(leaves, limit)

    # shrink (rebuild path)
    leaves = leaves[:7]
    assert cache.update(leaves) == _full_root(leaves, limit)

    # no-op update
    assert cache.update(leaves) == _full_root(leaves, limit)


def test_tree_hash_cache_empty_and_full():
    cache = TreeHashCache(16)
    empty = np.zeros((0, 32), dtype=np.uint8)
    assert cache.update(empty) == _full_root(empty, 16)
    rng = random.Random(1)
    full = _rand_leaves(rng, 16)
    assert cache.update(full) == _full_root(full, 16)


def test_update_rows_sparse_matches_merkleize():
    """The dirty-index fast path (no diff, no scan) must agree with the
    full-diff path and with from-scratch merkleize across random sparse
    update sequences, including growth within the pow2 envelope."""
    rng = random.Random(7)
    limit = 256
    cache = TreeHashCache(limit)
    n = 21
    leaves = _rand_leaves(rng, n)
    assert cache.update(leaves) == _full_root(leaves, limit)
    for _ in range(30):
        # mutate a few random chunks
        k = rng.randrange(1, 5)
        idx = sorted(rng.sample(range(n), min(k, n)))
        rows = _rand_leaves(rng, len(idx))
        for r, i in enumerate(idx):
            leaves[i] = rows[r]
        # occasional growth within the same pow2 block
        if rng.random() < 0.3 and n < 32:
            grow = _rand_leaves(rng, 1)
            leaves = np.vstack([leaves, grow])
            idx.append(n)
            rows = np.vstack([rows, grow])
            n += 1
        assert cache.can_sparse(n)
        got = cache.update_rows(np.asarray(idx, dtype=np.int64), rows, n)
        assert got == _full_root(leaves, limit)


def test_update_rows_refuses_outside_envelope():
    rng = random.Random(8)
    cache = TreeHashCache(64)
    leaves = _rand_leaves(rng, 8)
    cache.update(leaves)
    # growth crossing the pow2 envelope is NOT sparse-updatable
    assert not cache.can_sparse(9)
    with pytest.raises(ValueError):
        cache.update_rows(np.array([8]), _rand_leaves(rng, 1), 9)
    # neither is shrink
    assert not cache.can_sparse(7)


def test_cache_copy_is_cow_shares_until_first_write():
    """`copy()` must not duplicate the layer arrays; the first dirty
    write un-shares, and both sides stay correct and independent."""
    rng = random.Random(9)
    cache = TreeHashCache(64)
    leaves = _rand_leaves(rng, 32)
    cache.update(leaves)
    dup = cache.copy()
    assert all(
        np.shares_memory(a, b) for a, b in zip(cache.layers, dup.layers)
    )
    mutated = leaves.copy()
    mutated[5] = _rand_leaves(rng, 1)[0]
    assert cache.update(mutated) == _full_root(mutated, 64)
    # the write un-shared: dup's layers are not the mutated arrays
    assert not np.shares_memory(cache.layers[0], dup.layers[0])
    assert dup.update(leaves) == _full_root(leaves, 64)  # unaffected
    # sparse writes un-share too
    dup2 = dup.copy()
    row = _rand_leaves(rng, 1)
    leaves[0] = row[0]
    assert dup.update_rows(np.array([0]), row, 32) == _full_root(leaves, 64)
    assert not np.shares_memory(dup.layers[0], dup2.layers[0])


def test_cache_copy_is_independent():
    rng = random.Random(2)
    cache = TreeHashCache(32)
    leaves = _rand_leaves(rng, 8)
    cache.update(leaves)
    dup = cache.copy()
    mutated = leaves.copy()
    mutated[0] = _rand_leaves(rng, 1)[0]
    assert cache.update(mutated) == _full_root(mutated, 32)
    assert dup.update(leaves) == _full_root(leaves, 32)  # unaffected


# --- state-level ------------------------------------------------------------


def _fresh_root(state) -> bytes:
    """From-scratch root bypassing the instance override."""
    return type(state).hash_tree_root_of(state)


def test_cached_state_root_matches_full():
    from dataclasses import replace

    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.state_processing import interop_genesis_state
    from lighthouse_tpu.types.chain_spec import minimal_spec
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

    bls.set_backend("fake_crypto")
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    kps = bls.interop_keypairs(8)
    state = interop_genesis_state(kps, 1_600_000_000, b"\x42" * 32, spec, E)

    assert state.hash_tree_root() == _fresh_root(state)

    # balance mutation
    state.balances[0] += 12345
    assert state.hash_tree_root() == _fresh_root(state)

    # validator mutation (object-level memo invalidation)
    state.validators[3].slashed = True
    state.validators[3].withdrawable_epoch = 99
    assert state.hash_tree_root() == _fresh_root(state)

    # participation + inactivity churn
    state.current_epoch_participation[2] = 7
    state.inactivity_scores[5] = 42
    assert state.hash_tree_root() == _fresh_root(state)

    # slot-vector rotation
    state.block_roots[1] = b"\x11" * 32
    state.randao_mixes[0] = b"\x22" * 32
    assert state.hash_tree_root() == _fresh_root(state)

    # registry growth
    v = state.validators[0].copy()
    v.pubkey = b"\x05" * 48
    state.validators.append(v)
    state.balances.append(31_000_000_000)
    state.previous_epoch_participation.append(0)
    state.current_epoch_participation.append(0)
    state.inactivity_scores.append(0)
    assert state.hash_tree_root() == _fresh_root(state)

    # copies stay consistent and independent
    dup = state.copy()
    dup.balances[1] += 1
    assert dup.hash_tree_root() == _fresh_root(dup)
    assert state.hash_tree_root() == _fresh_root(state)


def test_cached_root_through_state_transition():
    """The cache must survive per-slot/per-epoch processing (the paths that
    mutate every big field)."""
    from dataclasses import replace

    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.beacon_chain.harness import BeaconChainHarness
    from lighthouse_tpu.types.chain_spec import minimal_spec
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

    bls.set_backend("fake_crypto")
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    h = BeaconChainHarness(spec, E, validator_count=8)
    h.extend_chain(E.SLOTS_PER_EPOCH + 3)
    st = h.chain.head_state
    assert st.hash_tree_root() == _fresh_root(st)


def _persistent_state(n_validators: int, seed: int = 5):
    """An Altair state with a persistent (tree-states) registry of
    `n_validators` cloned-and-varied validators."""
    from dataclasses import replace

    from lighthouse_tpu.beacon_chain.chain import _make_persistent
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.state_processing import interop_genesis_state
    from lighthouse_tpu.types.chain_spec import minimal_spec
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

    bls.set_backend("fake_crypto")
    rng = random.Random(seed)
    spec = replace(minimal_spec(), altair_fork_epoch=0)
    state = interop_genesis_state(
        bls.interop_keypairs(8), 1_600_000_000, b"\x42" * 32, spec, E
    )
    v0 = state.validators[0]
    vs, bal = [], []
    for i in range(n_validators):
        v = v0.copy()
        v.withdrawal_credentials = i.to_bytes(32, "little")
        v.effective_balance = 32_000_000_000 - (i % 7) * 1_000_000_000
        vs.append(v)
        bal.append(30_000_000_000 + rng.randrange(4_000_000_000))
    state.validators = vs
    state.balances = bal
    _make_persistent(state)
    return state


def test_columnar_registry_vs_per_object_roots():
    """Differential fuzz of the tentpole: the columnar batched registry
    path (dirty-index sparse updates + full columnar rebuilds) must be
    bit-identical to the plain per-object SSZ path across randomized
    mutation sequences — append, exit, slash, balance churn, and
    `state.copy()` aliasing."""
    from lighthouse_tpu.ssz.persistent import CONTAINER_BLOCK

    rng = random.Random(13)
    # enough validators for several container blocks (columnar bulk path)
    state = _persistent_state(2 * CONTAINER_BLOCK + 37)
    assert state.hash_tree_root() == _fresh_root(state)

    copies = []
    for step in range(12):
        n = len(state.validators)
        op = rng.randrange(5)
        if op == 0:  # registry append (deposit)
            v = state.validators[rng.randrange(n)].copy()
            v.withdrawal_credentials = rng.randbytes(32)
            state.validators.append(v)
            state.balances.append(32_000_000_000)
        elif op == 1:  # exit
            v = state.validators.mutate(rng.randrange(n))
            v.exit_epoch = rng.randrange(1, 2**32)
            v.withdrawable_epoch = v.exit_epoch + 256
        elif op == 2:  # slash
            v = state.validators.mutate(rng.randrange(n))
            v.slashed = True
            v.effective_balance = 0
        elif op == 3:  # balance churn
            for _ in range(rng.randrange(1, 40)):
                i = rng.randrange(n)
                state.balances[i] = rng.randrange(40_000_000_000)
        else:  # copy aliasing: keep the copy, mutate the original later
            cp = state.copy()
            copies.append((cp, cp.hash_tree_root()))
        root = state.hash_tree_root()
        assert root == _fresh_root(state), f"divergence at step {step} (op {op})"
    # every historical copy still roots to what it rooted before — the
    # CoW layers and structural sharing never leaked mutations backwards
    for c, r in copies:
        assert c.hash_tree_root() == r
        assert r == _fresh_root(c)


def test_mass_churn_takes_rebuild_path_and_matches():
    """Past the rebuild fraction (or a dirty-tracker overflow) the
    registry re-roots through the batched columnar rebuild — same bits."""
    state = _persistent_state(700)
    state.hash_tree_root()
    for i in range(0, 700, 2):  # dirty more than half the registry
        v = state.validators.mutate(i)
        v.effective_balance = 31_000_000_000
    assert state.hash_tree_root() == _fresh_root(state)


def test_registry_list_replacement_falls_back_safely():
    """Assigning a foreign persistent list (token lineage break) must
    full-diff, never trust stale dirty info."""
    from lighthouse_tpu.ssz.persistent import PersistentList

    state = _persistent_state(300)
    state.hash_tree_root()
    # replace balances wholesale with a list whose dirt baseline the
    # committed cache has never seen
    fresh = PersistentList([i * 3 for i in range(311)])
    state.balances = fresh
    assert state.hash_tree_root() == _fresh_root(state)
    # and mutations on the replacement keep working incrementally
    state.balances[7] = 123456
    assert state.hash_tree_root() == _fresh_root(state)


@pytest.mark.perf_smoke
def test_warm_noop_reroot_never_rescans_registry():
    """The dirty-index contract: a no-op warm re-root does ZERO hashing
    and ZERO full-list extractions; a one-balance churn hashes only one
    path (never the 'diff all leaves' scan the old cache paid)."""
    import time

    from lighthouse_tpu.ssz import cached_tree_hash as cth

    state = _persistent_state(3000)
    state.hash_tree_root()  # commit
    before = cth.stats()
    t0 = time.perf_counter()
    state.hash_tree_root()  # no-op re-root
    elapsed = time.perf_counter() - t0
    delta = {k: cth.stats()[k] - before[k] for k in before}
    assert delta["rows_hashed"] == 0, delta
    assert delta["full_extracts"] == 0, delta
    # loose wall bound: a no-op re-root is small-field recompute only
    assert elapsed < 0.25, elapsed

    # one balance write: a single path lift, not a registry scan
    before = cth.stats()
    state.balances[17] = int(state.balances[17]) + 1
    state.hash_tree_root()
    delta = {k: cth.stats()[k] - before[k] for k in before}
    assert delta["full_extracts"] == 0, delta
    assert 0 < delta["rows_hashed"] < 64, delta


def test_altair_and_electra_states_use_cache_and_match_plain_roots():
    """Altair+ states are not subclasses of the phase0 BeaconState, so
    they carry their own cached hash_tree_root hook — roots must equal
    the from-scratch classmethod computation through arbitrary churn."""
    import random
    from dataclasses import replace

    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.state_processing import interop_genesis_state
    from lighthouse_tpu.types.chain_spec import minimal_spec
    from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

    bls.set_backend("fake_crypto")
    rng = random.Random(5)
    for forks in (
        dict(altair_fork_epoch=0),
        dict(
            altair_fork_epoch=0,
            bellatrix_fork_epoch=0,
            capella_fork_epoch=0,
            deneb_fork_epoch=0,
            electra_fork_epoch=0,
        ),
    ):
        spec = replace(minimal_spec(), **forks)
        state = interop_genesis_state(
            bls.interop_keypairs(8), 1_600_000_000, b"\x42" * 32, spec, E
        )
        plain = type(state).hash_tree_root_of(state)
        assert state.hash_tree_root() == plain
        assert "_thc_cache" in state.__dict__  # the cache really engaged
        # churn: balances, validator record, participation, randao
        for _ in range(5):
            i = rng.randrange(len(state.balances))
            state.balances[i] = int(state.balances[i]) + rng.randrange(100)
            v = state.validators[rng.randrange(len(state.validators))]
            v.effective_balance = 31_000_000_000
            state.current_epoch_participation[
                rng.randrange(len(state.current_epoch_participation))
            ] = rng.randrange(8)
            state.randao_mixes[rng.randrange(8)] = rng.randbytes(32)
            assert state.hash_tree_root() == type(state).hash_tree_root_of(state)
        # copies share nothing observable: mutate the copy, original stable
        snap = state.hash_tree_root()
        cp = state.copy()
        cp.balances[0] = 1
        assert cp.hash_tree_root() != snap
        assert state.hash_tree_root() == snap


def _big_leaves(seed: int) -> np.ndarray:
    from lighthouse_tpu.ssz import cached_tree_hash as cth

    rng = np.random.default_rng(seed)
    n = cth._DEVICE_BUILD_THRESHOLD
    return rng.integers(0, 256, size=(n, 32), dtype=np.uint8)


def test_device_tree_build_matches_host_layers(monkeypatch):
    """The device lane (forced on here, on whatever backend runs the
    tests) builds the same layers as the batched host hasher, and counts
    itself."""
    from lighthouse_tpu.ssz import cached_tree_hash as cth

    leaves = _big_leaves(21)
    host = cth._build_layers(leaves)
    monkeypatch.setattr(cth, "_device_tree_enabled", lambda: True)
    before = cth.stats()["device_builds"]
    dev = cth._build_layers(leaves)
    assert cth.stats()["device_builds"] == before + 1
    assert len(dev) == len(host)
    for d, h in zip(dev, host):
        assert np.array_equal(d, h)


def test_device_tree_build_failure_raises(monkeypatch):
    """A device failure in the tree build surfaces; the host hasher never
    quietly takes over."""
    from lighthouse_tpu.ops import sha256
    from lighthouse_tpu.ssz import cached_tree_hash as cth

    def refuse(leaves):
        raise RuntimeError("device tree kernel refused")

    monkeypatch.setattr(cth, "_device_tree_enabled", lambda: True)
    monkeypatch.setattr(sha256, "merkle_tree_levels", refuse)
    rows = cth.stats()["rows_hashed"]
    with pytest.raises(RuntimeError, match="refused"):
        cth._build_layers(_big_leaves(22))
    assert cth.stats()["rows_hashed"] == rows
