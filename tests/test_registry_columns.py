"""Resident columnar registry: differential fuzz vs the per-validator
oracle, copy-aliasing isolation, and the zero-rebuild steady-state guard.

The tentpole contract (registry_columns.py): the resident columns are a
PROVEN mirror of the persistent lists — every epoch transition run over
them must leave the state bit-identical to the retained legacy
per-validator path, under randomized participation, slashings, ejections,
activation churn and `state.copy()` aliasing, across phase0/altair/electra.
"""

import random
from dataclasses import replace

import numpy as np
import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.beacon_chain.chain import _make_persistent
from lighthouse_tpu.state_processing import interop_genesis_state
from lighthouse_tpu.state_processing.per_epoch import process_epoch
from lighthouse_tpu.state_processing.registry_columns import (
    RegistryColumns,
    registry_columns_for,
)
from lighthouse_tpu.types.chain_spec import ForkName, minimal_spec
from lighthouse_tpu.types.eth_spec import MinimalEthSpec as E

FAR = (1 << 64) - 1

_FORK_OVERRIDES = {
    ForkName.PHASE0: {},
    ForkName.ALTAIR: dict(altair_fork_epoch=0),
    ForkName.ELECTRA: dict(
        altair_fork_epoch=0,
        bellatrix_fork_epoch=0,
        capella_fork_epoch=0,
        deneb_fork_epoch=0,
        electra_fork_epoch=0,
    ),
}


def _base_state(fork: ForkName, n: int, seed: int):
    """A boundary-ready state with randomized registry shape: mixed
    activation/exit/slashing status, participation, scores, balances."""
    bls.set_backend("fake_crypto")
    rng = random.Random(seed)
    spec = replace(minimal_spec(), **_FORK_OVERRIDES[fork])
    state = interop_genesis_state(
        bls.interop_keypairs(8), 1_600_000_000, b"\x42" * 32, spec, E
    )
    v0 = state.validators[0]
    vs, bal = [], []
    for i in range(n):
        v = v0.copy()
        v.withdrawal_credentials = bytes([rng.choice([0x00, 0x01, 0x02])]) + (
            i.to_bytes(31, "little")
        )
        v.effective_balance = rng.choice(
            [32_000_000_000, 31_000_000_000, 16_000_000_000]
        )
        if rng.random() < 0.1:  # pending activation (churn fodder)
            v.activation_epoch = FAR
            v.activation_eligibility_epoch = rng.choice([FAR, 0, 1])
        if rng.random() < 0.08:  # exited / exiting
            v.exit_epoch = rng.randrange(1, 12)
            v.withdrawable_epoch = v.exit_epoch + 256
        if rng.random() < 0.06:  # slashed, some at the correlated epoch
            v.slashed = True
            v.withdrawable_epoch = rng.choice(
                [3 + E.EPOCHS_PER_SLASHINGS_VECTOR // 2, 40, 300]
            )
        vs.append(v)
        bal.append(rng.randrange(0, 40_000_000_000))
    state.validators = vs
    state.balances = bal
    if fork >= ForkName.ALTAIR:
        state.previous_epoch_participation = bytearray(
            rng.randrange(8) for _ in range(n)
        )
        state.current_epoch_participation = bytearray(
            rng.randrange(8) for _ in range(n)
        )
        state.inactivity_scores = [rng.randrange(6) for _ in range(n)]
    for s in range(len(state.slashings)):
        state.slashings[s] = rng.randrange(0, 64_000_000_000)
    state.slot = 4 * E.SLOTS_PER_EPOCH - 1
    # a justified past so rewards/finality logic engages
    t = type(state)
    state.finalized_checkpoint = state.finalized_checkpoint.copy()
    state.finalized_checkpoint.epoch = 1
    return state, spec


def _phase0_attestations(state, spec, rng):
    """Seed pending attestations so the phase0 reward components engage."""
    from lighthouse_tpu.state_processing.accessors import (
        get_beacon_committee,
        get_block_root,
        get_previous_epoch,
    )
    from lighthouse_tpu.types.containers import build_types

    t = build_types(E)
    prev = get_previous_epoch(state, E)
    atts = []
    for slot in range(prev * E.SLOTS_PER_EPOCH, (prev + 1) * E.SLOTS_PER_EPOCH):
        committee = get_beacon_committee(state, slot, 0, E)
        bits = [rng.random() < 0.8 for _ in committee]
        data = t.AttestationData(
            slot=slot,
            index=0,
            beacon_block_root=state.block_roots[
                slot % E.SLOTS_PER_HISTORICAL_ROOT
            ],
            source=state.previous_justified_checkpoint,
            target=t.Checkpoint(
                epoch=prev, root=get_block_root(state, prev, E)
            ),
        )
        atts.append(
            t.PendingAttestation(
                aggregation_bits=bits,
                data=data,
                inclusion_delay=rng.randrange(1, E.SLOTS_PER_EPOCH),
                proposer_index=rng.randrange(len(state.validators)),
            )
        )
    state.previous_epoch_attestations = atts


def _state_fingerprint(state):
    """Everything the epoch transition mutates, field by field — compared
    against the oracle run (sharper diagnostics than root equality, and
    independent of the caching machinery under test)."""
    fp = {
        "balances": list(state.balances),
        "validators": [
            (
                v.effective_balance,
                bool(v.slashed),
                v.activation_eligibility_epoch,
                v.activation_epoch,
                v.exit_epoch,
                v.withdrawable_epoch,
            )
            for v in state.validators
        ],
        "checkpoints": (
            state.previous_justified_checkpoint.epoch,
            state.current_justified_checkpoint.epoch,
            state.finalized_checkpoint.epoch,
        ),
        "slashings": list(state.slashings),
    }
    if hasattr(state, "inactivity_scores"):
        fp["scores"] = list(state.inactivity_scores)
        fp["prev_part"] = bytes(state.previous_epoch_participation)
        fp["curr_part"] = bytes(state.current_epoch_participation)
    # the from-scratch SSZ root (bypassing every cache) seals the rest
    fp["root"] = type(state).hash_tree_root_of(state)
    return fp


@pytest.mark.parametrize("fork", [ForkName.PHASE0, ForkName.ALTAIR, ForkName.ELECTRA])
@pytest.mark.parametrize("seed", [11, 12])
def test_resident_epoch_matches_per_validator_oracle(fork, seed):
    """Cross-fork differential fuzz: the resident-columns transition must
    be bit-identical to the legacy per-validator path on an identical
    state, including registry churn (activations, ejections, slashings)
    and balance movement."""
    from lighthouse_tpu.state_processing.epoch_reference import (
        process_epoch_reference,
    )

    rng = random.Random(seed)
    subject, spec = _base_state(fork, 700, seed)
    if fork == ForkName.PHASE0:
        _phase0_attestations(subject, spec, rng)
    legacy = subject.copy()  # plain lists: copies stay plain
    scalar = subject.copy()

    _make_persistent(subject)
    cols = registry_columns_for(subject)
    assert cols is not None
    cols.refresh(subject)

    process_epoch(subject, spec, E)

    # comparator 1: the scalar per-validator spec loops
    process_epoch_reference(scalar, spec, E)
    # comparator 2: the legacy snapshot path, which plain-list states take
    assert registry_columns_for(legacy) is None
    process_epoch(legacy, spec, E)

    got = _state_fingerprint(subject)
    for name, other in (("scalar-oracle", scalar), ("legacy-snapshot", legacy)):
        want = _state_fingerprint(other)
        for key in want:
            assert got[key] == want[key], f"{fork}: '{key}' vs {name} diverged"


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_resident_epochs_survive_copy_aliasing_and_churn(seed):
    """Divergent copies must never share dirty writes: interleave
    randomized mutations, epoch transitions and state copies; every
    branch's cached root must equal its own from-scratch root."""
    rng = random.Random(seed)
    state, spec = _base_state(ForkName.ALTAIR, 520, seed)
    _make_persistent(state)
    registry_columns_for(state).refresh(state)
    branches = []
    for step in range(6):
        n = len(state.validators)
        op = rng.randrange(5)
        if op == 0:  # deposit-ish: append a validator
            v = state.validators[rng.randrange(n)].copy()
            v.withdrawal_credentials = rng.randbytes(32)
            state.validators.append(v)
            state.balances.append(32_000_000_000)
            state.inactivity_scores.append(0)
            state.previous_epoch_participation.append(0)
            state.current_epoch_participation.append(0)
        elif op == 1:  # slashing-ish mutation through the CoW discipline
            v = state.validators.mutate(rng.randrange(n))
            v.slashed = True
            v.withdrawable_epoch = 4 + E.EPOCHS_PER_SLASHINGS_VECTOR // 2
        elif op == 2:  # balance churn through the object path
            for _ in range(rng.randrange(1, 50)):
                state.balances[rng.randrange(n)] = rng.randrange(
                    40_000_000_000
                )
        elif op == 3:  # a full epoch transition on the resident path
            state.slot = (
                (state.slot // E.SLOTS_PER_EPOCH) + 1
            ) * E.SLOTS_PER_EPOCH - 1
            process_epoch(state, spec, E)
        else:  # branch: keep a copy, later mutate the original
            cp = state.copy()
            branches.append((cp, cp.hash_tree_root()))
        assert state.hash_tree_root() == type(state).hash_tree_root_of(state), (
            f"step {step} (op {op})"
        )
    for cp, root in branches:
        assert cp.hash_tree_root() == root
        assert root == type(cp).hash_tree_root_of(cp)


def test_validator_root_rows_match_per_object_ssz():
    """The columns' leaf-matrix element roots are bit-identical to
    per-object SSZ Merkleization for every validator shape in the fuzz
    registry (slashed/exited/pending/compounding)."""
    state, _ = _base_state(ForkName.ALTAIR, 400, 31)
    _make_persistent(state)
    cols = registry_columns_for(state)
    cols.refresh(state)
    rows = cols.validator_root_rows(None)
    for i, v in enumerate(state.validators):
        assert rows[i].tobytes() == type(v).hash_tree_root_of(v), i
    # sparse gather agrees too
    idx = np.array([0, 7, 399], dtype=np.int64)
    sparse = cols.validator_root_rows(idx)
    for r, i in enumerate(idx):
        assert sparse[r].tobytes() == rows[int(i)].tobytes()


def test_phase0_vectorized_deltas_match_reference_oracle():
    """Satellite: the vectorized phase0 get_attestation_deltas /
    process_slashings must equal the retained loop oracles."""
    from lighthouse_tpu.state_processing.per_epoch import (
        get_attestation_deltas,
        get_attestation_deltas_reference,
        process_slashings,
        process_slashings_reference,
    )

    for seed in (41, 42):
        rng = random.Random(seed)
        state, spec = _base_state(ForkName.PHASE0, 360, seed)
        _phase0_attestations(state, spec, rng)
        rewards, penalties = get_attestation_deltas(state, E)
        ref_r, ref_p = get_attestation_deltas_reference(state, E)
        assert [int(x) for x in rewards] == ref_r
        assert [int(x) for x in penalties] == ref_p

        # slashings: vectorized bulk writeback vs per-index loop
        a = state.copy()
        b = state.copy()
        process_slashings(a, E)
        process_slashings_reference(b, E)
        assert list(a.balances) == list(b.balances)


def test_shuffle_list_matches_compute_shuffled_index_elementwise():
    """Satellite: the batched one-call-per-round shuffle must equal the
    scalar spec algorithm element-wise (shuffle_list semantics:
    out[i] == values[compute_shuffled_index(i)])."""
    from lighthouse_tpu.state_processing.shuffle import (
        _shuffled_positions,
        compute_shuffled_index,
        shuffle_list,
    )

    rng = random.Random(5)
    for n in (2, 7, 255, 256, 257, 800):
        seed = rng.randbytes(32)
        rounds = E.SHUFFLE_ROUND_COUNT
        perm = _shuffled_positions(n, seed, rounds)
        values = list(range(1000, 1000 + n))
        shuffled = shuffle_list(values, seed, rounds)
        for i in range(n):
            want = compute_shuffled_index(i, n, seed, rounds)
            assert int(perm[i]) == want, (n, i)
            assert shuffled[i] == values[want], (n, i)


def test_committee_cache_slices_match_shuffled_permutation():
    """Committee assignment is one shuffled-permutation slice: committees
    partition the active set exactly, with plain-int members."""
    from lighthouse_tpu.state_processing.accessors import (
        CommitteeCache,
        get_active_validator_indices,
        get_current_epoch,
    )

    state, _ = _base_state(ForkName.ALTAIR, 640, 51)
    _make_persistent(state)
    epoch = get_current_epoch(state, E)
    cc = CommitteeCache.build(state, epoch, E)
    active = set(get_active_validator_indices(state, epoch))
    seen = []
    for slot in range(
        epoch * E.SLOTS_PER_EPOCH, (epoch + 1) * E.SLOTS_PER_EPOCH
    ):
        for index in range(cc.committees_per_slot):
            members = cc.committee(slot, index)
            assert all(type(m) is int for m in members)
            seen.extend(members)
    assert len(seen) == len(active)
    assert set(seen) == active


@pytest.mark.perf_smoke
def test_steady_state_epoch_rebuilds_zero_columns():
    """The residency guarantee: after the one-time warm-up, epoch
    transitions must perform ZERO full column rebuilds (the counter
    stays flat) and the columns channel must stay on the sparse path."""
    from lighthouse_tpu.metrics import REGISTRY

    state, spec = _base_state(ForkName.ALTAIR, 3000, 61)
    _make_persistent(state)
    registry_columns_for(state).refresh(state)  # one-time warm-up

    counter = REGISTRY.counter("registry_columns_rebuilds_total")
    before = dict(counter.values())
    for _ in range(3):
        # a block's worth of inter-epoch churn, then the transition
        rng = random.Random(int(state.slot))
        for _ in range(64):
            i = rng.randrange(len(state.balances))
            state.balances[i] = int(state.balances[i]) + 1
        state.validators.mutate(rng.randrange(len(state.validators))).slashed = True
        state.slot = (
            (state.slot // E.SLOTS_PER_EPOCH) + 1
        ) * E.SLOTS_PER_EPOCH - 1
        process_epoch(state, spec, E)
        state.hash_tree_root()
    after = dict(counter.values())
    assert after == before, f"columns rebuilt in steady state: {before} -> {after}"


def test_appended_zero_pubkey_validator_roots_correctly():
    """Regression: a validator appended with an all-zero pubkey must get
    the true subtree root sha256(64 zero bytes) — the sparse refresh's
    pubkey diff runs against zero-extended columns, so appended rows
    must be hashed unconditionally."""
    state, _ = _base_state(ForkName.ALTAIR, 300, 81)
    _make_persistent(state)
    cols = registry_columns_for(state)
    cols.refresh(state)
    v = state.validators[0].copy()
    v.pubkey = b"\x00" * 48
    state.validators.append(v)
    state.balances.append(1)
    state.inactivity_scores.append(0)
    state.previous_epoch_participation.append(0)
    state.current_epoch_participation.append(0)
    cols.refresh(state)
    rows = cols.validator_root_rows(np.array([300], dtype=np.int64))
    assert rows[0].tobytes() == type(v).hash_tree_root_of(
        state.validators[300]
    )
    assert state.hash_tree_root() == type(state).hash_tree_root_of(state)


def test_columns_detach_on_plain_list_replacement():
    """Wholesale field replacement with a plain list breaks residency
    safely: the columns detach and the state keeps rooting correctly."""
    state, spec = _base_state(ForkName.ALTAIR, 300, 71)
    _make_persistent(state)
    registry_columns_for(state).refresh(state)
    state.hash_tree_root()
    state.balances = [1_000_000_000] * len(state.validators)  # plain again
    assert registry_columns_for(state) is None
    assert "_registry_columns" not in state.__dict__
    assert state.hash_tree_root() == type(state).hash_tree_root_of(state)
    state.slot = ((state.slot // E.SLOTS_PER_EPOCH) + 1) * E.SLOTS_PER_EPOCH - 1
    process_epoch(state, spec, E)  # legacy path, still correct
    assert state.hash_tree_root() == type(state).hash_tree_root_of(state)
