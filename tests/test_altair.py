"""Altair→Deneb state transition tests: fork upgrades, participation-flag
epoch processing (vectorized vs scalar-spec parity), sync committees,
withdrawals.

The parity tests re-implement the spec formulas index-by-index in plain
Python and require the vectorized numpy sweep to match exactly — the same
oracle discipline the device kernels use against host implementations.
"""

import random
from dataclasses import replace

import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.state_processing import per_slot_processing
from lighthouse_tpu.state_processing.altair import (
    PARTICIPATION_FLAG_WEIGHTS,
    TIMELY_HEAD_FLAG_INDEX,
    TIMELY_TARGET_FLAG_INDEX,
    WEIGHT_DENOMINATOR,
    get_base_reward_per_increment,
    process_inactivity_updates,
    process_rewards_and_penalties_altair,
)
from lighthouse_tpu.state_processing.capella import (
    get_expected_withdrawals,
)
from lighthouse_tpu.state_processing.genesis import interop_genesis_state
from lighthouse_tpu.state_processing.per_epoch import get_finality_delay
from lighthouse_tpu.types.chain_spec import ForkName, minimal_spec
from lighthouse_tpu.types.containers import build_types
from lighthouse_tpu.types.eth_spec import MinimalEthSpec

E = MinimalEthSpec
T = build_types(E)


@pytest.fixture(autouse=True)
def fake_crypto():
    old = bls.backend_name()
    bls.set_backend("fake_crypto")
    yield
    bls.set_backend(old)


def altair_spec(**forks):
    base = dict(altair_fork_epoch=0)
    base.update(forks)
    return replace(minimal_spec(), **base)


def make_altair_state(n=16, spec=None):
    spec = spec or altair_spec()
    kps = bls.interop_keypairs(n)
    return interop_genesis_state(kps, 1_600_000_000, b"\x42" * 32, spec, E), spec


def randomize_participation(state, rng):
    n = len(state.validators)
    state.previous_epoch_participation = bytearray(
        rng.randrange(8) for _ in range(n)
    )
    state.current_epoch_participation = bytearray(
        rng.randrange(8) for _ in range(n)
    )
    state.inactivity_scores = [rng.randrange(100) for _ in range(n)]
    for i in range(n):
        state.balances[i] = 31_000_000_000 + rng.randrange(2_000_000_000)
    # a couple of slashed validators
    state.validators[1].slashed = True
    state.validators[1].withdrawable_epoch = 9999


# --- upgrades ---------------------------------------------------------------


def test_genesis_at_fork_starts_in_that_fork():
    for fork, cls_name in [
        (dict(altair_fork_epoch=0), "BeaconStateAltair"),
        (
            dict(altair_fork_epoch=0, bellatrix_fork_epoch=0),
            "BeaconStateBellatrix",
        ),
        (
            dict(
                altair_fork_epoch=0,
                bellatrix_fork_epoch=0,
                capella_fork_epoch=0,
                deneb_fork_epoch=0,
            ),
            "BeaconStateDeneb",
        ),
    ]:
        state, _ = make_altair_state(8, altair_spec(**fork))
        assert type(state).__name__ == cls_name


def test_upgrade_preserves_registry_and_sets_new_fields():
    spec = replace(minimal_spec(), altair_fork_epoch=1)
    kps = bls.interop_keypairs(8)
    state = interop_genesis_state(kps, 1_600_000_000, b"\x42" * 32, spec, E)
    assert type(state).__name__ == "BeaconState"
    pre_validators = [v.pubkey for v in state.validators]
    pre_balances = list(state.balances)
    while state.slot < E.SLOTS_PER_EPOCH:
        per_slot_processing(state, spec, E)
    assert type(state).__name__ == "BeaconStateAltair"
    assert [v.pubkey for v in state.validators] == pre_validators
    assert len(state.inactivity_scores) == 8
    assert len(state.previous_epoch_participation) == 8
    assert state.fork.current_version == spec.altair_fork_version
    assert state.fork.previous_version == spec.genesis_fork_version
    assert len(state.current_sync_committee.pubkeys) == E.SYNC_COMMITTEE_SIZE
    # registry preserved up to rewards/penalties applied at the boundary
    assert len(state.balances) == len(pre_balances)
    # state still hashes and round-trips
    root = state.hash_tree_root()
    data = type(state).serialize_value(state)
    back = type(state).deserialize(data)
    assert type(state).hash_tree_root_of(back) == root


def test_upgrade_chain_through_deneb():
    spec = replace(
        minimal_spec(),
        altair_fork_epoch=1,
        bellatrix_fork_epoch=2,
        capella_fork_epoch=2,
        deneb_fork_epoch=3,
    )
    kps = bls.interop_keypairs(8)
    state = interop_genesis_state(kps, 1_600_000_000, b"\x42" * 32, spec, E)
    while state.slot < 3 * E.SLOTS_PER_EPOCH:
        per_slot_processing(state, spec, E)
    assert type(state).__name__ == "BeaconStateDeneb"
    hdr = state.latest_execution_payload_header
    assert hdr.blob_gas_used == 0
    assert state.next_withdrawal_index == 0
    state.hash_tree_root()


# --- vectorized epoch processing parity ------------------------------------


def _scalar_flag_deltas(state, spec, E, fork):
    """Straight-from-spec per-index implementation (altair/beacon-chain.md
    get_flag_index_deltas + get_inactivity_penalty_deltas)."""
    from lighthouse_tpu.state_processing.accessors import (
        get_current_epoch,
        get_previous_epoch,
        is_active_validator,
    )

    n = len(state.validators)
    current = get_current_epoch(state, E)
    previous = get_previous_epoch(state, E)
    in_leak = get_finality_delay(state, E) > E.MIN_EPOCHS_TO_INACTIVITY_PENALTY
    rewards = [0] * n
    penalties = [0] * n

    def active_prev(v):
        return is_active_validator(v, previous)

    def eligible(i):
        v = state.validators[i]
        return active_prev(v) or (
            v.slashed and previous + 1 < v.withdrawable_epoch
        )

    total_active = max(
        sum(
            v.effective_balance
            for v in state.validators
            if is_active_validator(v, current)
        ),
        E.EFFECTIVE_BALANCE_INCREMENT,
    )
    from lighthouse_tpu.state_processing.accessors import int_sqrt

    brpi = E.EFFECTIVE_BALANCE_INCREMENT * E.BASE_REWARD_FACTOR // int_sqrt(
        total_active
    )
    tai = total_active // E.EFFECTIVE_BALANCE_INCREMENT

    for flag_index, weight in enumerate(PARTICIPATION_FLAG_WEIGHTS):
        unslashed = [
            i
            for i in range(n)
            if active_prev(state.validators[i])
            and not state.validators[i].slashed
            and state.previous_epoch_participation[i] & (1 << flag_index)
        ]
        upb = max(
            sum(state.validators[i].effective_balance for i in unslashed),
            E.EFFECTIVE_BALANCE_INCREMENT,
        )
        upi = upb // E.EFFECTIVE_BALANCE_INCREMENT
        uset = set(unslashed)
        for i in range(n):
            if not eligible(i):
                continue
            base_reward = (
                state.validators[i].effective_balance
                // E.EFFECTIVE_BALANCE_INCREMENT
                * brpi
            )
            if i in uset:
                if not in_leak:
                    rewards[i] += (
                        base_reward * weight * upi // (tai * WEIGHT_DENOMINATOR)
                    )
            elif flag_index != TIMELY_HEAD_FLAG_INDEX:
                penalties[i] += base_reward * weight // WEIGHT_DENOMINATOR

    quotient = (
        E.INACTIVITY_PENALTY_QUOTIENT_BELLATRIX
        if fork >= ForkName.BELLATRIX
        else E.INACTIVITY_PENALTY_QUOTIENT_ALTAIR
    )
    for i in range(n):
        if not eligible(i):
            continue
        v = state.validators[i]
        participated = (
            active_prev(v)
            and not v.slashed
            and state.previous_epoch_participation[i]
            & (1 << TIMELY_TARGET_FLAG_INDEX)
        )
        if not participated:
            penalty_numerator = (
                v.effective_balance * state.inactivity_scores[i]
            )
            penalties[i] += penalty_numerator // (
                spec.inactivity_score_bias * quotient
            )
    return rewards, penalties


@pytest.mark.parametrize("fork", [ForkName.ALTAIR, ForkName.BELLATRIX])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_rewards_and_penalties_vectorized_matches_scalar(fork, seed):
    rng = random.Random(seed)
    state, spec = make_altair_state(24)
    # advance past epoch 1 so previous-epoch logic is live
    while state.slot < 2 * E.SLOTS_PER_EPOCH + 3:
        per_slot_processing(state, spec, E)
    randomize_participation(state, rng)

    expected = list(state.balances)
    rewards, penalties = _scalar_flag_deltas(state, spec, E, fork)
    for i in range(len(expected)):
        expected[i] = max(expected[i] + rewards[i] - penalties[i], 0)

    process_rewards_and_penalties_altair(state, spec, E, fork)
    assert list(state.balances) == expected


@pytest.mark.parametrize("seed", [5, 6])
def test_inactivity_updates_vectorized_matches_scalar(seed):
    from lighthouse_tpu.state_processing.accessors import (
        get_previous_epoch,
        is_active_validator,
    )

    rng = random.Random(seed)
    state, spec = make_altair_state(24)
    while state.slot < 2 * E.SLOTS_PER_EPOCH + 3:
        per_slot_processing(state, spec, E)
    randomize_participation(state, rng)

    previous = get_previous_epoch(state, E)
    in_leak = get_finality_delay(state, E) > E.MIN_EPOCHS_TO_INACTIVITY_PENALTY
    expected = list(state.inactivity_scores)
    for i, v in enumerate(state.validators):
        eligible = is_active_validator(v, previous) or (
            v.slashed and previous + 1 < v.withdrawable_epoch
        )
        if not eligible:
            continue
        participated = (
            is_active_validator(v, previous)
            and not v.slashed
            and state.previous_epoch_participation[i]
            & (1 << TIMELY_TARGET_FLAG_INDEX)
        )
        if participated:
            expected[i] -= min(1, expected[i])
        else:
            expected[i] += spec.inactivity_score_bias
        if not in_leak:
            expected[i] -= min(
                spec.inactivity_score_recovery_rate, expected[i]
            )

    process_inactivity_updates(state, spec, E)
    assert list(state.inactivity_scores) == expected


# --- sync committee ---------------------------------------------------------


def test_sync_committee_membership_is_registry_subset():
    state, _ = make_altair_state(16)
    registry = {bytes(v.pubkey) for v in state.validators}
    for pk in state.current_sync_committee.pubkeys:
        assert bytes(pk) in registry


def test_sync_aggregate_rewards_flow():
    from lighthouse_tpu.state_processing.altair import process_sync_aggregate
    from lighthouse_tpu.state_processing.per_block import ConsensusContext

    state, spec = make_altair_state(16)
    per_slot_processing(state, spec, E)
    ctxt = ConsensusContext(state.slot)
    pre_balances = list(state.balances)
    bits = [True] * E.SYNC_COMMITTEE_SIZE
    aggregate = T.SyncAggregate(
        sync_committee_bits=bits,
        sync_committee_signature=bls.INFINITY_SIGNATURE,
    )
    process_sync_aggregate(state, aggregate, spec, E, False, ctxt)
    brpi = get_base_reward_per_increment(state, E)
    assert brpi > 0
    assert sum(state.balances) > sum(pre_balances)  # full participation pays

    # all-empty: everyone in the committee is penalized
    state2, _ = make_altair_state(16)
    per_slot_processing(state2, spec, E)
    pre2 = sum(state2.balances)
    empty = T.SyncAggregate(
        sync_committee_bits=[False] * E.SYNC_COMMITTEE_SIZE,
        sync_committee_signature=bls.INFINITY_SIGNATURE,
    )
    process_sync_aggregate(state2, empty, spec, E, False, ctxt)
    assert sum(state2.balances) < pre2


# --- capella withdrawals ----------------------------------------------------


def test_expected_withdrawals_sweep():
    spec = altair_spec(
        bellatrix_fork_epoch=0, capella_fork_epoch=0
    )
    state, _ = make_altair_state(8, spec)
    assert type(state).__name__ == "BeaconStateCapella"
    # give validator 2 an eth1 credential + excess balance (partial)
    v = state.validators[2]
    v.withdrawal_credentials = b"\x01" + b"\x00" * 11 + b"\xaa" * 20
    state.balances[2] = E.MAX_EFFECTIVE_BALANCE + 7
    # validator 3: fully withdrawable
    v3 = state.validators[3]
    v3.withdrawal_credentials = b"\x01" + b"\x00" * 11 + b"\xbb" * 20
    v3.withdrawable_epoch = 0
    ws = get_expected_withdrawals(state, E)
    assert [w.validator_index for w in ws] == [2, 3]
    assert ws[0].amount == 7
    assert ws[1].amount == state.balances[3]
    assert bytes(ws[1].address) == b"\xbb" * 20


def test_withdrawals_applied_in_block_flow():
    from lighthouse_tpu.state_processing.capella import process_withdrawals

    spec = altair_spec(bellatrix_fork_epoch=0, capella_fork_epoch=0)
    state, _ = make_altair_state(8, spec)
    v = state.validators[4]
    v.withdrawal_credentials = b"\x01" + b"\x00" * 11 + b"\xcc" * 20
    state.balances[4] = E.MAX_EFFECTIVE_BALANCE + 123
    expected = get_expected_withdrawals(state, E)
    payload = T.ExecutionPayloadCapella(withdrawals=expected)
    process_withdrawals(state, payload, E)
    assert state.balances[4] == E.MAX_EFFECTIVE_BALANCE
    assert state.next_withdrawal_index == 1

    # wrong withdrawals must be rejected
    from lighthouse_tpu.state_processing.per_block import BlockProcessingError

    state2, _ = make_altair_state(8, spec)
    state2.validators[4].withdrawal_credentials = (
        b"\x01" + b"\x00" * 11 + b"\xcc" * 20
    )
    state2.balances[4] = E.MAX_EFFECTIVE_BALANCE + 123
    bad = T.ExecutionPayloadCapella(withdrawals=[])
    with pytest.raises(BlockProcessingError):
        process_withdrawals(state2, bad, E)


# --- full-chain cross-fork runs --------------------------------------------


def test_chain_crosses_all_forks_and_finalizes():
    """Harness drives one block per slot through phase0→altair→bellatrix→
    capella→deneb and finality keeps advancing (the reference's
    fork-transition beacon-chain tests)."""
    from lighthouse_tpu.beacon_chain.harness import BeaconChainHarness

    spec = replace(
        minimal_spec(),
        altair_fork_epoch=1,
        bellatrix_fork_epoch=2,
        capella_fork_epoch=3,
        deneb_fork_epoch=4,
    )
    h = BeaconChainHarness(spec, E, validator_count=16)
    h.extend_chain(6 * E.SLOTS_PER_EPOCH)
    st = h.chain.head_state
    assert type(st).__name__ == "BeaconStateDeneb"
    assert h.finalized_epoch >= 4
    # participation-flag bookkeeping stayed registry-shaped
    assert len(st.previous_epoch_participation) == len(st.validators)
    assert len(st.inactivity_scores) == len(st.validators)


@pytest.mark.slow
def test_chain_altair_real_crypto():
    """Sync-aggregate + attestation signatures verify under the real BLS
    backend across the altair boundary."""
    from lighthouse_tpu.beacon_chain.harness import BeaconChainHarness

    bls.set_backend("host")
    try:
        spec = replace(minimal_spec(), altair_fork_epoch=1)
        h = BeaconChainHarness(spec, E, validator_count=8)
        h.extend_chain(3 * E.SLOTS_PER_EPOCH + 2)
        assert type(h.chain.head_state).__name__ == "BeaconStateAltair"
        assert h.chain.justified_checkpoint.epoch >= 2
    finally:
        bls.set_backend("fake_crypto")


def test_device_sweep_scopes_x64_to_its_call():
    """The uint64 epoch sweep turns x64 on for its own call only: the
    uint32/int32 crypto kernels sharing the process keep their dtypes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lighthouse_tpu.ops import bls381
    from lighthouse_tpu.ops.epoch_sweep import epoch_sweep

    n = 8
    u64 = lambda v: np.full(n, v, dtype=np.uint64)  # noqa: E731
    balances, scores = epoch_sweep(
        u64(32 * 10**9),
        np.zeros(n, dtype=bool),
        u64(0),
        u64(2**64 - 1),
        u64(2**64 - 1),
        np.full(n, 0b111, dtype=np.uint8),
        u64(0),
        u64(32 * 10**9),
        np.array([1, 2, 64, n * 32, 0, 4, 16, 4 * 2**24, 10**9], dtype=np.uint64),
    )
    assert balances.dtype == np.uint64 and scores.dtype == np.uint64
    assert (balances > 32 * 10**9).all()  # full participation earns rewards
    assert not jax.config.jax_enable_x64
    limbs = jnp.asarray(bls381.fq_to_device([1, 2]))
    out = jax.eval_shape(bls381.mont_mul, limbs, limbs)
    assert limbs.dtype == jnp.int32 and out.dtype == jnp.int32
    assert jnp.asarray(np.arange(3, dtype=np.int64)).dtype == jnp.int32
