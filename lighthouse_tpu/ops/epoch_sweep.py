"""Device epoch sweep: the fused all-validator rewards/penalties pass.

The single_pass.rs:20 analog on device (SURVEY §7 step 3): Altair's
epoch-boundary flag deltas, inactivity penalties, and inactivity-score
updates as ONE jitted pass over flat uint64 arrays — integer-only
(consensus-grade, no floats), shape-stable per validator count, epoch
scalars traced (no per-epoch recompiles).

uint64 needs JAX's x64 mode. `epoch_sweep` turns it on only for its own
trace and call (`with jax.enable_x64(True)`), so the uint32/int32 crypto
kernels that share the process keep their default dtypes.

Parity contract: bit-exact equality with the numpy sweep in
state_processing/altair.py for every input where the u64 overflow guard
(effective_balance·score) does not trip; the host wrapper must pre-check
that guard and keep such states on the host bigint path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# altair participation flag weights (TIMELY_SOURCE/TARGET/HEAD)
PARTICIPATION_FLAG_WEIGHTS = (14, 26, 14)
WEIGHT_DENOMINATOR = 64
TIMELY_TARGET_FLAG_INDEX = 1
TIMELY_HEAD_FLAG_INDEX = 2


def epoch_sweep(*arrays):
    """Run the sweep with x64 on for this call only; returns numpy
    (new_balances, new_scores) uint64 arrays."""
    with jax.enable_x64(True):
        new_balances, new_scores = _epoch_sweep(*arrays)
        # contiguous: a device array may come back with strided host layout
        return np.ascontiguousarray(new_balances), np.ascontiguousarray(new_scores)


@jax.jit
def _epoch_sweep(
    effective_balance,  # [n] u64
    slashed,  # [n] bool
    activation_epoch,  # [n] u64
    exit_epoch,  # [n] u64
    withdrawable_epoch,  # [n] u64
    prev_flags,  # [n] u8 previous-epoch participation
    scores,  # [n] u64 inactivity scores
    balances,  # [n] u64
    scalars,  # [9] u64: prev_epoch, curr_epoch, base_reward_per_increment,
    #                total_active_increments, in_leak, score_bias,
    #                score_recovery, inactivity_denom,
    #                effective_balance_increment — see host wrapper
):
    prev_epoch = scalars[0]
    curr_epoch = scalars[1]
    base_reward_per_increment = scalars[2]
    total_active_increments = scalars[3]
    in_leak = scalars[4] != 0
    score_bias = scalars[5]
    score_recovery = scalars[6]
    inactivity_denom = scalars[7]
    eb_increment = scalars[8]

    u64 = jnp.uint64
    one = jnp.uint64(1)

    def active_at(epoch):
        return (activation_epoch <= epoch) & (epoch < exit_epoch)

    prev_active = active_at(prev_epoch)
    curr_active = active_at(curr_epoch)
    del curr_active  # totals are precomputed on host (traced scalars)
    eligible = prev_active | (slashed & (prev_epoch + one < withdrawable_epoch))

    eb_increments = effective_balance // eb_increment
    base_rewards = eb_increments * base_reward_per_increment

    rewards = jnp.zeros_like(balances)
    penalties = jnp.zeros_like(balances)

    def unslashed_participating(flag_index):
        has = (prev_flags >> flag_index) & 1
        return (has == 1) & (~slashed) & prev_active

    for flag_index, weight in enumerate(PARTICIPATION_FLAG_WEIGHTS):
        participating = unslashed_participating(flag_index)
        upb = jnp.maximum(
            jnp.sum(jnp.where(participating, effective_balance, u64(0))),
            eb_increment,
        )
        upb_increments = upb // eb_increment
        got_flag = eligible & participating
        numer = base_rewards * u64(weight) * upb_increments
        flag_reward = numer // (
            total_active_increments * u64(WEIGHT_DENOMINATOR)
        )
        rewards = rewards + jnp.where(
            got_flag & ~in_leak, flag_reward, u64(0)
        )
        if flag_index != TIMELY_HEAD_FLAG_INDEX:
            missed = eligible & ~participating
            penalties = penalties + jnp.where(
                missed,
                (base_rewards * u64(weight)) // u64(WEIGHT_DENOMINATOR),
                u64(0),
            )

    # inactivity-score updates (process_inactivity_updates) — computed on
    # the PRE-update scores ordering-wise BEFORE the inactivity penalty
    # uses... the spec runs process_inactivity_updates before
    # rewards_and_penalties, so penalties see the UPDATED scores
    participating_target = unslashed_participating(TIMELY_TARGET_FLAG_INDEX)
    dec = eligible & participating_target
    inc = eligible & ~participating_target
    new_scores = scores - jnp.where(dec, jnp.minimum(one, scores), u64(0))
    new_scores = new_scores + jnp.where(inc, score_bias, u64(0))
    new_scores = new_scores - jnp.where(
        eligible & ~in_leak, jnp.minimum(score_recovery, new_scores), u64(0)
    )

    # inactivity penalties (get_inactivity_penalty_deltas) on the updated
    # scores
    inactive = eligible & ~participating_target
    penalties = penalties + jnp.where(
        inactive,
        (effective_balance * new_scores) // inactivity_denom,
        u64(0),
    )

    new_balances = balances + rewards
    new_balances = jnp.maximum(new_balances, penalties) - penalties
    return new_balances, new_scores
