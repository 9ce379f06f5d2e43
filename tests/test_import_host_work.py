"""Host work on the block-import path at 1M validators: a state copy keeps
its epoch's shufflings (the reference's state clone keeps its committee
caches), and the host-to-device limb conversions of the signature batch
and the KZG proof check run in one pass over bytes."""

import random

import numpy as np
import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.state_processing import (
    committee_cache_at,
    get_beacon_committee,
    per_slot_processing,
)
from lighthouse_tpu.state_processing import shuffle
from lighthouse_tpu.state_processing.accessors import invalidate_caches
from lighthouse_tpu.testing.harness import StateHarness
from lighthouse_tpu.types import MinimalEthSpec, minimal_spec

E = MinimalEthSpec


@pytest.fixture
def state():
    bls.set_backend("fake_crypto")
    try:
        yield StateHarness(minimal_spec(), E, validator_count=64).state
    finally:
        bls.set_backend("host")


@pytest.fixture
def shuffles(monkeypatch):
    """Counts the whole-list shuffles the committee caches run."""
    calls = []
    real = shuffle._shuffled_positions

    def counted(*args):
        calls.append(args)
        return real(*args)

    monkeypatch.setattr(shuffle, "_shuffled_positions", counted)
    return calls


def _committees(state, epoch):
    slots = range(epoch * E.SLOTS_PER_EPOCH, (epoch + 1) * E.SLOTS_PER_EPOCH)
    per_slot = committee_cache_at(state, epoch, E).committees_per_slot
    return [get_beacon_committee(state, s, i, E) for s in slots for i in range(per_slot)]


def test_copy_keeps_the_epochs_shuffling(state, shuffles):
    committee_cache_at(state, 0, E)
    assert len(shuffles) == 1
    copy = state.copy()
    assert committee_cache_at(copy, 0, E) is committee_cache_at(state, 0, E)
    assert len(shuffles) == 1


def test_each_copy_caches_on_its_own(state, shuffles):
    committee_cache_at(state, 0, E)
    copy = state.copy()
    committee_cache_at(copy, 1, E)  # the next epoch, on the copy only
    invalidate_caches(copy)
    assert len(shuffles) == 2
    committee_cache_at(state, 0, E)  # the original's cache stands
    assert len(shuffles) == 2
    committee_cache_at(state, 1, E)  # and never saw the copy's entry
    assert len(shuffles) == 3


def test_copy_across_an_epoch_boundary_reads_the_new_shuffling(state):
    for epoch in (0, 1):
        committee_cache_at(state, epoch, E)
    copy = state.copy()
    fresh = state.copy()
    invalidate_caches(fresh)
    for st in (copy, fresh):
        while st.slot < 2 * E.SLOTS_PER_EPOCH:
            per_slot_processing(st, minimal_spec(), E)
    for epoch in (1, 2, 3):
        assert _committees(copy, epoch) == _committees(fresh, epoch)


def test_ints_to_limbs_equals_one_int_at_a_time():
    from lighthouse_tpu.ops.bls381 import NLIMB, P, R_MONT, fq_to_device, int_to_limbs, ints_to_limbs
    from lighthouse_tpu.ops.fr import FR_MOD, NLIMB_FR, R_MONT_FR, fr_to_device

    rng = random.Random(7)
    fq = [0, 1, P - 1] + [rng.randrange(P) for _ in range(61)]
    fr = [0, 1, FR_MOD - 1, FR_MOD + 5] + [rng.randrange(FR_MOD) for _ in range(60)]
    rows = ints_to_limbs(fq)
    assert rows.dtype == np.int32 and rows.shape == (64, NLIMB)
    assert (rows == np.stack([int_to_limbs(v) for v in fq])).all()
    assert (fq_to_device(fq) == np.stack([int_to_limbs(v * R_MONT % P) for v in fq])).all()
    want = np.stack([int_to_limbs(v % FR_MOD * R_MONT_FR % FR_MOD, NLIMB_FR) for v in fr])
    assert (fr_to_device(fr) == want).all()


def test_pubkey_grid_holds_each_point_and_infinity_pads():
    from lighthouse_tpu.ops.bls381 import P, R_MONT, int_to_limbs
    from lighthouse_tpu.ops.bls381_verify import _g1_affine_grid

    rng = random.Random(11)
    grid = [
        [(rng.randrange(P), rng.randrange(P)) if rng.random() < 0.7 else None for _ in range(16)]
        for _ in range(4)
    ]
    grid.append([None] * 16)  # a padding set
    xs, ys, zs = _g1_affine_grid(grid)
    assert xs.shape == ys.shape == zs.shape == (5, 16, 48)
    one = int_to_limbs(R_MONT % P)
    for i, row in enumerate(grid):
        for j, aff in enumerate(row):
            if aff is None:
                assert not zs[i, j].any()
                continue
            assert (xs[i, j] == int_to_limbs(aff[0] * R_MONT % P)).all()
            assert (ys[i, j] == int_to_limbs(aff[1] * R_MONT % P)).all()
            assert (zs[i, j] == one).all()


@pytest.mark.parametrize("blobs", [1, 3])
def test_kzg_evaluation_pads_with_the_first_blobs_rows(monkeypatch, blobs):
    from lighthouse_tpu.crypto import kzg
    from lighthouse_tpu.ops import fr

    seen = {}

    def stand_in(evals, roots, zs, log_n):
        seen["evals"] = np.asarray(evals)
        return zs  # the points themselves: enough to follow the lanes

    monkeypatch.setattr(fr, "barycentric_eval_batch", stand_in)
    dev = kzg._DeviceKzg(kzg.TrustedSetup.insecure_dev(16))
    rng = random.Random(blobs)
    evals = [[rng.randrange(fr.FR_MOD) for _ in range(16)] for _ in range(blobs)]
    zs = [rng.randrange(fr.FR_MOD) for _ in range(blobs)]
    assert dev.evaluate_batch(evals, zs) == zs
    got = seen["evals"]
    assert got.shape == (8, 16, 32)
    for j in range(blobs):
        assert (got[j] == fr.fr_to_device(evals[j])).all()
    assert (got[blobs:] == got[0]).all()
