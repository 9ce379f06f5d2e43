"""The device verifier's committee sums against the host's point adds.

`_aggregate` sums each set's keys, and `_message_groups` each message's
scaled keys, as a halving tree over the padded committee axis; each is
compared, in affine form, with a left-to-right sum by the host curve code
(`crypto/bls12_381`). The grids are small, so each program compiles once
for the whole file in seconds on the CPU: 64 sets of a 64-lane bucket,
and a 32-set message gather."""

import jax.numpy as jnp
import numpy as np
import pytest

from lighthouse_tpu.crypto.bls12_381 import FQ, G1_GEN, pt_add, pt_neg
from lighthouse_tpu.crypto.bls12_381.curve import inf, to_affine
from lighthouse_tpu.ops import bls381 as D
from lighthouse_tpu.ops.bls381_verify import (
    _aggregate,
    _g1_affine_grid,
    _levels,
    _message_groups,
)

SETS = 64
BUCKET = 64
CHUNK = 32


def _points(count):
    """[1]G, [2]G, …: distinct affine G1 points."""
    out, acc = [], G1_GEN
    for _ in range(count):
        out.append(to_affine(FQ, acc))
        acc = pt_add(FQ, acc, G1_GEN)
    return out


POINTS = _points(BUCKET)


def _host_sum(affs):
    acc = inf(FQ)
    for a in affs:
        if a is not None:
            acc = pt_add(FQ, acc, (*a, 1))
    return to_affine(FQ, acc)


def _device_affine(pt):
    return [to_affine(FQ, p) for p in D.g1_points_from_device(pt)]


def _row(case):
    if case == "double":
        return [POINTS[5], POINTS[5]]
    if case == "cancel":
        return [POINTS[7], to_affine(FQ, pt_neg(FQ, (*POINTS[7], 1)))]
    return POINTS[:case]


@pytest.mark.parametrize("case", [1, 15, 16, 17, 33, 64, "double", "cancel"])
def test_aggregate_matches_host_sum(case):
    """Row 0 holds the case, row 1 is all infinity (a padded set lane),
    and every further row the case's keys rotated to start at another
    lane, so the tree pairs them differently; the level count is the
    case's own."""
    keys = _row(case)
    rows = [keys, []]
    for r in range(2, SETS):
        shift = r % len(keys)
        rows.append(keys[shift:] + keys[:shift])
    grid = [row + [None] * (BUCKET - len(row)) for row in rows]
    got = _device_affine(_aggregate(_g1_affine_grid(grid), _levels(len(keys))))
    want = _host_sum(keys)
    assert got[0] == want
    assert got[1] is None
    assert got[2:] == [want] * (SETS - 2)
    if case == "cancel":
        assert want is None


@pytest.mark.parametrize("pad_slot", [CHUNK - 1, CHUNK])
def test_message_groups_match_host_sum(pad_slot):
    """Groups of 1, 3 and 17 scaled keys and one holding a key twice; the
    rows past the groups hold only the gather's pad slot: a padded set
    lane (infinity) when the chunk has one, else the appended infinity
    lane."""
    live = CHUNK - 1 if pad_slot == CHUNK - 1 else CHUNK
    s_pk = [(*a, 1) for a in POINTS[:live]] + [inf(FQ)] * (CHUNK - live)
    groups = [[4], [0, 1, 2], list(range(5, 22)), [3, 3], [live - 1]]
    idx = np.full((CHUNK, CHUNK), pad_slot, dtype=np.int32)
    for g, members in enumerate(groups):
        idx[g, : len(members)] = members
    levels = _levels(max(len(m) for m in groups))
    got = _device_affine(
        _message_groups(D.g1_points_to_device(s_pk), jnp.asarray(idx), levels)
    )
    for g, members in enumerate(groups):
        assert got[g] == _host_sum([to_affine(FQ, s_pk[i]) for i in members])
    assert got[len(groups):] == [None] * (CHUNK - len(groups))


@pytest.mark.parametrize("keys, levels", [(1, 0), (2, 1), (15, 4), (16, 4), (17, 5), (486, 9), (512, 9)])
def test_levels_cover_the_keys(keys, levels):
    assert _levels(keys) == levels
