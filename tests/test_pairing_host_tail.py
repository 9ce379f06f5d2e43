"""The host half of the device multi-pairing check: Miller lanes in the
device's limb format in, one verdict out. The lanes are built on the host
from the oracle's per-pair Miller loops, so nothing here compiles a
device program."""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from lighthouse_tpu.crypto.bls12_381 import FQ, FQ2, G1_GEN, G2_GEN, pt_mul, to_affine
from lighthouse_tpu.crypto.bls12_381.curve import pt_neg
from lighthouse_tpu.crypto.bls12_381.fields import P
from lighthouse_tpu.crypto.bls12_381.pairing import miller_loop, pairing_check
from lighthouse_tpu.metrics import REGISTRY
from lighthouse_tpu.ops import bls381_pairing as DP
from lighthouse_tpu.ops.bls381 import NLIMB, R_MONT, fq_from_device, limbs_to_int
from lighthouse_tpu.ops.bls381_tower import f12_from_device, f12_to_device

rng = random.Random(25)


def _rand_f12():
    f2 = lambda: (rng.randrange(P), rng.randrange(P))  # noqa: E731
    return ((f2(), f2(), f2()), (f2(), f2(), f2()))


def _pairs(a, b):
    """e(−[a]G1, G2) · e(G1, [b]G2): one iff a ≡ b (mod r)."""
    return [
        (pt_neg(FQ, pt_mul(FQ, G1_GEN, a)), G2_GEN),
        (G1_GEN, pt_mul(FQ2, G2_GEN, b)),
    ]


def _miller(pairs):
    return [miller_loop(to_affine(FQ2, q), to_affine(FQ, p)) for p, q in pairs]


def _lanes(pairs):
    """The pairs' Miller lanes, padded to PAIRING_LANES with skipped lanes
    of random junk: (lanes [33, 2, 3, 2, 48] int32, skip [33] bool)."""
    vals = _miller(pairs)
    pad = DP.PAIRING_LANES - len(vals)
    skip = np.array([False] * len(vals) + [True] * pad)
    return f12_to_device(vals + [_rand_f12() for _ in range(pad)]), skip


@pytest.mark.parametrize(
    "a, b",
    [(5, 5), (5, 6), (987654321, 987654321), (987654321, 987654322)],
)
def test_host_tail_answers_as_the_oracle(a, b):
    pairs = _pairs(a, b)
    want = pairing_check(pairs)
    assert want == (a == b)
    assert DP.pairing_host_tail(*_lanes(pairs)) == want


def test_host_tail_multiplies_every_lane_not_skipped():
    # three pairs whose product is one only all together
    pairs = _pairs(7, 3) + [(G1_GEN, pt_mul(FQ2, G2_GEN, 4))]
    assert pairing_check(pairs)
    lanes, skip = _lanes(pairs)
    assert DP.pairing_host_tail(lanes, skip)
    skip[2] = True  # one pair's lane left out
    assert not DP.pairing_host_tail(lanes, skip)


def test_skipped_lanes_are_ignored_whatever_they_hold():
    lanes, skip = _lanes(_pairs(11, 11))
    assert skip[2:].all()
    assert DP.pairing_host_tail(lanes, skip)
    # the same junk, multiplied in, breaks the check
    skip[2] = False
    assert not DP.pairing_host_tail(lanes, skip)
    # a skipped lane holding limbs that are no field element at all
    skip[2] = True
    lanes[2:] = 255
    assert DP.pairing_host_tail(lanes, skip)


def test_host_tail_counts_once_a_check():
    counter = REGISTRY.counter("bls_pairing_host_tails_total")
    before = counter.value()
    DP.pairing_host_tail(*_lanes(_pairs(2, 2)))
    DP.pairing_host_tail(*_lanes(_pairs(2, 3)))
    assert counter.value() - before == 2


def test_f12_from_device_matches_the_per_limb_decoding():
    vals = [_rand_f12() for _ in range(6)]
    vals.append(((((0, P - 1), (1, 0), (0, 0)), ((P - 1, 0), (0, 1), (P - 2, 3)))))
    arr = f12_to_device(vals)
    rinv = pow(R_MONT, -1, P)
    per_limb = [limbs_to_int(row) * rinv % P for row in arr.reshape(-1, NLIMB)]
    got = f12_from_device(arr)
    assert got == vals
    flat = [c for f12 in got for f6 in f12 for f2 in f6 for c in f2]
    assert flat == per_limb


def test_multi_pairing_check_device_pads_and_skips(monkeypatch):
    """The composition the KZG check calls, with the device Miller loop
    replaced by the host oracle's on the same limbs: three pairs pad to
    PAIRING_LANES, the padding and the infinity lanes are skipped."""

    def host_miller(xp, yp, q_x, q_y):
        px, py = fq_from_device(xp), fq_from_device(yp)
        qx, qy = (fq_from_device(a) for a in (q_x, q_y))
        vals = [
            miller_loop(((qx[2 * i], qx[2 * i + 1]), (qy[2 * i], qy[2 * i + 1])), (px[i], py[i]))
            for i in range(len(px))
        ]
        return jnp.asarray(f12_to_device(vals))

    monkeypatch.setattr(DP, "_miller_jit", host_miller)
    for a, b, want in ((9, 9, True), (9, 10, False)):
        pairs = _pairs(a, b)
        g1 = [to_affine(FQ, p) for p, _ in pairs] + [to_affine(FQ, G1_GEN)]
        g2 = [to_affine(FQ2, q) for _, q in pairs] + [None]  # an infinity lane
        xp, yp, p_inf = DP.g1_affine_to_device(g1)
        qx, qy, q_inf = DP.g2_affine_to_device(g2)
        lanes, skip = DP.miller_lanes_device(xp, yp, p_inf, qx, qy, q_inf)
        assert lanes.shape == (DP.PAIRING_LANES, 2, 3, 2, NLIMB)
        assert np.asarray(skip).tolist() == [False, False] + [True] * (DP.PAIRING_LANES - 2)
        assert DP.multi_pairing_check_device(xp, yp, p_inf, qx, qy, q_inf) is want
