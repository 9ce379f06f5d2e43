"""Device pairing stack vs the host bigint oracle.

Covers the full device BLS chain (VERDICT r2 task #1): Fq12 tower ops,
ψ-ladder subgroup checks, Budroni–Pintore cofactor clearing, staged SSWU
hash-to-G2, the batched Miller loop + shared-final-exponentiation
multi-pairing check, and the end-to-end device batch verifier. Everything
is `slow` — first XLA-CPU compiles take minutes; the repo-local persistent
cache amortizes them across runs."""

import hashlib
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lighthouse_tpu.crypto.bls12_381 import (
    FQ,
    FQ2,
    G1_GEN,
    G2_GEN,
    g2_in_subgroup,
    hash_to_g2,
    pt_eq,
    pt_mul,
    to_affine,
)
from lighthouse_tpu.crypto.bls12_381 import fields as HF
from lighthouse_tpu.crypto.bls12_381.curve import g2_clear_cofactor, pt_neg
from lighthouse_tpu.crypto.bls12_381.fields import P, f2, f2_add, f2_mul, f2_sqrt
from lighthouse_tpu.ops import bls381_htc as H
from lighthouse_tpu.ops import bls381_pairing as DP
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.ops import bls381_tower as TW
from lighthouse_tpu.ops import bls381_verify as V
from lighthouse_tpu.ops.bls381 import NLIMB, g2_points_from_device
from lighthouse_tpu.ops.bls381_tower import fq2_const

# every test in this file is tier-2: pairing kernels: the slowest compiles in the tree.
# tests/conftest.py enforces this marker at collection time.
pytestmark = pytest.mark.slow

rng = random.Random(21)


def _rand_f12():
    def rf2():
        return (rng.randrange(P), rng.randrange(P))

    def rf6():
        return (rf2(), rf2(), rf2())

    return (rf6(), rf6())


def _non_subgroup_g2():
    x = f2(3, 1)
    while True:
        rhs = f2_add(f2_mul(f2_mul(x, x), x), (4, 4))
        y = f2_sqrt(rhs)
        if y is not None and not g2_in_subgroup((x, y, f2(1))):
            return (x, y)
        x = f2_add(x, f2(1))


def test_f12_tower_ops_vs_host():
    a12 = [_rand_f12() for _ in range(4)]
    b12 = [_rand_f12() for _ in range(4)]
    da = jnp.asarray(TW.f12_to_device(a12))
    db = jnp.asarray(TW.f12_to_device(b12))
    assert TW.f12_from_device(TW.f12_mul(da, db)) == [
        HF.f12_mul(x, y) for x, y in zip(a12, b12)
    ]
    assert TW.f12_from_device(TW.f12_sqr(da)) == [HF.f12_sqr(x) for x in a12]
    assert TW.f12_from_device(TW.f12_inv(da)) == [HF.f12_inv(x) for x in a12]
    assert TW.f12_from_device(TW.f12_frob(da)) == [HF.f12_frob(x) for x in a12]


@pytest.mark.slow
def test_f2_sqrt_device():
    sq_in = []
    for _ in range(6):
        v = (rng.randrange(P), rng.randrange(P))
        sq_in.append(HF.f2_sqr(v))
    sq_in.append((4, 0))  # y == 0 path
    x = 5
    while HF.f2_legendre((x, 3)) >= 0:
        x += 1
    sq_in.append((x, 3))  # non-square
    dev = jnp.asarray(np.stack([fq2_const(v) for v in sq_in]))

    # the square root as map_to_e2 takes it: the candidate roots, then
    # the root from 1/(2t)
    @jax.jit
    def sqrt(a):
        roots, t, two_t = H._sqrt_roots(a)
        return H._sqrt_finish(a, roots, t, TW.fq_inv(two_t))

    roots, is_sq = sqrt(dev)
    assert np.asarray(is_sq).tolist() == [True] * 7 + [False]
    got_sq = np.asarray(TW.f2_sqr(roots))
    assert (got_sq[:7] == np.asarray(dev)[:7]).all()


@pytest.mark.slow
def test_g2_subgroup_check_device():
    good = [pt_mul(FQ2, G2_GEN, k) for k in (1, 5, 123456789)]
    bad = _non_subgroup_g2()
    pts = [to_affine(FQ2, p) for p in good] + [bad]
    sigs = DP.g2_affine_to_device(pts)
    # the verifier's ladders program with zero RLC scalars: the subgroup
    # verdicts come from its extra [|x|] lanes
    n = len(pts)
    agg = tuple(jnp.zeros((n, NLIMB), jnp.int32) for _ in range(3))
    bits = np.zeros((n, bls.RAND_BITS), np.int32)
    _, _, in_sub = V._ladders(agg, sigs, bits)
    assert np.asarray(in_sub).tolist() == [True, True, True, False]


@pytest.mark.slow
def test_g2_clear_cofactor_device_vs_host():
    bad = _non_subgroup_g2()
    qx, qy, _ = DP.g2_affine_to_device([bad])
    out = DP.g2_clear_cofactor_device((qx, qy, DP._one_fq2((1,))))
    got = g2_points_from_device(out)[0]
    want = g2_clear_cofactor((bad[0], bad[1], f2(1)))
    assert pt_eq(FQ2, got, want)
    assert g2_in_subgroup(got)


@pytest.mark.slow
def test_hash_to_g2_device_vs_host():
    msgs = [hashlib.sha256(bytes([i])).digest() for i in range(4)]
    u = H.messages_to_field_device(msgs)
    got = g2_points_from_device(H.hash_to_g2_device(jnp.asarray(u)))
    for m, g in zip(msgs, got):
        assert pt_eq(FQ2, g, hash_to_g2(m))


@pytest.mark.slow
def test_multi_pairing_check_device():
    a = 987654321
    pa = pt_mul(FQ, G1_GEN, a)
    qa = pt_mul(FQ2, G2_GEN, a)
    xp, yp, p_inf = DP.g1_affine_to_device(
        [to_affine(FQ, pt_neg(FQ, pa)), to_affine(FQ, G1_GEN)]
    )
    qx, qy, q_inf = DP.g2_affine_to_device(
        [to_affine(FQ2, G2_GEN), to_affine(FQ2, qa)]
    )
    assert bool(DP.multi_pairing_check_device(xp, yp, p_inf, qx, qy, q_inf))
    xp2, yp2, p_inf2 = DP.g1_affine_to_device(
        [to_affine(FQ, pt_neg(FQ, pt_mul(FQ, G1_GEN, a + 1))), to_affine(FQ, G1_GEN)]
    )
    assert not bool(
        DP.multi_pairing_check_device(xp2, yp2, p_inf2, qx, qy, q_inf)
    )


def _batch_case(case):
    """Signature sets for the device verifier's oracle cases: a mixed chunk
    (singles and a 3-key aggregate), a 496-key row (the import's committee
    bucket, 512), an invalid set, a signature on the twist outside G2, and
    a full 32-set chunk, whose message gather pads with the appended
    infinity lane."""
    from lighthouse_tpu.crypto.bls import AggregateSignature, SecretKey
    from lighthouse_tpu.crypto.bls12_381.fields import R

    def singles(count):
        out = []
        for i, kp in enumerate(bls.interop_keypairs(count)):
            m = hashlib.sha256(bytes([i % 5])).digest()
            out.append(bls.SignatureSet.single(kp.sk.sign(m), kp.pk, m))
        return out

    msg = hashlib.sha256(b"full device").digest()
    if case == "key_row_496":
        kps = bls.interop_keypairs(496)
        # Σ skᵢ·H(m) is the aggregate of the 496 signatures
        sig = SecretKey(sum(kp.sk.scalar for kp in kps) % R).sign(msg)
        return singles(2) + [bls.SignatureSet(sig, [kp.pk for kp in kps], msg)]
    if case == "full_chunk":
        return singles(32)
    kps = bls.interop_keypairs(8)
    sets = singles(8)
    aggsig = AggregateSignature.from_signatures(
        [kp.sk.sign(msg) for kp in kps[:3]]
    ).to_signature()
    sets.append(bls.SignatureSet(aggsig, [kp.pk for kp in kps[:3]], msg))
    if case == "invalid":
        sets[2] = bls.SignatureSet.single(
            sets[3].signature, sets[2].pubkeys[0], sets[2].message
        )
    if case == "not_in_g2":
        x, y = _non_subgroup_g2()
        sig = bls.Signature.from_point((x, y, f2(1)))
        sets[2] = bls.SignatureSet.single(sig, sets[2].pubkeys[0], sets[2].message)
    return sets


@pytest.mark.slow
@pytest.mark.parametrize(
    "case", ["mixed", "key_row_496", "invalid", "not_in_g2", "full_chunk"]
)
def test_full_device_batch_verify(case):
    from lighthouse_tpu.ops.bls381_verify import verify_signature_sets_device_full
    from lighthouse_tpu.utils.tracing import span

    bls.set_backend("host")
    try:
        sets = _batch_case(case)
        want = bls.verify_signature_sets(sets)
        assert want == (case not in ("invalid", "not_in_g2"))
        with span("device_batch_root") as root:
            assert verify_signature_sets_device_full(sets, random.Random(5)) == want
        # the subgroup verdict, not the pairing, rejects a signature outside
        # G2: the chunk ends before the pairing's two programs
        (call,) = root.children
        assert call.fields["programs"] == (4 if case == "not_in_g2" else 6)
    finally:
        bls.set_backend("fake_crypto")
