"""Device BLS limb arithmetic vs host bigint oracle.

Field ops run in the default suite (fast compiles); batch point ops and the
full device batch-verify are marked slow (minutes of XLA-CPU compile on
first run; the repo-local persistent cache amortizes them)."""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from lighthouse_tpu.crypto.bls12_381 import (
    FQ,
    FQ2,
    G1_GEN,
    G2_GEN,
    pt_add,
    pt_eq,
    pt_mul,
)
from lighthouse_tpu.crypto.bls12_381.fields import P, R
from lighthouse_tpu.ops import bls381 as D

# every test in this file is tier-2: device kernels: XLA-CPU compiles take minutes cold.
# tests/conftest.py enforces this marker at collection time.
pytestmark = pytest.mark.slow


def test_limb_roundtrip():
    rng = random.Random(0)
    xs = [0, 1, P - 1] + [rng.randrange(P) for _ in range(5)]
    arr = D.fq_to_device(xs)
    assert D.fq_from_device(arr) == xs


def test_field_ops_vs_bigint():
    rng = random.Random(1)
    xs = [rng.randrange(P) for _ in range(16)]
    ys = [rng.randrange(P) for _ in range(16)]
    ax, ay = jnp.asarray(D.fq_to_device(xs)), jnp.asarray(D.fq_to_device(ys))
    assert D.fq_from_device(D.mont_mul(ax, ay)) == [
        (x * y) % P for x, y in zip(xs, ys)
    ]
    assert D.fq_from_device(D.mod_add(ax, ay)) == [
        (x + y) % P for x, y in zip(xs, ys)
    ]
    assert D.fq_from_device(D.mod_sub(ax, ay)) == [
        (x - y) % P for x, y in zip(xs, ys)
    ]


def test_field_edge_cases():
    edge = [0, P - 1, 1, P - 1, 12345, 0x123456789ABCDEF]
    e = jnp.asarray(D.fq_to_device(edge))
    assert D.fq_from_device(D.mod_sub(e, e)) == [0] * 6
    assert D.fq_from_device(D.mod_add(e, e)) == [(v * 2) % P for v in edge]
    assert D.fq_from_device(D.mont_mul(e, e)) == [(v * v) % P for v in edge]


def test_carry_cascade_regression():
    """Values engineered to produce long 255-chains (the lookahead resolve
    path); ripple passes alone would mis-normalize these."""
    vals = [((1 << 380) - 1) % P, P - 1, ((255 << 376) + 255) % P]
    a = jnp.asarray(D.fq_to_device(vals))
    one = jnp.asarray(D.fq_to_device([1, 1, 1]))
    got = D.fq_from_device(D.mont_mul(a, one))
    assert got == vals


@pytest.mark.slow
def test_g1_batch_scalar_mul():
    rng = random.Random(2)
    pts = [pt_mul(FQ, G1_GEN, rng.randrange(1, 10**9)) for _ in range(8)]
    scalars = [rng.getrandbits(64) for _ in range(8)]
    xs, ys, zs = D.g1_points_to_device(pts)
    bits = jnp.asarray(D.scalars_to_bits(scalars, 64))
    got = D.g1_points_from_device(D.batch_g1_scalar_mul(xs, ys, zs, bits))
    for g, p, s in zip(got, pts, scalars):
        assert pt_eq(FQ, g, pt_mul(FQ, p, s))


@pytest.mark.slow
def test_g2_batch_scalar_mul():
    rng = random.Random(4)
    pts = [pt_mul(FQ2, G2_GEN, rng.randrange(1, 10**9)) for _ in range(8)]
    scalars = [rng.getrandbits(64) for _ in range(8)]
    xs, ys, zs = D.g2_points_to_device(pts)
    bits = jnp.asarray(D.scalars_to_bits(scalars, 64))
    got = D.g2_points_from_device(D.batch_g2_scalar_mul(xs, ys, zs, bits))
    for g, p, s in zip(got, pts, scalars):
        assert pt_eq(FQ2, g, pt_mul(FQ2, p, s))


@pytest.mark.slow
@pytest.mark.parametrize("case", ["single_keys", "key_row_496", "invalid", "full_chunk"])
def test_device_verify_signature_sets(case):
    import hashlib

    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls import SecretKey
    from lighthouse_tpu.ops.bls381_verify import verify_signature_sets_device_full
    from lighthouse_tpu.utils.tracing import span

    bls.set_backend("host")
    msg = hashlib.sha256(b"device batch").digest()
    kps = bls.interop_keypairs(32 if case == "full_chunk" else 8)
    sets = [bls.SignatureSet.single(kp.sk.sign(msg), kp.pk, msg) for kp in kps]
    keys = 16
    if case == "key_row_496":
        row = bls.interop_keypairs(496)
        # Σ skᵢ·H(m) is the aggregate of the 496 signatures
        sig = SecretKey(sum(kp.sk.scalar for kp in row) % R).sign(msg)
        sets.append(bls.SignatureSet(sig, [kp.pk for kp in row], msg))
        keys = 512
    if case == "invalid":
        sets[3] = bls.SignatureSet.single(
            sets[4].signature, sets[3].pubkeys[0], sets[3].message
        )
    want = bls.verify_signature_sets(sets)
    assert want == (case != "invalid")
    with span("device_bls_test_root") as root:
        assert verify_signature_sets_device_full(sets, random.Random(5)) == want
    # one verify span with every stage once, in order, the two waits and
    # the pairing's host tail; six device programs
    (call,) = root.children
    assert call.name == "bls_device_verify"
    assert call.fields == {"sets": len(sets), "lanes": 32, "keys": keys, "programs": 6}
    assert [c.name for c in call.children] == [
        "bls_device_decode", "bls_device_hash_to_g2", "bls_device_aggregate",
        "bls_device_ladders", "bls_device_message_groups", "bls_device_subgroup",
        "bls_device_pairing",
    ]
    inner = [w for c in call.children for w in c.children]
    assert [w.name for w in inner] == ["bls_device_wait"] * 2 + ["bls_pairing_host_tail"]
