"""Device KZG kernels vs the host bigint oracle.

VERDICT r4 #3: ops/fr.py + ops/msm.py + the _DeviceKzg path were untested.
These tests pin every kernel against the host implementation at small
shapes (the math is size-generic; the 4096-element mainnet domain rides
the same code), both accepting and rejecting, on the CPU test platform.
Reference behavior being mirrored: crypto/kzg/src/lib.rs:81-117 (c-kzg
wrapper), polynomial-commitments.md evaluate_polynomial_in_evaluation_form.
"""

import random

import numpy as np
import pytest

from lighthouse_tpu.crypto.bls12_381 import FQ, pt_add, pt_eq, pt_mul, to_affine
from lighthouse_tpu.crypto.bls12_381.curve import G1_GEN
from lighthouse_tpu.crypto.kzg import (
    FR_MODULUS,
    Kzg,
    TrustedSetup,
)

# every test in this file is tier-2: device kernels — XLA-CPU compiles
# take minutes cold. tests/conftest.py enforces this marker at collection.
pytestmark = pytest.mark.slow

N = 16  # dev domain size: big enough to exercise folds, small compiles
rng = random.Random(1234)


@pytest.fixture(scope="module")
def setup():
    return TrustedSetup.insecure_dev(N)


@pytest.fixture(scope="module")
def dev_kzg(setup):
    k = Kzg(setup, device=True)
    assert k._dev is not None
    return k


# ---------------------------------------------------------------------------
# Fr limb arithmetic
# ---------------------------------------------------------------------------


def test_fr_roundtrip_and_mul_add_sub_inv():
    from lighthouse_tpu.ops.fr import (
        fr_add,
        fr_from_device,
        fr_inv,
        fr_mul,
        fr_sub,
        fr_to_device,
    )

    xs = [rng.randrange(1, FR_MODULUS) for _ in range(8)]
    ys = [rng.randrange(1, FR_MODULUS) for _ in range(8)]
    # edge lanes: 0, 1, r-1
    xs[0], ys[0] = 0, 1
    xs[1], ys[1] = FR_MODULUS - 1, FR_MODULUS - 1
    a = fr_to_device(xs)
    b = fr_to_device(ys)
    assert fr_from_device(a) == xs  # encode/decode inverse

    got = fr_from_device(fr_mul(a, b))
    assert got == [x * y % FR_MODULUS for x, y in zip(xs, ys)]

    got = fr_from_device(fr_add(a, b))
    assert got == [(x + y) % FR_MODULUS for x, y in zip(xs, ys)]

    got = fr_from_device(fr_sub(a, b))
    assert got == [(x - y) % FR_MODULUS for x, y in zip(xs, ys)]

    nz = [v if v else 5 for v in xs]
    got = fr_from_device(fr_inv(fr_to_device(nz)))
    assert got == [pow(v, FR_MODULUS - 2, FR_MODULUS) for v in nz]


def test_barycentric_eval_matches_host(setup):
    import jax.numpy as jnp

    from lighthouse_tpu.ops.fr import (
        barycentric_eval_batch,
        fr_from_device,
        fr_to_device,
    )

    k = Kzg(setup)  # host oracle
    log_n = (N - 1).bit_length()
    evals_lists = [
        [rng.randrange(FR_MODULUS) for _ in range(N)] for _ in range(3)
    ]
    zs = [rng.randrange(FR_MODULUS) for _ in range(3)]
    ev = jnp.asarray(np.stack([fr_to_device(e) for e in evals_lists]))
    roots = jnp.asarray(fr_to_device(setup.roots_brp))
    z_dev = jnp.asarray(fr_to_device(zs))
    ys = fr_from_device(barycentric_eval_batch(ev, roots, z_dev, log_n))
    for got, evs, z in zip(ys, evals_lists, zs):
        assert got == k._evaluate_host(evs, z)


def test_quotient_batch_matches_host(setup):
    import jax.numpy as jnp

    from lighthouse_tpu.ops.fr import fr_from_device, fr_to_device, quotient_batch

    evals = [rng.randrange(FR_MODULUS) for _ in range(N)]
    z = rng.randrange(FR_MODULUS)
    k = Kzg(setup)
    y = k._evaluate_host(evals, z)
    got = fr_from_device(
        quotient_batch(
            jnp.asarray(fr_to_device(evals)),
            jnp.asarray(fr_to_device(setup.roots_brp)),
            jnp.asarray(fr_to_device([z]))[0],
            jnp.asarray(fr_to_device([y]))[0],
        )
    )
    want = [
        (e - y) * pow((w - z) % FR_MODULUS, FR_MODULUS - 2, FR_MODULUS)
        % FR_MODULUS
        for e, w in zip(evals, setup.roots_brp)
    ]
    assert got == want


# ---------------------------------------------------------------------------
# MSM
# ---------------------------------------------------------------------------


def _host_msm(scalars, points):
    acc = None
    for s, p in zip(scalars, points):
        term = pt_mul(FQ, p, s)
        acc = term if acc is None else pt_add(FQ, acc, term)
    return acc


@pytest.mark.parametrize(
    "kind", ["random", "zero_lanes", "one_skewed_bucket", "extremes"]
)
def test_msm_matches_host(setup, kind):
    """The bucketized kernel on every scalar shape it must survive: zero
    lanes, every scalar in one bucket (spills into extra table rounds),
    and r−1 / 1."""
    from lighthouse_tpu.ops.bls381 import g1_points_to_device
    from lighthouse_tpu.ops.msm import bucket_tables, g1_msm_device

    pts = setup.g1_lagrange
    dev = g1_points_to_device(pts)
    scalars = {
        "random": [rng.randrange(FR_MODULUS) for _ in range(N)],
        "zero_lanes": [0] * 3 + [rng.randrange(FR_MODULUS) for _ in range(N - 3)],
        "one_skewed_bucket": [12345] * N,
        "extremes": [FR_MODULUS - 1] * (N // 2) + [1] * (N // 2),
    }[kind]
    if kind == "one_skewed_bucket":
        assert bucket_tables(scalars, N).shape[0] > 1
    got = g1_msm_device(scalars, dev)
    assert pt_eq(FQ, got, _host_msm(scalars, pts))


# ---------------------------------------------------------------------------
# End-to-end device engine vs host engine
# ---------------------------------------------------------------------------


def _blob(seed: int) -> bytes:
    r = random.Random(seed)
    return b"".join(
        r.randrange(FR_MODULUS).to_bytes(32, "big") for _ in range(N)
    )


def test_device_commitment_matches_host(setup, dev_kzg):
    host = Kzg(setup)
    blob = _blob(7)
    assert dev_kzg.blob_to_kzg_commitment(blob) == host.blob_to_kzg_commitment(
        blob
    )
    assert dev_kzg._dev is not None  # device path survived (no fallback)


def test_device_proof_roundtrip_and_reject(setup, dev_kzg):
    host = Kzg(setup)
    blob = _blob(8)
    c = dev_kzg.blob_to_kzg_commitment(blob)
    z = (99991).to_bytes(32, "big")
    proof, y = dev_kzg.compute_kzg_proof(blob, z)
    h_proof, h_y = host.compute_kzg_proof(blob, z)
    assert (proof, y) == (h_proof, h_y)
    assert dev_kzg.verify_kzg_proof(c, z, y, proof)
    bad_y = ((int.from_bytes(y, "big") + 1) % FR_MODULUS).to_bytes(32, "big")
    assert not dev_kzg.verify_kzg_proof(c, z, bad_y, proof)
    assert dev_kzg._dev is not None


def test_device_blob_batch_verify_accept_and_reject(setup, dev_kzg):
    blobs = [_blob(i) for i in range(20, 23)]
    cs = [dev_kzg.blob_to_kzg_commitment(b) for b in blobs]
    proofs = [dev_kzg.compute_blob_kzg_proof(b, c) for b, c in zip(blobs, cs)]
    assert dev_kzg.verify_blob_kzg_proof_batch(blobs, cs, proofs)
    assert not dev_kzg.verify_blob_kzg_proof_batch(blobs, cs, proofs[::-1])
    assert dev_kzg._dev is not None
