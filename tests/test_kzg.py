"""KZG commitments + blob proofs + DA checker.

Math validated on a small (n=64) insecure dev setup — the scheme is
size-generic; full 4096-element blobs ride the same code (ef-test style
coverage for commit/prove/verify, domain-point openings, batch RLC)."""

import hashlib
import random
from types import SimpleNamespace

import pytest

from lighthouse_tpu.crypto.kzg import (
    FR_MODULUS,
    Kzg,
    KzgError,
    TrustedSetup,
    fft_fr,
)

N = 64


@pytest.fixture(scope="module")
def kzg():
    return Kzg(TrustedSetup.insecure_dev(N))


def _blob(seed: int, n: int = N) -> bytes:
    rng = random.Random(seed)
    return b"".join(
        rng.randrange(FR_MODULUS).to_bytes(32, "big") for _ in range(n)
    )


def test_default_setup_resolves_mainnet_ceremony():
    """default() prefers the real ceremony output when one is reachable
    (env var, packaged file, or the known public locations) and only then
    falls back to the insecure dev setup. On this image the reference's
    embedded ceremony JSON is present, so default() must be mainnet-sized
    and commit the zero blob to the identity point."""
    import os

    ts = TrustedSetup.default()
    if not os.environ.get("LIGHTHOUSE_TPU_TRUSTED_SETUP") and not any(
        os.path.exists(p) for p in TrustedSetup.CEREMONY_SEARCH_PATHS
    ):
        pytest.skip("no ceremony file reachable; dev fallback expected")
    assert ts.n == 4096
    c = Kzg(ts).blob_to_kzg_commitment(bytes(4096 * 32))
    assert c[0] == 0xC0 and set(c[1:]) == {0}  # point at infinity


def test_fft_roundtrip():
    rng = random.Random(1)
    coeffs = [rng.randrange(FR_MODULUS) for _ in range(16)]
    evals = fft_fr(coeffs)
    back = fft_fr(evals, inverse=True)
    assert back == coeffs


def test_fft_evaluates_polynomial():
    # p(x) = 3 + 5x + 7x² on the order-4 domain
    coeffs = [3, 5, 7, 0]
    evals = fft_fr(coeffs)
    from lighthouse_tpu.crypto.kzg import _root_of_unity

    w = _root_of_unity(4)
    for i, e in enumerate(evals):
        x = pow(w, i, FR_MODULUS)
        assert e == (3 + 5 * x + 7 * x * x) % FR_MODULUS


def test_commit_prove_verify(kzg):
    blob = _blob(2)
    c = kzg.blob_to_kzg_commitment(blob)
    z = (12345).to_bytes(32, "big")
    proof, y = kzg.compute_kzg_proof(blob, z)
    assert kzg.verify_kzg_proof(c, z, y, proof)
    # wrong y rejected
    bad_y = ((int.from_bytes(y, "big") + 1) % FR_MODULUS).to_bytes(32, "big")
    assert not kzg.verify_kzg_proof(c, z, bad_y, proof)


def test_proof_at_domain_point(kzg):
    blob = _blob(3)
    c = kzg.blob_to_kzg_commitment(blob)
    z = kzg.setup.roots_brp[5].to_bytes(32, "big")
    proof, y = kzg.compute_kzg_proof(blob, z)
    # y must equal the raw evaluation stored in the blob at brp index 5
    assert int.from_bytes(y, "big") == int.from_bytes(blob[5 * 32 : 6 * 32], "big")
    assert kzg.verify_kzg_proof(c, z, y, proof)


def test_blob_proof_roundtrip(kzg):
    blob = _blob(4)
    c = kzg.blob_to_kzg_commitment(blob)
    proof = kzg.compute_blob_kzg_proof(blob, c)
    assert kzg.verify_blob_kzg_proof(blob, c, proof)
    tampered = bytearray(blob)
    tampered[33] ^= 1
    assert not kzg.verify_blob_kzg_proof(bytes(tampered), c, proof)


def test_blob_batch_verify(kzg):
    blobs = [_blob(i) for i in range(5, 8)]
    cs = [kzg.blob_to_kzg_commitment(b) for b in blobs]
    proofs = [kzg.compute_blob_kzg_proof(b, c) for b, c in zip(blobs, cs)]
    assert kzg.verify_blob_kzg_proof_batch(blobs, cs, proofs)
    # a swapped proof breaks the batch
    assert not kzg.verify_blob_kzg_proof_batch(blobs, cs, proofs[::-1])
    assert kzg.verify_blob_kzg_proof_batch([], [], [])


def test_field_element_range(kzg):
    blob = bytearray(_blob(9))
    blob[0:32] = (FR_MODULUS + 1).to_bytes(32, "big")
    with pytest.raises(KzgError):
        kzg.blob_to_kzg_commitment(bytes(blob))


def test_da_checker_flow(kzg):
    from lighthouse_tpu.beacon_chain.data_availability import (
        AvailabilityCheckError,
        DataAvailabilityChecker,
    )

    E = SimpleNamespace(MAX_BLOBS_PER_BLOCK=6)
    checker = DataAvailabilityChecker(kzg, E)

    blobs = [_blob(20), _blob(21)]
    cs = [kzg.blob_to_kzg_commitment(b) for b in blobs]
    proofs = [kzg.compute_blob_kzg_proof(b, c) for b, c in zip(blobs, cs)]
    sidecars = [
        SimpleNamespace(index=i, blob=b, kzg_commitment=c, kzg_proof=p)
        for i, (b, c, p) in enumerate(zip(blobs, cs, proofs))
    ]
    block = SimpleNamespace(
        message=SimpleNamespace(body=SimpleNamespace(blob_kzg_commitments=cs))
    )
    root = hashlib.sha256(b"block").digest()

    # block first: pending
    avail = checker.put_block(root, block)
    assert not avail.available
    # one blob: still pending
    avail = checker.put_blobs(root, sidecars[:1])
    assert not avail.available
    # second blob: complete — and non-destructive until the import pops it
    avail = checker.put_blobs(root, sidecars[1:])
    assert avail.available
    assert len(avail.blobs) == 2
    assert checker.has_pending(root)
    assert checker.check_availability(root).available  # re-checkable
    checker.pop(root)
    assert not checker.has_pending(root)

    # tampered proof rejected outright
    bad = SimpleNamespace(
        index=0, blob=blobs[0], kzg_commitment=cs[0], kzg_proof=proofs[1]
    )
    with pytest.raises(AvailabilityCheckError):
        checker.put_blobs(hashlib.sha256(b"other").digest(), [bad])

    # commitment mismatch vs block detected at completion; the poisoned
    # index is dropped so an honest re-send still completes the set
    root2 = hashlib.sha256(b"block2").digest()
    checker.put_block(root2, block)
    wrong_c = kzg.blob_to_kzg_commitment(_blob(99))
    proof_w = kzg.compute_blob_kzg_proof(_blob(99), wrong_c)
    mism = [
        SimpleNamespace(index=0, blob=_blob(99), kzg_commitment=wrong_c, kzg_proof=proof_w),
        sidecars[1],
    ]
    with pytest.raises(AvailabilityCheckError):
        checker.put_blobs(root2, mism)
    avail = checker.put_blobs(root2, sidecars[:1])  # honest recovery
    assert avail.available

    # finalization prune drops stale pending entries
    root3 = hashlib.sha256(b"stale").digest()
    checker.put_block(root3, block, slot=3)
    checker.prune_before(10)
    assert not checker.has_pending(root3)


# ---------------------------------------------------------------------------
# a device engine fails loudly: no host fallback, at init or per call
# ---------------------------------------------------------------------------


class _ExplodingDev:
    def msm(self, *args):
        raise RuntimeError("simulated device kernel failure")

    evaluate_batch = quotient = pairing_check = msm


@pytest.fixture
def exploding_kzg():
    k = Kzg(TrustedSetup.insecure_dev(N), device=False)
    k._dev = _ExplodingDev()
    return k


def test_device_failure_on_commit_raises(exploding_kzg):
    with pytest.raises(RuntimeError, match="simulated device"):
        exploding_kzg.blob_to_kzg_commitment(_blob(2))
    # the device engine stays configured: a failure never demotes it
    assert isinstance(exploding_kzg._dev, _ExplodingDev)
    assert exploding_kzg.verify_blob_kzg_proof_device_stats() == {"device": True}


def test_device_failure_on_batch_verify_raises(kzg, exploding_kzg):
    blobs = [_blob(i) for i in range(5, 7)]
    cs = [kzg.blob_to_kzg_commitment(b) for b in blobs]
    proofs = [kzg.compute_blob_kzg_proof(b, c) for b, c in zip(blobs, cs)]
    with pytest.raises(RuntimeError, match="simulated device"):
        exploding_kzg.verify_blob_kzg_proof_batch(blobs, cs, proofs)


def test_device_failure_at_init_raises(monkeypatch):
    import lighthouse_tpu.crypto.kzg as kzg_mod

    def refuse(setup):
        raise RuntimeError("simulated device init failure")

    monkeypatch.setattr(kzg_mod, "_DeviceKzg", refuse)
    with pytest.raises(RuntimeError, match="device init"):
        Kzg(TrustedSetup.insecure_dev(N), device=True)


def test_device_call_noop_when_no_device(kzg):
    # no device configured at all: the host engine serves the call
    assert kzg._dev is None
    assert kzg._device_call(lambda d: d.boom()) is None


def test_mainnet_setup_commits_x_to_tau():
    """p(X) = X, given by its evaluations on the bit-reversed domain,
    commits to [τ]G1: the ceremony's Lagrange points line up with the
    evaluation domain (e([C], G2) == e(G1, [τ]G2))."""
    from lighthouse_tpu.crypto.bls12_381 import (
        FQ,
        G1_GEN,
        G2_GEN,
        g1_from_bytes,
        pairing_check,
        pt_neg,
    )

    ts = TrustedSetup.default()
    if ts.n != 4096:
        pytest.skip("no ceremony file reachable")
    blob = b"".join(w.to_bytes(32, "big") for w in ts.roots_brp)
    c = g1_from_bytes(Kzg(ts).blob_to_kzg_commitment(blob))
    assert pairing_check([(c, G2_GEN), (pt_neg(FQ, G1_GEN), ts.g2_monomial[1])])
