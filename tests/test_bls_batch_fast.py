"""Differential + adversarial tests for the MSM fast batch-verify path.

The fast path (`_HostBackend.verify_signature_sets`: Pippenger MSMs,
bilinearity regrouping, fork-pool Miller loops) is pinned against the
retained serial per-set loop (`verify_signature_sets_serial`) — the same
oracle discipline as test_pairing_fast.py and test_msm.py. The adversarial
case the RLC argument must hold for: ONE tampered signature or pubkey in a
1024-set batch flips the whole batch to invalid, at every pool size.
"""

import random
import time

import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls12_381 import FQ2, hash_to_g2, pt_mul
from lighthouse_tpu.metrics import REGISTRY
from lighthouse_tpu.parallel import host_pool

rng = random.Random(0x5E7)


@pytest.fixture(autouse=True)
def host_backend():
    bls.set_backend("host")
    host_pool.reset_pool()
    yield
    host_pool.reset_pool()
    bls.set_backend("host")


HOST = bls._BACKENDS["host"]


def _random_batch(n_sets, n_keys=5, n_msgs=3, committee_max=3):
    """Batches with repeated messages AND repeated committees, so both
    regrouping factorizations (by-message / by-pubkeys) get exercised."""
    kps = bls.interop_keypairs(n_keys)
    sets = []
    for _ in range(n_sets):
        m = bytes([rng.randrange(n_msgs)]) * 32
        members = rng.sample(kps, rng.randrange(1, committee_max + 1))
        agg = bls.AggregateSignature.from_signatures(
            [kp.sk.sign(m) for kp in members]
        ).to_signature()
        sets.append(bls.SignatureSet(agg, [kp.pk for kp in members], m))
    return sets


def test_fast_agrees_with_serial_on_random_batches():
    for trial in range(4):
        sets = _random_batch(rng.randrange(1, 9))
        seed = 100 + trial
        serial = HOST.verify_signature_sets_serial(sets, random.Random(seed))
        fast = HOST.verify_signature_sets(sets, random.Random(seed))
        assert serial is True and fast is True


def test_fast_agrees_with_serial_on_tampered_batches():
    sets = _random_batch(6)
    variants = []
    # wrong signature: the same committee's valid signature over a DIFFERENT
    # message (a valid subgroup point, so only the pairing product catches it)
    kps = bls.interop_keypairs(5)
    by_bytes = {kp.pk.to_bytes(): kp for kp in kps}
    members = [by_bytes[pk.to_bytes()] for pk in sets[2].pubkeys]
    wrong_sig = bls.AggregateSignature.from_signatures(
        [kp.sk.sign(b"\xEE" * 32) for kp in members]
    ).to_signature()
    assert wrong_sig != sets[2].signature
    v = list(sets)
    v[2] = bls.SignatureSet(wrong_sig, v[2].pubkeys, v[2].message)
    variants.append(v)
    # wrong pubkey
    other = bls.interop_keypairs(9)[-1].pk
    v = list(sets)
    v[4] = bls.SignatureSet(v[4].signature, [other], v[4].message)
    variants.append(v)
    # wrong message
    v = list(sets)
    v[1] = bls.SignatureSet(v[1].signature, v[1].pubkeys, b"\xEE" * 32)
    variants.append(v)
    for i, v in enumerate(variants):
        assert HOST.verify_signature_sets_serial(v, random.Random(i)) is False
        assert HOST.verify_signature_sets(v, random.Random(i)) is False


def test_fast_rejects_structurally_invalid_sets():
    kp = bls.interop_keypairs(1)[0]
    m = b"\x01" * 32
    good = bls.SignatureSet(kp.sk.sign(m), [kp.pk], m)
    # infinity signature
    assert (
        HOST.verify_signature_sets(
            [good, bls.SignatureSet(bls.Signature.empty(), [kp.pk], m)], None
        )
        is False
    )
    # empty pubkey list
    assert (
        HOST.verify_signature_sets(
            [good, bls.SignatureSet(kp.sk.sign(m), [], m)], None
        )
        is False
    )
    # infinity pubkey encoding
    inf_pk = bls.PublicKey(bls.INFINITY_PUBLIC_KEY)
    assert (
        HOST.verify_signature_sets(
            [good, bls.SignatureSet(kp.sk.sign(m), [inf_pk], m)], None
        )
        is False
    )
    # malformed signature bytes (not on curve)
    bad_sig = bls.Signature(bytes([0x80]) + bytes(95))
    assert (
        HOST.verify_signature_sets(
            [good, bls.SignatureSet(bad_sig, [kp.pk], m)], None
        )
        is False
    )
    # non-subgroup signature is caught by the worker's subgroup check
    assert good.signature.subgroup_check()
    # empty batch
    assert HOST.verify_signature_sets([], None) is False


def _thousand_sets():
    """1024 single-key sets over one shared message: small secret keys make
    generation ~1k cheap ladders, and the shared message keeps hash_to_g2
    out of the runtime (this shape drives the G1-side MSM; the gossip
    shape of distinct messages drives the G2 side)."""
    m = b"\xA7" * 32
    h = hash_to_g2(m)
    sets = []
    for i in range(1024):
        sk = bls.SecretKey(2 + i)
        pk = sk.public_key()
        sig = bls.Signature.from_point(pt_mul(FQ2, h, sk.scalar))
        sets.append(bls.SignatureSet(sig, [pk], m))
    return sets


def test_tampered_item_in_1k_batch_fails_across_pool_sizes(monkeypatch):
    sets = _thousand_sets()
    sig_tamper = list(sets)
    # swap two honest signatures: each is a valid G2 subgroup point, so only
    # the RLC pairing product can catch it
    sig_tamper[517] = bls.SignatureSet(
        sets[518].signature, sets[517].pubkeys, sets[517].message
    )
    pk_tamper = list(sets)
    pk_tamper[901] = bls.SignatureSet(
        sets[901].signature, [sets[902].pubkeys[0]], sets[901].message
    )
    for size in ("0", "4"):
        monkeypatch.setenv(host_pool.ENV_VAR, size)
        host_pool.reset_pool()
        assert bls.verify_signature_sets(sets, random.Random(7)) is True, size
        assert (
            bls.verify_signature_sets(sig_tamper, random.Random(7)) is False
        ), size
        assert (
            bls.verify_signature_sets(pk_tamper, random.Random(7)) is False
        ), size


@pytest.mark.perf_smoke
def test_64_set_batch_verify_engages_msm_within_budget():
    """64-set host batch verify under a generous wall-clock budget, with
    the MSM path provably engaged: the bls_msm_g2 span fires and the
    per-set serial loop (path="serial") is never taken."""
    sets = _random_batch(64, n_keys=8, n_msgs=6)
    msm_hist = REGISTRY.histogram("trace_span_seconds_bls_msm_g2")
    pair_hist = REGISTRY.histogram("trace_span_seconds_bls_parallel_pairing")
    path_counter = REGISTRY.counter("bls_batch_verify_total")
    msm_count0 = msm_hist.count
    pair_count0 = pair_hist.count
    serial0 = path_counter.value(path="serial")
    msm0 = path_counter.value(path="msm")

    bls.verify_signature_sets(sets, random.Random(11))  # warm caches/tables
    t0 = time.perf_counter()
    assert bls.verify_signature_sets(sets, random.Random(12)) is True
    elapsed = time.perf_counter() - t0

    assert msm_hist.count >= msm_count0 + 2  # MSM stage ran both times
    assert pair_hist.count >= pair_count0 + 2
    assert path_counter.value(path="msm") == msm0 + 2
    assert path_counter.value(path="serial") == serial0  # no fallback
    # generous bound: warm-path cost is ~6 Miller loops + 3 small MSMs
    # (~0.2 s measured on the 1-core CI image); 20× headroom for load
    assert elapsed < 4.0, f"64-set batch verify took {elapsed:.2f}s"
