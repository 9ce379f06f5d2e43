"""_TpuBackend batch-verification routing: the FULL device verifier is
the only path, batches are chunked to bounded shapes, a failing chunk
fails the batch, and a kernel failure raises instead of falling back.
Kernel correctness itself is covered by the device suites; this pins the
wiring."""

import pytest

import lighthouse_tpu.ops.bls381_verify as ops_full
from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls import _TpuBackend


@pytest.fixture
def backend():
    return _TpuBackend()


def _sets(n):
    kps = bls.interop_keypairs(2)
    msg = b"\x11" * 32
    sig = kps[0].sk.sign(msg)
    return [bls.SignatureSet(sig, [kps[0].pk], msg) for _ in range(n)]


def test_full_path_chunks_batches(backend, monkeypatch):
    calls = []

    def fake_full(sets, rng=None):
        calls.append(len(sets))
        return True

    monkeypatch.setattr(
        ops_full, "verify_signature_sets_device_full", fake_full
    )
    monkeypatch.setattr(bls, "DEFAULT_DEVICE_CHUNK", 4)
    assert backend.verify_signature_sets(_sets(10)) is True
    assert calls == [4, 4, 2]  # bounded shapes, full coverage


def test_failing_chunk_fails_the_batch(backend, monkeypatch):
    calls = []

    def fake_full(sets, rng=None):
        calls.append(len(sets))
        return len(calls) != 2  # second chunk reports an invalid set

    monkeypatch.setattr(
        ops_full, "verify_signature_sets_device_full", fake_full
    )
    monkeypatch.setattr(bls, "DEFAULT_DEVICE_CHUNK", 3)
    assert backend.verify_signature_sets(_sets(9)) is False
    assert len(calls) == 2  # short-circuits after the failing chunk


def test_kernel_failure_raises(backend, monkeypatch):
    host_calls = []

    def exploding_full(sets, rng=None):
        raise RuntimeError("device kernel failed to compile")

    monkeypatch.setattr(
        ops_full, "verify_signature_sets_device_full", exploding_full
    )
    monkeypatch.setattr(
        bls._HostBackend,
        "verify_signature_sets",
        lambda self, sets, rng=None: host_calls.append(len(sets)) or True,
    )
    with pytest.raises(RuntimeError, match="failed to compile"):
        backend.verify_signature_sets(_sets(5))
    assert host_calls == []  # the host never served the batch


def test_empty_batch_uses_host_semantics(backend):
    host = bls._BACKENDS["host"]
    assert backend.verify_signature_sets([]) == host.verify_signature_sets([])
