"""The generator at a small size: distinct keys in every committee, the
seeded share of invalid messages, and signatures that the program's host
backend accepts exactly where the generator meant them to be valid."""

import os

import pytest

from benchmark import registry, spec
from benchmark.traffic import gossip_attestations as traffic
from benchmark import run as bench_run

N = 1 << 14


def _cfg():
    cfg = bench_run.load_json(
        os.path.join(bench_run.ROOT, "benchmark", "configs", "mainnet-1m-allsubnets.json")
    )
    cfg["validators"] = N
    return cfg


def _committees(cfg):
    return spec.Committees(N, 32, 64, 128, 90, spec.attester_seed(0, bytes.fromhex(cfg["randao_mix"])))


def test_keys_distinct_within_committees():
    cfg = _cfg()
    keys = registry.keys(cfg)
    committees = _committees(cfg)
    for c in range(committees.per_slot):
        members = committees.committee(5, c)
        assert len({keys[v, 0].tobytes() for v in members}) == len(members)


def test_invalid_share_and_host_verdicts():
    from lighthouse_tpu.crypto import bls as node_bls

    cfg = _cfg()
    keys = registry.keys(cfg)
    committees = _committees(cfg)
    anchor = b"\x11" * 32
    mix = {"invalid_one_in": 64}
    msgs = traffic.attestation_slot(cfg, mix, committees, anchor, 7, seed=2**40 + 5)
    assert len(msgs) == N // 32
    assert sum(not m.valid for m in msgs) == N // 32 // 64
    again = traffic.attestation_slot(cfg, mix, committees, anchor, 7, seed=2**40 + 5)
    assert [m.raw for m in again] == [m.raw for m in msgs]
    dom = traffic.domain(cfg)
    host = node_bls._BACKENDS["host"]
    checked = [m for m in msgs if not m.valid] + [m for m in msgs if m.valid][:6]
    for m in checked:
        (pos,), _, data, sig = spec.parse_attestation(m.raw)
        v = int(committees.committee(7, int.from_bytes(data[8:16], "little"))[pos])
        root = spec.signing_root(spec.attestation_data_root(data), dom)
        ok = host.verify(node_bls.Signature(sig), node_bls.PublicKey(keys[v, 0].tobytes()), root)
        assert ok == m.valid


def test_kinds_are_found_by_name():
    from benchmark import traffic as kinds

    assert kinds.load("gossip_attestations") is traffic
    with pytest.raises(ValueError):
        kinds.load("../run")


def test_device_checks():
    from benchmark.traffic import passes

    def ok(*counts):
        return all(passes(c) for c in traffic.device_checks(*counts).values())

    assert ok(130, 130, 130) and ok(130, 130, 40)
    assert not ok(130, 0, 0)  # the verifier ran where the observer could not see it
    assert not ok(0, 0, 0)  # no device batch at all
