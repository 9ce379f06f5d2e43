"""The harness end to end on the CPU at a small size, with the program's
host BLS backend standing in for the device: a sound run is correct, the
control and each fault the cell can have are not, and without a TPU the
command refuses to run."""

import os
import subprocess
import sys
import time

import pytest

from benchmark import run as bench_run
from benchmark.traffic import passes

ROOT = bench_run.ROOT
SIZE = 1 << 14  # 4 committees of 128 per slot, 512 attestations


def _cell(invalid_one_in=16):
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = {w["name"]: w for w in bench["workloads"]}["att-backlog"]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = bench_run.load_json(os.path.join(ROOT, config["file"]))
    cfg["validators"] = SIZE
    mix = bench_run.load_json(os.path.join(bench_run.HERE, "traffic", "att-backlog.json"))
    # enough invalid messages that every fault meets one in a short window
    mix["invalid_one_in"] = invalid_one_in
    mix["outstanding"] = 64
    return bench, cell, cfg, mix


def _run(seed, fault=None, seconds=3.0, invalid_one_in=16):
    bench, cell, cfg, mix = _cell(invalid_one_in)
    run = bench_run.run_cell(cell, cfg, mix, seed, seconds, False, time.perf_counter(),
                             backend="host", fault=fault)
    return run.checks, run


def _correct(checks):
    return all(passes(c) for c in checks.values())


def test_sound_run_is_correct():
    checks, run = _run(4_000_000_001)
    assert _correct(checks), checks
    assert run.compared["compared_invalid"] >= 2
    assert run.compared["compared_batches"] >= 2
    assert checks["anchor_root_mismatch"]["value"] == 0


def test_sampled_reference_is_correct(monkeypatch):
    # beyond MAX_COMPARED messages the reference takes a seeded sample of
    # the device batches, each with every message in it
    from benchmark.traffic import gossip_attestations

    monkeypatch.setattr(gossip_attestations, "MAX_COMPARED", 100)
    checks, run = _run(4_000_000_004)
    assert _correct(checks), checks
    assert 1 <= run.compared["compared_batches"] < len(run.calls)


def _host_verifier(make):
    def fault(node):
        from lighthouse_tpu.crypto import bls as node_bls

        host = node_bls._BACKENDS["host"]
        host.verify_signature_sets = make(host.verify_signature_sets)
        node._undo.append((host, "verify_signature_sets", None))

    return fault


def _accept_all(verify):
    return lambda sets, rng=None: True


def _reject_all(verify):
    return lambda sets, rng=None: False


def _half_batch(verify):
    def half(sets, rng=None):
        sets = list(sets)
        return verify(sets[: max(1, len(sets) // 2)], rng)

    return half


def _lose_a_verdict(node):
    router = node.net.gossip
    batch = router._run_queued_batch
    router._run_queued_batch = lambda works: batch(works[:-1])


def _process_nothing(node):
    node.chain.process_attestation_batch = lambda atts: [None] * len(atts)


@pytest.mark.parametrize(
    "fault",
    [_host_verifier(_accept_all), _host_verifier(_reject_all),
     _host_verifier(_half_batch), _lose_a_verdict, _process_nothing],
    ids=["answer-altered-accept", "answer-altered-reject", "half-batch-left-out",
         "verdict-lost", "step-does-nothing"],
)
def test_fault_is_not_correct(fault):
    # one message in four invalid: a batch that checks only half its sets
    # then always meets an invalid one it skipped
    checks, _ = _run(4_000_000_002, fault=fault, invalid_one_in=4)
    assert not _correct(checks), checks


def test_control_is_not_correct():
    from benchmark.control import accept_every_signature

    checks, _ = _run(4_000_000_003, fault=accept_every_signature)
    assert checks["verdict_mismatch"]["value"] > 0


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "att-backlog",
         "--seed", "1", "--seconds", "10", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""
