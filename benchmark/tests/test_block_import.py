"""The import-full-blocks cell end to end on the CPU at a small size (2^14
validators: 4 committees of 128 a slot, a 3-block chain, 6 blobs), with
the program's host BLS backend standing in for the device: the
reference's post-state roots are the program's, a sound run is correct,
and each fault the cell can have, and the control, read not correct."""

import os
import time

import pytest

from benchmark import run as bench_run
from benchmark.traffic import passes

ROOT = bench_run.ROOT
SIZE = 1 << 14
SEED = 4_000_000_021


def _cell():
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = {w["name"]: w for w in bench["workloads"]}["import-full-blocks"]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = bench_run.load_json(os.path.join(ROOT, config["file"]))
    cfg["validators"] = SIZE
    mix = bench_run.load_json(os.path.join(bench_run.HERE, "traffic", f"{cell['traffic']}.json"))
    # three blocks: one to warm up, then the tampered copy of the second
    # and the chain's second and third
    mix.update(blocks=3, warmup_blocks=1, tamper_first=2, tamper_last=2)
    return cell, cfg, mix


def _run(seconds, fault=None, backend="host", seed=SEED):
    cell, cfg, mix = _cell()
    return bench_run.run_cell(cell, cfg, mix, seed, seconds, False, time.perf_counter(),
                              backend=backend, fault=fault)


def _correct(checks):
    return all(passes(c) for c in checks.values())


def test_sound_run_is_correct():
    # a short window: the tampered block is in flight when it ends and
    # gets its verdict while the run waits for late answers
    run = _run(0.01)
    assert _correct(run.checks), run.checks
    assert run.checks["tampered_rejected"]["value"] == 1


def _whole_chain(backend):
    run = _run(300.0, backend=backend)
    checks = dict(run.checks)
    # the window outlived the 3-block chain; every other check holds
    assert checks.pop("traffic_exhausted")["value"] == 1
    assert _correct(checks), checks
    # the warm-up block, the tampered copy, and the chain's next two
    assert checks["blocks_compared"]["value"] == 4
    assert run.blocks_imported == 2 and run.in_window == 3 * 7
    return run


def test_reference_roots_are_the_programs_on_the_host_backend():
    # every valid block imports (the program checks the reference's state
    # root in it), the head is the last block, and the node's head state
    # hashes, by the benchmark's SSZ, to the reference's root
    _whole_chain("host")


@pytest.mark.slow  # compiles the device verifier and KZG for the CPU: about 5 minutes
def test_reference_roots_are_the_programs_on_the_tpu_backend():
    run = _whole_chain("tpu")
    # each imported block's 11 sets (8 aggregates, the sync aggregate, the
    # proposal, the RANDAO reveal) are one device call inside its
    # signature batch; the tampered copy's call is not counted
    assert bench_run.read_metric("device_calls_per_block.import", run) == 1.0
    assert 0 < bench_run.read_metric("device_set_lane_fill.import", run) <= 100
    assert bench_run.read_metric("verify_wait_s_per_call.import", run) > 0


def _host_verifier_accepts_all(node):
    from lighthouse_tpu.crypto import bls as node_bls

    host = node_bls._BACKENDS["host"]
    host.verify_signature_sets = lambda sets, rng=None: True
    node._undo.append((host, "verify_signature_sets", None))


def _skip_kzg(node):
    checker = node.chain.data_availability_checker
    verify = checker.verify_blob_sidecars
    checker.verify_blob_sidecars = lambda scs, root, skip_kzg=False: verify(scs, root, True)


def _head_stays(node):
    chain = node.chain
    chain.recompute_head = lambda: chain.head_root


def _state_drifts(node):
    chain = node.chain
    process = chain.process_block

    def process_and_drift(block, *args, **kwargs):
        root = process(block, *args, **kwargs)
        chain._states[root].eth1_deposit_index += 1
        return root

    chain.process_block = process_and_drift


@pytest.mark.parametrize(
    "fault, broken",
    [(_host_verifier_accepts_all, "verdict_mismatch"), (_skip_kzg, "kzg_verifications_unobserved"),
     (_head_stays, "head_mismatch"), (_state_drifts, "state_root_mismatch")],
    ids=["tampered-accepted", "kzg-skipped", "head-stays", "state-drifts"],
)
def test_fault_is_not_correct(fault, broken):
    run = _run(0.01, fault=fault)
    assert not _correct(run.checks), run.checks
    assert run.checks[broken]["value"] > 0, run.checks


def test_control_is_not_correct():
    from benchmark.control import accept_every_signature

    run = _run(0.01, fault=accept_every_signature)
    assert run.checks["verdict_mismatch"]["value"] > 0
    assert run.checks["tampered_rejected"]["value"] == 0


def test_the_parent_program_is_refused(monkeypatch):
    # a program without the blob-proof counter fails at once, before boot
    from benchmark.traffic import block_import

    monkeypatch.setattr(block_import, "KZG_COUNTER", "no_such_counter_total")
    with pytest.raises(RuntimeError, match="cannot run this cell"):
        _run(0.01)

