"""The readers of the import-full-blocks cell's per-layer metrics, on
hand-made span trees of the program's block import and sidecar checks."""

import importlib.util
import os
from types import SimpleNamespace

import pytest

from lighthouse_tpu.metrics import trace_collector
from lighthouse_tpu.utils.tracing import Span

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = (
    "sig_batch_s_per_block.import", "transition_host_s_per_block.import",
    "state_root_s_per_block.import", "blob_verify_s_per_block.import",
    "store_write_s_per_block.import", "head_update_s_per_block.import",
    "device_calls_per_block.import", "verify_host_s_per_call.import",
    "verify_wait_s_per_call.import", "device_set_lane_fill.import",
)


def read(name, run):
    spec = importlib.util.spec_from_file_location(name, os.path.join(HERE, "metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(run)


def _span(name, t0, duration, children=(), **fields):
    s = Span(name, **fields)
    s.t0, s.duration_s = t0, duration
    s.children = list(children)
    return s


def _verify(t0, sets, wait):
    """A 0.5 s device batch verification with its two waits."""
    return _span("bls_device_verify", t0, 0.5, [
        _span("bls_device_subgroup", t0, 0.1, [_span("bls_device_wait", t0, wait / 2)]),
        _span("bls_device_pairing", t0 + 0.2, 0.3, [_span("bls_device_wait", t0 + 0.2, wait / 2)]),
    ], sets=sets, lanes=32, keys=512)


def _import(t0, imported=True):
    """A 6 s block import: transition 4 s (signature batch 2.5 s of 5
    device calls for 131 sets, state root 0.5 s), fork choice 0.25 s,
    store 0.5 s, head 0.75 s."""
    calls = [_verify(t0 + 0.3 + 0.5 * i, 32 if i < 4 else 3, 0.1) for i in range(5)]
    transition = _span("state_transition", t0 + 0.1, 4.0, [
        _span("signature_set_assembly", t0 + 0.1, 0.2),
        _span("signature_batch_verify", t0 + 0.3, 2.5, calls),
        _span("import_state_root", t0 + 3.5, 0.5),
    ])
    if not imported:  # rejected in its signature batch
        return _span("block_import", t0, 2.9, [_span("import_da_gate", t0, 0.05), transition])
    return _span("block_import", t0, 6.0, [
        _span("import_da_gate", t0, 0.05), transition,
        _span("fork_choice_on_block", t0 + 4.1, 0.25),
        _span("import_store_write", t0 + 4.4, 0.5),
        _span("import_head_update", t0 + 5.0, 0.75),
    ])


def _check(t0):
    return _span("blob_sidecar_verify", t0, 0.4, [
        _span("blob_header_signature", t0, 0.25), _span("blob_kzg_verify", t0 + 0.25, 0.15),
    ], n=1)


@pytest.fixture
def collector(monkeypatch):
    c = trace_collector.TraceCollector(ring_size=64)
    monkeypatch.setattr(trace_collector, "COLLECTOR", c)
    return c


def _run(**kw):
    counters = ({"bls_device_batches_total": 10, "bls_device_sets_total": 0},
                {"bls_device_batches_total": 32, "bls_device_sets_total": 0})
    base = dict(seconds=50.0, t0=10.0, t_end=60.0, counters=counters, blocks_imported=2,
                sidecars_per_block=6, reduced=None)
    base.update(kw)
    return SimpleNamespace(**base)


def test_readers_per_imported_block(collector):
    collector.record(_import(2.0))  # the warm-up, before the window
    collector.record(_span("block_import", 11.0, 0.01, [_span("import_da_gate", 11.0, 0.01)]))
    for i in range(6):
        collector.record(_check(11.1 + 0.5 * i))
    collector.record(_import(14.0))
    collector.record(_import(21.0, imported=False))  # the tampered copy
    collector.record(_import(25.0))
    run = _run()
    assert read("sig_batch_s_per_block.import", run) == pytest.approx(2.5)
    assert read("transition_host_s_per_block.import", run) == pytest.approx(1.5)
    assert read("state_root_s_per_block.import", run) == pytest.approx(0.5)
    assert read("store_write_s_per_block.import", run) == pytest.approx(0.5)
    assert read("head_update_s_per_block.import", run) == pytest.approx(1.0)
    assert read("blob_verify_s_per_block.import", run) == pytest.approx(6 * 0.4)
    # the 5 calls of each imported block's batch; the tampered copy's 5
    # and the sidecar checks' calls outside a batch are not counted
    collector.record(_verify(30.0, 1, 0.05))
    assert read("device_calls_per_block.import", run) == pytest.approx(5.0)
    assert read("verify_wait_s_per_call.import", run) == pytest.approx(0.1)
    assert read("verify_host_s_per_call.import", run) == pytest.approx(0.4)
    assert read("device_set_lane_fill.import", run) == pytest.approx(100.0 * 131 / 160)


def test_readers_without_the_programs_spans(collector):
    # a program without the import's stage spans (the parent of this
    # cell) records block_import trees with no store write inside
    collector.record(_span("block_import", 12.0, 5.0, [_span("state_transition", 12.0, 4.0)]))
    run = _run(blocks_imported=0)
    for name in NAMES:
        assert read(name, run) is None, name


@pytest.mark.parametrize("listeners", [True, False])
def test_build_readers(monkeypatch, listeners):
    from lighthouse_tpu.metrics import REGISTRY
    from lighthouse_tpu.utils import compile_cache

    events = [
        (1.0, "trace", 2.0, "jit(a)"), (3.0, "compile", 5.0, "jit(a)"),
        (12.0, "compile", 0.25, "jit(late)"),  # in the window
        (65.0, "compile", 0.5, "jit(after)"),
    ]
    if listeners:
        monkeypatch.setattr(compile_cache, "program_builds", lambda: list(events))
    else:  # a program without the compile listeners
        monkeypatch.delattr(compile_cache, "program_builds")
    counter = REGISTRY.counter("jax_program_build_seconds_total")
    monkeypatch.setattr(counter, "_values", {(("phase", "trace"),): 2.0, (("phase", "compile"),): 5.75})
    run = _run()
    built = read("window_programs_built.import", run)
    setup = read("setup_program_build_s.import", run)
    if listeners:
        assert built == 1 and setup == pytest.approx(7.0)
    else:
        assert built is None and setup is None


@pytest.mark.parametrize("ops, idle", [({}, 100.0), ({"/device:TPU:0": [(0, 100, "p")]}, 50.0)])
def test_idle_share_reader(ops, idle):
    from benchmark import trace

    # a 200 ns traced sub-window: no device operation in it reads 100,
    # one busy for half of it reads 50
    reduced = trace.Reduced(window=(0, 200), ops=ops)
    assert read("device_idle_share.import", _run(reduced=reduced)) == pytest.approx(idle)
    assert read("device_idle_share.import", _run()) is None  # untraced
