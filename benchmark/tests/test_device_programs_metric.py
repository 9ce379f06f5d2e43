"""The readers of the device programs a batch verification launched, on
hand-made span trees: the gossip cell's calls in the window, and the
calls in the signature batches of the blocks imported in the window."""

import importlib.util
import os
from types import SimpleNamespace

import pytest

from lighthouse_tpu.metrics import trace_collector
from lighthouse_tpu.utils.tracing import Span

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def _verify(t0, programs):
    """A 1 s device batch verification that launched `programs` device
    programs (no such field where `programs` is None, as before the
    program counted them)."""
    fields = {"sets": 16, "lanes": 32, "keys": 16}
    if programs is not None:
        fields["programs"] = programs
    wait = _span("bls_device_wait", t0 + 0.6, 0.1)
    return _span("bls_device_verify", t0, 1.0, [_span("bls_device_pairing", t0 + 0.5, 0.5, [wait])], **fields)


def _gossip_batch(t0, programs):
    return _span("attestation_batch", t0, 1.1, [_verify(t0 + 0.01, programs)], n=16)


def _block(t0, programs, imported=True):
    batch = _span("signature_batch_verify", t0 + 0.1, 2.0, [
        _verify(t0 + 0.1 + i, p) for i, p in enumerate(programs)
    ], sets=131)
    transition = _span("state_transition", t0, 2.5, [batch])
    children = [transition] + ([_span("import_store_write", t0 + 2.6, 0.01)] if imported else [])
    return _span("block_import", t0, 3.0, children)


@pytest.fixture
def collector(monkeypatch):
    c = trace_collector.TraceCollector(ring_size=16)
    monkeypatch.setattr(trace_collector, "COLLECTOR", c)
    return c


def _run():
    return SimpleNamespace(seconds=10.0, t0=10.0, t_end=20.0, in_window=48)


def test_gossip_calls_in_the_window(collector):
    collector.record(_gossip_batch(5.0, 300))  # set-up: before the window
    for t0, programs in zip((10.5, 13.0, 18.0), (7, 7, 1)):
        collector.record(_gossip_batch(t0, programs))
    collector.record(_gossip_batch(20.5, 300))  # after the window
    assert read("device_programs_per_call.att", _run()) == pytest.approx(5.0)


def test_import_calls_of_imported_blocks(collector):
    collector.record(_block(11.0, (7, 7, 7)))
    collector.record(_block(14.0, (1,), imported=False))  # a rejected block
    collector.record(_block(16.0, (7, 7)))
    assert read("device_programs_per_call.import", _run()) == pytest.approx(7.0)


@pytest.mark.parametrize("name", ["device_programs_per_call.att", "device_programs_per_call.import"])
def test_a_program_without_the_field_reads_nothing(collector, name):
    collector.record(_gossip_batch(10.5, None))
    collector.record(_block(12.0, (None, None)))
    assert read(name, _run()) is None
