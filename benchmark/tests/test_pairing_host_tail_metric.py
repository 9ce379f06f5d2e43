"""The reader of the pairing check's host tail, on hand-made span trees."""

import importlib.util
import os
from types import SimpleNamespace

import pytest

from lighthouse_tpu.metrics import trace_collector
from lighthouse_tpu.utils.tracing import Span

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "pairing_host_tail_s_per_call.att"


def read(run):
    spec = importlib.util.spec_from_file_location(NAME, os.path.join(HERE, "metrics", f"{NAME}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(run)


def _span(name, t0, duration, children=(), **fields):
    s = Span(name, **fields)
    s.t0, s.duration_s = t0, duration
    s.children = list(children)
    return s


def _verify(t0, tail=None):
    """A 1 s device batch verification; its pairing stage waits 0.1 s on
    the Miller lanes, then spends `tail` seconds on the host (no tail span
    where `tail` is None)."""
    pairing = [_span("bls_device_wait", t0 + 0.6, 0.1)]
    if tail is not None:
        pairing.append(_span("bls_pairing_host_tail", t0 + 0.7, tail, lanes=33))
    return _span("bls_device_verify", t0, 1.0, [
        _span("bls_device_subgroup", t0, 0.2, [_span("bls_device_wait", t0 + 0.1, 0.05)]),
        _span("bls_device_pairing", t0 + 0.5, 0.5, pairing),
    ], sets=16, lanes=32, keys=16)


def _batch(t0, tail=None):
    return _span("attestation_batch", t0, 1.1, [_verify(t0 + 0.01, tail)], n=16)


@pytest.fixture
def collector(monkeypatch):
    c = trace_collector.TraceCollector(ring_size=16)
    monkeypatch.setattr(trace_collector, "COLLECTOR", c)
    return c


def _run():
    return SimpleNamespace(seconds=10.0, t0=10.0, t_end=20.0, in_window=48)


@pytest.mark.parametrize(
    "tails, want",
    [
        # every call in the window ends in a host tail
        ((0.02, 0.03, 0.04), 0.03),
        # a call that returned before its pairing check (a signature out
        # of the subgroup) counts as a call without a tail
        ((0.02, None, 0.04), 0.02),
    ],
)
def test_tail_seconds_per_call_in_the_window(collector, tails, want):
    collector.record(_batch(5.0, 1.0))  # set-up: before the window
    for t0, tail in zip((10.5, 13.0, 18.0), tails):
        collector.record(_batch(t0, tail))
    collector.record(_batch(20.5, 1.0))  # after the window
    assert read(_run()) == pytest.approx(want)


def test_a_program_without_the_tail_reads_nothing(collector):
    for t0 in (10.5, 13.0):
        collector.record(_batch(t0))
    assert read(_run()) is None
    collector.record(_span("attestation_batch", 12.0, 1.0))
    assert read(SimpleNamespace(seconds=10.0, t0=30.0, t_end=40.0, in_window=0)) is None
