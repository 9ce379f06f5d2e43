"""The readers of what the program recorded by itself (its trace spans and
its program builds), on hand-made spans and build events."""

import importlib.util
import os
from types import SimpleNamespace

import pytest

from lighthouse_tpu.metrics import REGISTRY, trace_collector
from lighthouse_tpu.utils import compile_cache
from lighthouse_tpu.utils.tracing import Span

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = (
    "verify_host_s_per_call.att", "verify_wait_s_per_call.att", "gossip_host_s_per_msg.att",
    "device_set_lane_fill.att", "window_programs_built.att", "setup_program_build_s.att",
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


def _verify(t0, sets, wait_a, wait_b):
    """A 1 s device batch verification with its two waits."""
    return _span("bls_device_verify", t0, 1.0, [
        _span("bls_device_decode", t0, 0.05),
        _span("bls_device_subgroup", t0 + 0.05, 0.2, [_span("bls_device_wait", t0 + 0.1, wait_a)]),
        _span("bls_device_pairing", t0 + 0.5, 0.5, [_span("bls_device_wait", t0 + 0.6, wait_b)]),
    ], sets=sets, lanes=32, keys=16)


def _batch(t0, sets, fallback=False):
    stages = [_span("attestation_prepare", t0, 0.01), _verify(t0 + 0.01, sets, 0.1, 0.3)]
    if fallback:
        stages.append(_span("attestation_fallback", t0 + 1.01, 0.5))
    stages.append(_span("attestation_apply", t0 + 1.6, 0.02, [_span("import_lock_wait", t0 + 1.6, 0.001)]))
    return _span("attestation_batch", t0, 1.7, stages, n=sets)


@pytest.fixture
def collector(monkeypatch):
    c = trace_collector.TraceCollector(ring_size=16)
    monkeypatch.setattr(trace_collector, "COLLECTOR", c)
    return c


def _run():
    return SimpleNamespace(seconds=10.0, t0=10.0, t_end=20.0, in_window=40)


def test_span_readers(collector):
    collector.record(_batch(5.0, 16))  # set-up: before the window
    for t0, sets, bad in ((10.5, 16, False), (13.0, 8, True), (18.0, 16, False)):
        collector.record(_batch(t0, sets, bad))
    collector.record(_batch(20.5, 16))  # after the window
    run = _run()
    # three calls in the window, each 1 s with 0.4 s waiting
    assert read("verify_wait_s_per_call.att", run) == pytest.approx(0.4)
    assert read("verify_host_s_per_call.att", run) == pytest.approx(0.6)
    assert read("device_set_lane_fill.att", run) == pytest.approx(100.0 * 40 / 96)
    # prepare 0.01 and apply 0.02 a batch, one fallback of 0.5, 40 verdicts
    assert read("gossip_host_s_per_msg.att", run) == pytest.approx((3 * 0.03 + 0.5) / 40)


def test_span_readers_read_the_newest_trees_of_a_ring_that_lost_the_window(collector):
    # the ring holds 16 trees, all of them closed inside the window: the
    # readers read those 16, each a ratio over what it read
    for i in range(20):
        collector.record(_batch(10.0 + 0.4 * i, 8, fallback=i < 4))
    run = _run()
    assert read("verify_wait_s_per_call.att", run) == pytest.approx(0.4)
    assert read("verify_host_s_per_call.att", run) == pytest.approx(0.6)
    assert read("device_set_lane_fill.att", run) == pytest.approx(25.0)
    # the four batches with a fallback were the oldest, and are gone
    assert read("gossip_host_s_per_msg.att", run) == pytest.approx(0.03 / 8)


def test_span_readers_without_the_programs_spans(collector):
    collector.record(_span("attestation_batch", 5.0, 1.0))
    collector.record(_span("attestation_batch", 12.0, 1.0))
    for name in NEW[:4]:
        assert read(name, _run()) is None


def test_build_readers(monkeypatch):
    events = [
        (1.0, "trace", 2.0, "jit(a)"), (3.0, "compile", 5.0, "jit(a)"),
        (12.0, "compile", 0.25, "jit(late)"),  # in the window
        (25.0, "compile", 0.5, "jit(after)"),
    ]
    monkeypatch.setattr(compile_cache, "program_builds", lambda: list(events))
    counter = REGISTRY.counter("jax_program_build_seconds_total")
    monkeypatch.setattr(counter, "_values", {(("phase", "trace"),): 2.0, (("phase", "compile"),): 5.75})
    run = _run()
    assert read("window_programs_built.att", run) == 1
    assert read("setup_program_build_s.att", run) == pytest.approx(7.0)
    # a full log whose oldest event is in the window may have lost some
    monkeypatch.setattr(compile_cache, "BUILD_LOG_SIZE", 2)
    monkeypatch.setattr(compile_cache, "program_builds", lambda: list(events[2:]))
    assert read("window_programs_built.att", run) is None
    assert read("setup_program_build_s.att", run) is None


def test_build_readers_without_the_listeners(monkeypatch):
    monkeypatch.delattr(compile_cache, "program_builds")
    assert read("window_programs_built.att", _run()) is None
    assert read("setup_program_build_s.att", _run()) is None
