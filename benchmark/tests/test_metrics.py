"""The metric readers' arithmetic on a hand-made run record."""

import importlib.util
import os
from types import SimpleNamespace

import pytest

from benchmark import trace
from benchmark.node import DeviceCall

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(name, run):
    spec = importlib.util.spec_from_file_location(name, os.path.join(HERE, "metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(run)


def _run(reduced=None):
    calls = [
        DeviceCall(10.0, 11.0, 32, 1, 20, [], True),
        DeviceCall(10.5, 12.0, 32, 1, 20, [], True),  # overlaps the first
        DeviceCall(15.0, 15.5, 8, 1, 8, [], False),
        DeviceCall(21.0, 22.0, 32, 1, 20, [], True),  # after the window
    ]
    return SimpleNamespace(
        seconds=10.0, t0=10.0, t_end=20.0, setup_s=42.0, in_window=200,
        calls=calls, counters=({"bls_device_batches_total": 5, "bls_device_sets_total": 100},
                               {"bls_device_batches_total": 9, "bls_device_sets_total": 204}),
        reduced=reduced,
    )


def test_end_to_end_readers():
    run = _run()
    assert read("setup_s", run) == 42.0
    assert read("gossip_msgs_per_s", run) == 20.0


def test_host_clock_readers():
    run = _run()
    assert read("verify_call_s.att", run) == pytest.approx((1.0 + 1.5 + 0.5) / 3)
    # covered: [10, 12) and [15, 15.5) = 2.5 s of 10
    assert read("host_s_per_msg.att", run) == pytest.approx(7.5 / 200)
    assert read("device_sets_per_call.att", run) == 26.0


def test_trace_readers():
    assert read("device_idle_share.att", _run()) is None
    red = trace.Reduced(window=(0, 1_000_000_000))
    red.ops["/device:TPU:0"] = [(100_000_000, 350_000_000, "m"), (600_000_000, 850_000_000, "m")]
    red.spans = [("bench.verify_call", 50_000_000, 400_000_000, {"sets": 32, "keys": 1, "msgs": 20}),
                 ("bench.verify_call", 900_000_000, 1_100_000_000, {"sets": 32, "keys": 1, "msgs": 20})]
    run = _run(red)
    assert read("device_idle_share.att", run) == pytest.approx(50.0)

