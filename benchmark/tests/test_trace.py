"""The trace reduction: busy time, idle gaps and kernel time, on hand-made
intervals and on a trace recorded from a tiny jitted program."""

import jax
import jax.numpy as jnp
import pytest

from benchmark import trace


def _reduced():
    # one device, window [0, 100): ops [10, 30) and [20, 40) overlap,
    # [60, 70) alone; host spans cover the gaps
    red = trace.Reduced(window=(0, 100))
    red.ops["/device:TPU:0"] = [(10, 30, "jit_a"), (20, 40, "jit_a"), (60, 70, "jit_b")]
    red.spans = [
        ("bench.attestation_batch", 0, 100, {}),
        ("bench.verify_call", 6, 75, {"sets": 32, "keys": 1, "msgs": 20}),
    ]
    return red


def test_busy_union_and_gaps():
    red = _reduced()
    assert trace.union(red.ops["/device:TPU:0"], 0, 100) == [[10, 40], [60, 70]]
    assert trace.union(red.ops["/device:TPU:0"], 15, 65) == [[15, 40], [60, 65]]
    assert trace.busy_s(red) == pytest.approx(40e-9)
    bd = trace.breakdown(red)
    assert bd["device_ops"] == [["jit_a", pytest.approx(40e-9)], ["jit_b", pytest.approx(10e-9)]]
    # gaps: [70,100) 30 outside the call, [40,60) 20 and [0,10) 10 ...
    assert [g[1] for g in bd["idle_gaps"]] == pytest.approx([30e-9, 20e-9, 10e-9])
    assert [g[0] for g in bd["idle_gaps"]] == [
        "bench.attestation_batch", "bench.verify_call", "bench.attestation_batch"
    ]


def test_host_spans_name_the_gaps():
    red = trace.Reduced(window=(1_000, 1_100))
    red.ops["/device:TPU:0"] = [(1_010, 1_040, "jit_a")]
    # host clock 5.0 s is the trace's 1_000 ns; the span began before it
    trace.add_host_spans(red, [("bench.verify_call", 4.9, 5.00000005)], 5.0)
    assert red.spans[0][1:3] == (pytest.approx(1_000 - 1e8), pytest.approx(1_050))
    gaps = trace.breakdown(red)["idle_gaps"]
    assert gaps[0][0] == "no benchmark span" and gaps[1][0] == "bench.verify_call"


def test_device_time_within_spans():
    red = _reduced()
    assert trace.device_time_within(red, [("x", 25, 65, {})]) == pytest.approx(20e-9)


def _cpu_programs(plane, line):
    # on the CPU, programs run on the XLA client's host threads
    return plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient")


def test_extract_from_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=trace.profile_options())
    with jax.profiler.TraceAnnotation("bench.traced"):
        with jax.profiler.TraceAnnotation("bench.verify_call#sets=32,keys=1,msgs=20#"):
            for _ in range(3):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    red = trace.extract(trace.latest_xplane(str(tmp_path)), is_device=_cpu_programs)
    assert red.window_s > 0
    (call,) = [s for s in red.spans if s[0] == "bench.verify_call"]
    assert call[3] == {"sets": 32, "keys": 1, "msgs": 20}
    assert red.ops and 0 < trace.busy_s(red) <= red.window_s
    assert red.window[1] <= max(s[2] for s in red.spans) or red.window_s > 0
    bd = trace.breakdown(red)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
