"""Run one cell of the benchmark once and print its result line.

    python3 -m benchmark.run --workload att-backlog --seed 7 --seconds 10 --trace 0

Run from the root of a checkout on a machine with the chips the cell asks
for. With no TPU, or fewer chips than the cell needs, it prints no result
and exits 2. The last line of standard output is one JSON object:
correct, attempted, failed, metrics (the cell's end-to-end metrics with
--trace 0, its per-layer metrics with --trace 1), device, with --trace 1
a breakdown, and last the numbers compared with their limits, which are
also the last lines of standard error.

Everything about a cell is found by name: BENCHMARK.json names its
configuration file and its traffic mix (traffic/<mix>.json); the mix names
its kind, the module traffic/<kind>.py that generates, offers and checks
that traffic; and each metric is read by metrics/<metric name>.py from the
run's record: seconds, t0, t_end, setup_s, stages, reduced (the trace,
with --trace 1) and what the kind records (traffic/__init__.py).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(HERE, ".cache")
#: the traced sub-window starts this far into the window
TRACE_LEAD_S = 2.0
#: traced sub-window: the verifier runs ~3M device ops a second, and on
#: a TPU v5e host a 1 s trace took 460-480 s to stop while the node ran,
#: past a run's 360 s
TRACE_S = 0.25


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries a run of `cell` reports."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metric(name: str, run) -> float | None:
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmark_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(run)


def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


class Tracer(threading.Thread):
    """Records the profiler's trace for [start, start + length) of the
    window, marked by a bench.traced annotation."""

    def __init__(self, start: float, length: float, path: str):
        super().__init__(daemon=True)
        self.start_at, self.length, self.path = start, length, path

    def run(self):
        import jax

        from . import trace

        time.sleep(max(0.0, self.start_at - time.perf_counter()))
        jax.profiler.start_trace(self.path, profiler_options=trace.profile_options())
        try:
            with jax.profiler.TraceAnnotation("bench.traced"):
                # the host clock at the traced span's start: it places the
                # run's own host-clock spans on the profiler's clock
                self.mark = time.perf_counter()
                time.sleep(self.length)
        finally:
            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - t


def run_cell(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
             trace: bool, t_start: float, backend: str = "tpu", fault=None):
    """One run: set-up, the measured window, the reference check. Returns
    the run's record for the metric readers. The traffic kind that the mix
    names does the cell's own part (traffic/__init__.py); `fault`, for the
    tests only, breaks the timed path underneath its observers."""
    import jax

    from lighthouse_tpu.utils.compile_cache import enable_compile_cache

    from . import traffic

    enable_compile_cache()
    # persist every program, however fast it compiled: small programs
    # recompiling in each process were most of a warm start's compile time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    stages = {}
    traffic_cell = traffic.load(mix["kind"]).Cell(cfg, mix, seed, backend, fault)
    trace_dir = os.path.join(CACHE, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        traffic_cell.setup(stages)
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        t_end = t0 + seconds
        tracer = trace_mark = None
        if trace:
            lead = min(TRACE_LEAD_S, seconds / 5)
            tracer = Tracer(t0 + lead, min(TRACE_S, seconds - 2 * lead), trace_dir)
            tracer.start()
        traffic_cell.window(t0, t_end)
        traffic_cell.close()
        if tracer is not None:
            tracer.join()
            stages["trace_write_s"] = tracer.stop_s
            trace_mark = tracer.mark
        memory = jax.devices()[0].memory_stats() or {}
    finally:
        traffic_cell.stop()
    t = time.perf_counter()
    checks = traffic_cell.check()
    reference_s = time.perf_counter() - t
    return SimpleNamespace(
        seconds=seconds, t0=t0, t_end=t_end, setup_s=setup_s, stages=stages,
        checks=checks, reference_s=reference_s,
        memory_peak=memory.get("peak_bytes_in_use"),
        trace_dir=trace_dir if trace else None, trace_mark=trace_mark, reduced=None,
        **traffic_cell.record(),
    )


def correct(run) -> bool:
    from .traffic import passes

    return all(passes(c) for c in run.checks.values())


def result_line(bench: dict, cell: dict, run, trace: bool, device: dict) -> dict:
    from . import trace as tr

    out_device = dict(device, memory_peak_bytes=run.memory_peak)
    breakdown = None
    if trace:
        t = time.perf_counter()
        run.reduced = tr.extract(tr.latest_xplane(run.trace_dir))
        tr.add_host_spans(run.reduced, run.host_spans, run.trace_mark)
        run.stages["trace_read_s"] = time.perf_counter() - t
        out_device["busy_s"] = tr.busy_s(run.reduced)
        out_device["window_s"] = run.reduced.window_s
        breakdown = tr.breakdown(run.reduced)
    metrics = {}
    for m in cell_metrics(bench, cell["name"], trace):
        value = read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {
        "correct": correct(run),
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": out_device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = run.compared
    line["window"] = {
        "answered": run.in_window,
        "device_calls": len([c for c in run.calls if run.t0 <= c.start < run.t_end]),
        "sets_per_call": read_metric("device_sets_per_call.att", run),
    }
    line["setup_stages_s"] = run.stages
    line["reference_s"] = run.reference_s
    line["checks"] = run.checks
    return line


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, config["file"]))
    mix = load_json(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))

    # the compile cache lives inside the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    device = device_info()
    if device["platform"] != "tpu" or device["count"] < cell["chips"]:
        print(f"needs {cell['chips']} TPU chip(s); JAX reports {device}", file=sys.stderr)
        return 2
    if cfg["bls_backend"] != "tpu":
        raise ValueError("the benchmark measures the node's tpu BLS backend")
    run = run_cell(cell, cfg, mix, args.seed, args.seconds, bool(args.trace), t_start)
    line = result_line(bench, cell, run, bool(args.trace), device)
    for name, v in line["checks"].items():
        limit = f"at least {v['least']}" if "least" in v else f"limit {v['limit']}"
        print(f"{name} {v['value']} ({limit})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
