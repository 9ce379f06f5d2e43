"""From a profiler trace to device busy time, idle gaps and kernel time.

The run records the profiler's XPlane file for a sub-window of the
measured window, marked by a `bench.traced` annotation. The device is
busy while one of its programs runs: the events of the `XLA Modules` line
of each `/device:TPU` plane, named by program. The `XLA Ops` line beside
it holds millions of tiny op events a second; it is never read (walking
it took minutes), and the profiler's buffers overflow with it within a
few seconds, so the window is cut where the trace reports `Trace Buffers
Dropped`. The host spans are the benchmark's own `bench.*` annotations,
which carry their arguments as event stats (for `bench.verify_call`:
sets, keys, msgs).
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field


def profile_options():
    """Device events and the benchmark's own spans only: no Python call
    tracing, host TraceMe events at level 1 (the `bench.*` annotations
    keep), and no HLO protos of the loaded programs, whose writing cost
    more than 100 s of a 0.25 s trace's stop on a TPU v5e host."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    return options


def _is_tpu_modules(plane_name: str, line_name: str) -> bool:
    return plane_name.startswith("/device:TPU") and line_name == "XLA Modules"


@dataclass
class Reduced:
    window: tuple  # (start_ns, end_ns) of the traced sub-window
    ops: dict = field(default_factory=dict)  # device -> [(start, end, program)]
    spans: list = field(default_factory=list)  # (name, start, end, stats)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def latest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def extract(path: str, is_device=_is_tpu_modules) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, ops, drops = [], {}, []
    for plane in pd.planes:
        for line in plane.lines:
            if is_device(plane.name, line.name):
                ops.setdefault(plane.name, []).extend(
                    (e.start_ns, e.end_ns, _module_name(e.name)) for e in line.events
                )
            elif plane.name.startswith("/device:"):
                if line.name == "XLA TraceMe":
                    drops.extend(
                        (e.start_ns, e.end_ns) for e in line.events
                        if e.name == "Trace Buffers Dropped"
                    )
            else:
                spans.extend(
                    (e.name, e.start_ns, e.end_ns, dict(e.stats))
                    for e in line.events
                    if e.name.startswith("bench.")
                )
    marks = [s for s in spans if s[0] == "bench.traced"]
    if not marks:
        raise ValueError("the trace has no bench.traced window")
    # where the profiler ran out of buffer it drops device events: the
    # window ends where the first drop begins
    end = min([marks[0][2]] + [start for start, _ in drops])
    window = (marks[0][1], end)
    return Reduced(window=window, ops=ops, spans=[s for s in spans if s[0] != "bench.traced"])


def add_host_spans(red: Reduced, spans, mark: float) -> None:
    """Add spans timed on the host clock (name, start, end in
    perf_counter seconds), given `mark`, the host clock at the start of the
    traced window: a span that began before the trace is missing from it,
    and these name the idle gaps inside it."""
    origin = red.window[0] - mark * 1e9
    red.spans.extend((name, origin + a * 1e9, origin + b * 1e9, {}) for name, a, b in spans)


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def union(intervals, lo, hi) -> list:
    """Sorted, merged [start, end) intervals clipped to [lo, hi)."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_s(red: Reduced) -> float:
    """Seconds of the window in which some op ran, averaged over devices."""
    if not red.ops:
        return 0.0
    lo, hi = red.window
    return sum(
        sum(e - s for s, e in union(evs, lo, hi)) for evs in red.ops.values()
    ) / len(red.ops) / 1e9


def device_time_within(red: Reduced, spans) -> float:
    """Device-busy seconds inside the union of the given host spans."""
    total = 0.0
    cover = union([(s, e) for _, s, e, _ in spans], *red.window)
    for evs in red.ops.values():
        for s, e in cover:
            total += sum(b - a for a, b in union(evs, s, e))
    return total / max(1, len(red.ops)) / 1e9


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The programs that took most device time, and the longest idle gaps
    named by the innermost benchmark span that covers each."""
    lo, hi = red.window
    by_module: dict = {}
    gaps = []
    for evs in red.ops.values():
        for s, e, name in evs:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                by_module[name] = by_module.get(name, 0.0) + (e - s) / 1e9
        busy = union(evs, lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    named = []
    for length, a, b in gaps[:top]:
        mid = (a + b) / 2
        inside = [sp for sp in red.spans if sp[1] <= mid <= sp[2]]
        label = min(inside, key=lambda sp: sp[2] - sp[1])[0] if inside else "no benchmark span"
        named.append([label, length / 1e9])
    ops = sorted(by_module.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}
