"""What the program recorded of a run by itself, read after the run in the
run's own process: its trace spans, from the trees its trace collector
keeps (`lighthouse_tpu.metrics.trace_collector`), and its program builds,
from the compile listeners (`lighthouse_tpu.utils.compile_cache`).

Both are the program's own registries, and a program that lacks them
(before it had the device verifier's spans or the compile listeners)
gives None, never an error. A span belongs to the window when it began
inside it. The collector keeps a bounded ring of the newest trees; where
the ring no longer holds the start of the window, the span readers read
the window's newest trees it still holds, which is why each of them is a
ratio over the spans it read and not a total.
"""

from __future__ import annotations


def _walk(root):
    stack = [root]
    while stack:
        s = stack.pop()
        yield s
        stack.extend(list(s.children))


def window_spans(run, names) -> dict:
    """{name: [closed spans of that name that began in the window and that
    the collector still holds]}."""
    from lighthouse_tpu.metrics import trace_collector

    out = {name: [] for name in names}
    for root in trace_collector.COLLECTOR.recent(limit=1 << 30):
        for s in _walk(root):
            if s.name in out and s.duration_s is not None and run.t0 <= s.t0 < run.t_end:
                out[s.name].append(s)
    return out


def within(span, name: str) -> list:
    """The closed spans named `name` inside `span`'s tree, itself excluded."""
    return [s for s in _walk(span) if s is not span and s.name == name and s.duration_s is not None]


def verify_calls(run):
    """(bls_device_verify spans that began in the window, the seconds each
    waited on the device), or None without such spans."""
    calls = window_spans(run, ("bls_device_verify",))["bls_device_verify"]
    if not calls:
        return None
    return calls, [sum(w.duration_s for w in within(c, "bls_device_wait")) for c in calls]


def builds_since(t: float) -> list | None:
    """The program's build events that ended at host clock `t` or later,
    oldest first: (host clock, phase, seconds, program). None for a
    program without the compile listeners, or when the bounded log may
    have dropped some of them."""
    from lighthouse_tpu.utils import compile_cache

    if not hasattr(compile_cache, "program_builds"):
        return None
    events = compile_cache.program_builds()
    if len(events) >= compile_cache.BUILD_LOG_SIZE and events[0][0] >= t:
        return None
    return [e for e in events if e[0] >= t]


def build_seconds_total() -> float:
    """Seconds the program spent building programs since its compile
    listeners started, over every phase."""
    from lighthouse_tpu.metrics import REGISTRY

    return sum(REGISTRY.counter("jax_program_build_seconds_total").values().values())
