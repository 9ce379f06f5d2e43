"""Shared JAX persistent-compile-cache configuration + observability.

Pairing-class kernels take minutes to compile cold; every entry point
(the node, tests, bench, chip_smoke.py) points at the same on-disk cache
so compiles amortize across processes. Keep the settings here — the one
place — and call `enable_compile_cache()` before kernels are traced.

Where the cache lives: `JAX_COMPILATION_CACHE_DIR` when the environment
sets it (JAX reads it itself, and no directory is set in code), otherwise
the fixed `<repo>/.jax_cache`. The path is part of what makes a cache hit,
so it never moves with the working directory.

Observability: `track_device_compile(kernel)` wraps a first (compiling)
invocation in a `device_compile` trace span and classifies it as a cache
hit or miss by whether the cache directory gained entries, feeding
`compile_cache_{hits,misses}_total` and
`compile_cache_compile_seconds_total` — so the device bench lanes report
compile-vs-execute through the standard metrics path instead of ad-hoc
phase labels, and a real TPU host's warm-cache boot shows up as hits."""

from __future__ import annotations

import os
import time
from contextlib import contextmanager

from ..metrics import REGISTRY

#: repo root = parent of the lighthouse_tpu package
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")
#: compiles faster than this are never persisted (and so can't be
#: distinguished from cache hits by track_device_compile — an accepted
#: sub-threshold blind spot: the kernels this tracks compile in minutes)
MIN_PERSIST_SECS = 0.5

# eagerly registered (conftest asserts): dashboards and the bench JSON
# read these even at zero
_HITS = REGISTRY.counter(
    "compile_cache_hits_total",
    "tracked device-kernel warmups served from the persistent compile cache",
)
_HITS.inc(0)
_MISSES = REGISTRY.counter(
    "compile_cache_misses_total",
    "tracked device-kernel warmups that had to compile (cache dir grew)",
)
_MISSES.inc(0)
_COMPILE_SECONDS = REGISTRY.counter(
    "compile_cache_compile_seconds_total",
    "cumulative wall time of tracked compiling warmups",
)
_COMPILE_SECONDS.inc(0)


def enable_compile_cache():
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", MIN_PERSIST_SECS
    )


def active_cache_dir() -> str | None:
    """The persistent cache directory JAX uses now (None when off)."""
    import jax

    return jax.config.jax_compilation_cache_dir or None


def _cache_entries(cache_dir: str | None) -> int:
    if cache_dir is None:
        return 0
    try:
        return len(os.listdir(cache_dir))
    except OSError:
        return 0


@contextmanager
def track_device_compile(kernel: str):
    """Wrap a warmup/first invocation of a device kernel: opens a
    `device_compile` span (so the compile shows up inside whatever trace
    is active — the device bench partials' compile phase) and counts a
    cache hit when the persistent cache directory did not grow, a miss
    (plus the elapsed compile seconds) when it did. Classification is by
    directory growth, not elapsed time: a slow hit on a loaded box must
    not masquerade as a compile. The inverse blind spot — a compile
    under MIN_PERSIST_SECS is never persisted, so it counts as a hit —
    is accepted: it bounds the unaccounted compile time per warmup to
    under half a second, noise against the minutes-scale kernels this
    instrumented path exists for."""
    from .tracing import span

    cache_dir = active_cache_dir()
    before = _cache_entries(cache_dir)
    t0 = time.perf_counter()
    with span("device_compile", kernel=kernel):
        yield
    elapsed = time.perf_counter() - t0
    if _cache_entries(cache_dir) > before:
        _MISSES.inc()
        _COMPILE_SECONDS.inc(elapsed)
    else:
        _HITS.inc()


def compile_cache_stats() -> dict:
    """Counter snapshot for the bench JSON (`compile_cache` key)."""
    return {
        "hits": _HITS.value(),
        "misses": _MISSES.value(),
        "compile_seconds": round(_COMPILE_SECONDS.value(), 2),
    }
