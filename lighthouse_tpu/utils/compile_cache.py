"""Shared JAX persistent-compile-cache configuration + observability.

Pairing-class kernels take minutes to compile cold; every entry point
(the node, tests, benchmark/, chip_smoke.py) points at the same on-disk cache
so compiles amortize across processes. Keep the settings here — the one
place — and call `enable_compile_cache()` before kernels are traced.

Where the cache lives: `JAX_COMPILATION_CACHE_DIR` when the environment
sets it (JAX reads it itself, and no directory is set in code), otherwise
the fixed `<repo>/.jax_cache`. The path is part of what makes a cache hit,
so it never moves with the working directory.

Observability: `enable_compile_cache()` also registers, once, listeners on
the events JAX records for every program it builds, so the counts come
from where the work happens:

  * `jax_programs_built_total`: programs compiled or loaded from the
    persistent cache (`/jax/core/compile/backend_compile_duration`),
  * `jax_program_build_seconds_total{phase=trace|lower|compile}`: seconds
    tracing to a jaxpr, lowering to MLIR, and compiling or loading,
  * `compile_cache_hits_total`: programs loaded from the persistent cache
    (`/jax/compilation_cache/cache_hits`),
  * `compile_cache_misses_total`: programs that asked the persistent cache
    and were compiled instead,

and `program_builds()` lists the newest build events with their program
names and times, to say which program was built when."""

from __future__ import annotations

import os
import threading
import time
from collections import deque

from ..metrics import REGISTRY

#: repo root = parent of the lighthouse_tpu package
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")
#: compiles faster than this are never persisted
MIN_PERSIST_SECS = 0.5
#: how many build events `program_builds()` keeps
BUILD_LOG_SIZE = 4096

#: JAX's duration events -> the phase label they count under
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}

# eagerly registered (conftest asserts): dashboards and the bench JSON
# read these even at zero
_BUILT = REGISTRY.counter(
    "jax_programs_built_total",
    "device programs compiled or loaded from the persistent compile cache",
)
_BUILT.inc(0)
_BUILD_SECONDS = REGISTRY.counter(
    "jax_program_build_seconds_total",
    "seconds spent building device programs, by phase (trace, lower, compile)",
)
for _phase in _PHASES.values():
    _BUILD_SECONDS.inc(0, phase=_phase)
del _phase
_HITS = REGISTRY.counter(
    "compile_cache_hits_total",
    "device programs loaded from the persistent compile cache",
)
_HITS.inc(0)
_MISSES = REGISTRY.counter(
    "compile_cache_misses_total",
    "device programs that asked the persistent compile cache and compiled",
)
_MISSES.inc(0)

#: (perf_counter at the event, phase, seconds, program name), oldest first
_BUILDS: deque = deque(maxlen=BUILD_LOG_SIZE)
_listening = False
_listen_lock = threading.Lock()
#: per thread: whether the program being built asked the cache, and hit
_request = threading.local()


def _on_event(event: str, **_kw):
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        _request.asked, _request.hit = True, False
    elif event == "/jax/compilation_cache/cache_hits":
        _request.hit = True
        _HITS.inc()


def _on_duration(event: str, seconds: float, fun_name: str = "", **_kw):
    phase = _PHASES.get(event)
    if phase is None:
        return
    _BUILD_SECONDS.inc(seconds, phase=phase)
    _BUILDS.append((time.perf_counter(), phase, seconds, fun_name))
    if phase == "compile":
        # the cache is asked, and answers, inside the compile event
        _BUILT.inc()
        if getattr(_request, "asked", False) and not _request.hit:
            _MISSES.inc()
        _request.asked = False


def _listen():
    global _listening
    with _listen_lock:
        if _listening:
            return
        from jax import monitoring

        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


def enable_compile_cache():
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", MIN_PERSIST_SECS
    )
    _listen()


def active_cache_dir() -> str | None:
    """The persistent cache directory JAX uses now (None when off)."""
    import jax

    return jax.config.jax_compilation_cache_dir or None


def program_builds() -> list:
    """The newest program-build events, oldest first, at most
    BUILD_LOG_SIZE: (time.perf_counter() at the end of the phase, phase,
    seconds, program name)."""
    return list(_BUILDS)
