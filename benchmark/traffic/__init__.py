"""Traffic: a mix is a data file, `traffic/<mix>.json`, that names its
`kind` and that kind's parameters; the kind is a module of this package,
`traffic/<kind>.py`, found by that name.

A kind module holds everything one kind of traffic needs: its generator,
its warm-up, the loop that offers it in the window (closed or open), the
observers it attaches to the node, and the reference comparison that
decides `correct`. It defines `Cell(cfg, mix, seed, backend, fault)` with

  setup(stages)          boot the node, make the traffic from the seed and
                         warm up every shape the window uses; time each
                         stage into `stages`
  window(t0, t_end)      offer the traffic from t0 until t_end
  close()                wait for late answers, record what was observed
  stop()                 stop the node (always called)
  check() -> dict        the reference comparison, after the node stopped:
                         {name: {"value": v, "limit": l}} (v <= l passes)
                         or {"value": v, "least": l} (v >= l passes)
  record() -> dict       what the metric readers read, with at least
                         attempted, failed, compared (counts the reference
                         compared), in_window, calls, counters (the device
                         counters at the window's start and end) and
                         host_spans (name, start, end on the host clock,
                         which name the idle gaps of a trace)

`fault`, for the tests only, is called with the booted node before the
observers are attached, to break the timed path underneath them.
"""

from __future__ import annotations

import importlib


def load(kind: str):
    """The module of a traffic kind."""
    if not kind.isidentifier():
        raise ValueError(f"bad traffic kind {kind!r}")
    return importlib.import_module(f"{__name__}.{kind}")


def passes(check: dict) -> bool:
    if "least" in check:
        return check["value"] >= check["least"]
    return check["value"] <= check["limit"]
