"""Host spans of the decision path: one mechanism for the runner's
in-memory time totals and the profiler's trace.

``with span(name, stats, key):`` opens a ``jax.profiler.TraceAnnotation``
named ``name``: with a profiler session on (``jax.profiler.start_trace``)
the span lands in the session's ``.xplane.pb`` beside the device ops, on
the device trace's clock; with none on it creates no annotation at
all. When a key is given, the span also adds its host duration
(``time.perf_counter``) to ``stats[key]``; ``seconds`` keeps the last
duration either way.

Span names describe the system, under ``rb.``:

- decision runner (``core/hotpath.py``): ``rb.stage`` (gathers into the
  staging buffers), ``rb.telemetry`` (the dirty-row read, then the
  one telemetry block's fill and upload, and the alive mask's when the
  roster moved), ``rb.dispatch`` (the jitted step:
  argument transfers, the launch and the start of the result's host
  transfers), ``rb.fetch`` with ``rb.wait`` (until the transfers are
  complete) and ``rb.copy`` (the numpy slice and cast) inside;
- serving engine (``core/engine.py``): ``rb.window`` around one fired
  window, with ``rb.assign`` (the policy call) and ``rb.submit`` (the
  loop that submits the decided requests) inside.
- recovery (``serving/recovery.py``): ``rb.recovery`` around the
  manager's work: requeueing a failure's victims, arming the hedge
  timer of each dispatch (inside ``rb.submit``), the hedge check, the
  telemetry watchdog and its releases.
"""
from __future__ import annotations

from time import perf_counter
from typing import Dict, Optional

from jax.profiler import TraceAnnotation

_tracing = TraceAnnotation.is_enabled


class span:
    """Context manager: a profiler annotation plus a host-clock total."""

    __slots__ = ("name", "stats", "key", "seconds", "_ann", "_t0")

    def __init__(self, name: str, stats: Optional[Dict] = None,
                 key: Optional[str] = None):
        self.name = name
        self.stats = stats
        self.key = key
        self.seconds = 0.0

    def __enter__(self) -> "span":
        # no annotation object at all while no profiler session is on
        self._ann = TraceAnnotation(self.name) if _tracing() else None
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self.key is not None:
            self.stats[self.key] += self.seconds
        return False
