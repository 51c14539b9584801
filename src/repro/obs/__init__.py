"""Serving telemetry: metrics registry, trace spans, live row-locality.

The observability layer the MARS serving stack reports through:

  ``obs.metrics``    counters / gauges / fixed-bucket histograms behind a
                     process-local registry, plus the ``StatGroup``
                     facade that superseded the ad-hoc stats dataclasses
  ``obs.trace``      ring-buffered JSONL event log with monotonic
                     timestamps and nested spans, and ``span``: a
                     region on the profiler's clock (and in the log)
  ``obs.rowsim``     incremental open-row model (extracted from
                     ``core/dram.py``) feeding the live row-hit % gauge
  ``obs.observer``   the ``Observer`` hub + ``attach(engine)`` wiring
                     and the shared ``shard_load_snapshot`` helper

Everything is stdlib + numpy (the row model shares ``core/dram``'s
address decode; ``span`` enters a ``jax.profiler.TraceAnnotation``).
The registry, log and row model cost one ``is not None`` test per
instrumented site when disabled; a ``span`` costs about a microsecond
when no profiler trace is running.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               StatGroup, exp_edges)
from repro.obs.observer import Observer, shard_load_snapshot
from repro.obs.rowsim import OpenRowCounter
from repro.obs.trace import TraceLog

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "StatGroup",
    "exp_edges", "Observer", "shard_load_snapshot", "OpenRowCounter",
    "TraceLog",
]
