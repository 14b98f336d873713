"""Dependency-free serving observability: metrics registry + span tracing.

  * metrics  — process-local registry of named counters / gauges /
               fixed-bucket histograms (labels, declared units,
               percentile estimation, snapshot-to-dict, Prometheus-style
               ``render_text``), with an injectable clock
  * trace    — ring-buffered span tracer exporting Chrome trace-event
               JSON (Perfetto-loadable), per-request lifecycle tracks,
               optional ``jax.profiler.TraceAnnotation`` pass-through
  * validate — artifact schema validators shared by tests and the CI
               metric-name/unit check
  * attribution — compiled-HLO per-step cost attribution joined with
               measured step times: roofline utilization + cost-model
               drift gauges
  * slo      — declarative serving SLOs (sliding-window percentiles,
               burn rate, edge-triggered violation watchdog)

:class:`Observability` bundles one registry + one tracer around a shared
clock; the serving engine owns one and threads it through the scheduler,
page pool and speculative decoder (see docs/observability.md).
"""
from __future__ import annotations

import contextlib
import time
from typing import Optional

from repro.obs.metrics import (DEFAULT_LATENCY_BUCKETS, METRIC_NAME_RE,
                               Counter, Gauge, Histogram, MetricsRegistry)
from repro.obs.trace import (ENGINE_TRACK, REQUEST_TRACK_BASE, SpanHandle,
                             Tracer)
from repro.obs.validate import validate_chrome_trace, validate_snapshot


class Observability:
    """One registry + one tracer sharing one (injectable) clock.

    The unit every instrumented subsystem receives: the engine creates
    one per instance (metrics are process-local to an engine, matching
    ``aggregate_stats``'s scope) and hands it to the scheduler and pool.
    ``trace=False`` keeps the registry live but makes spans no-ops.
    """

    def __init__(self, clock=time.monotonic, trace_capacity: int = 65536,
                 trace: bool = True, xla_annotations: bool = False):
        self.registry = MetricsRegistry(clock=clock)
        self.tracer = Tracer(clock=clock, capacity=trace_capacity,
                             enabled=trace,
                             xla_annotations=xla_annotations)
        self.step_seconds = self.registry.histogram(
            "serving_step_seconds", "host-side latency of one engine-step "
            "phase (includes device sync)", unit="seconds",
            labelnames=("phase",))

    @contextlib.contextmanager
    def phase(self, phase: str, span: Optional[str] = None, **args):
        """Time one engine-step phase once, two ways: as the tracer span
        ``span`` (default: the phase's name) with ``args``, and as one
        observation of ``serving_step_seconds{phase=}``, which is made
        with tracing off too. Yields the span (see ``Tracer.span``)."""
        with self.tracer.span(span or phase, **args) as s, \
                self.step_seconds.time(phase=phase):
            yield s


# attribution/slo import AFTER Observability: they are host-only leaf
# modules importing repro.obs.metrics / repro.obs.trace directly, and
# re-exporting them here keeps `from repro.obs import SLO, ...` working
# without a package-init cycle
from repro.obs.attribution import StepAttribution, StepCost  # noqa: E402
from repro.obs.slo import (SLO, SLOMonitor, SlidingWindow,  # noqa: E402
                           attach_engine_slos, parse_slo, parse_slo_list)

__all__ = ["Counter", "DEFAULT_LATENCY_BUCKETS", "ENGINE_TRACK", "Gauge",
           "Histogram", "METRIC_NAME_RE", "MetricsRegistry",
           "Observability", "REQUEST_TRACK_BASE", "SLO", "SLOMonitor",
           "SlidingWindow", "SpanHandle", "StepAttribution", "StepCost",
           "Tracer", "attach_engine_slos", "parse_slo", "parse_slo_list",
           "validate_chrome_trace", "validate_snapshot"]
