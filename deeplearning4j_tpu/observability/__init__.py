"""Observability: structured tracing + metrics + status/metrics HTTP.

The production observability layer (grown from the seed
``parallel/observe.py``; that module remains as a compat shim):

- ``trace`` (module alias) / ``span`` — nestable spans with trace identity
  (``trace_id``/``span_id``/``parent_id``, W3C ``traceparent`` propagation
  via ``trace.bind``/``trace.current_traceparent``), contextvar
  propagation, Chrome-trace (Perfetto) + JSONL export (``tracing``)
- ``METRICS`` / ``MetricsRegistry`` — counters, gauges, timing histograms
  with p50/p95/p99, Prometheus text exposition (``metrics``)
- ``COSTS`` / ``CostModel`` — XLA ``cost_analysis()`` FLOPs/bytes per
  compiled signature; live ``*.mfu`` / ``*.mbu`` gauges (``cost``)
- ``FLIGHTREC`` — bounded rings of recent spans/metric deltas/chaos fires,
  dumped to a JSON bundle on failure triggers (``flightrec``)
- ``TimeSeriesStore`` — background sampler turning the registry into
  bounded per-series rings + JSONL history (``timeseries``)
- ``GoodputTracker`` — wall-clock state accounting for supervised runs:
  productive/checkpoint/restore/rollback/stall/drain (``goodput``)
- ``SLObjective``/``SLOEvaluator`` — rolling-window objectives with
  multi-window error-budget burn rates; breaches dump flightrec bundles
  and publish ``slo.burn_rate.*`` (``slo``)
- ``FleetScraper``/``FederatedRegistry`` — metric federation across a
  replica pool; ``TENANTS`` bounded tenant labels; ``ForecastEvaluator``
  time-to-breach extrapolation (``fleet``)
- ``StatusServer`` — ``/healthz`` ``/metrics`` ``/metrics.prom`` ``/status``
- ``scopemap`` (module) — what each operation of a compiled program holds,
  by ``jax.named_scope`` path: ``register`` at a program's first dispatch
  (shapes only), ``scope_map`` on request beside a trace (compiles)
- ``sample_device_memory`` — per-device HBM gauges (no-op gauge on
  backends without memory stats)
- ``enabled``/``enable``/``disable`` — process-global flag;
  zero-per-step-allocation when off (see ``core``)
"""

from . import scopemap
from . import tracing as trace
from .core import NOOP_SPAN, disable, enable, enabled
from .cost import COSTS, CostInfo, CostModel
from .device import sample_device_memory, sample_state_bytes
from .fleet import (
    TENANTS,
    FederatedRegistry,
    FleetScraper,
    ForecastEvaluator,
    TenantLabels,
    parse_prometheus,
)
from .flightrec import FLIGHTREC, FlightRecorder
from .metrics import (
    DEFAULT_TIME_BUCKETS,
    METRICS,
    Histogram,
    MetricsRegistry,
    StepTimer,
)
from .goodput import GoodputTracker
from .server import StatusServer
from .slo import SLObjective, SLOEvaluator
from .slo import default_serving_objectives, default_training_objectives
from .timeseries import TimeSeriesStore
from .tracing import TRACER, Tracer, profiler_trace, span

__all__ = [
    "COSTS", "CostInfo", "CostModel", "DEFAULT_TIME_BUCKETS", "FLIGHTREC",
    "FederatedRegistry", "FleetScraper", "FlightRecorder",
    "ForecastEvaluator", "GoodputTracker", "Histogram", "METRICS",
    "MetricsRegistry", "NOOP_SPAN", "SLOEvaluator", "SLObjective",
    "StatusServer", "StepTimer", "TENANTS", "TRACER", "TenantLabels",
    "TimeSeriesStore", "Tracer",
    "default_serving_objectives", "default_training_objectives",
    "disable", "enable", "enabled", "parse_prometheus", "profiler_trace",
    "sample_device_memory", "sample_state_bytes", "scopemap", "span", "trace",
]
