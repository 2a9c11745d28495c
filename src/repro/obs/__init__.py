"""repro.obs: unified metrics, per-request tracing, and profiling hooks.

The serving stack (continuous batching, paged KV + prefix sharing,
speculative decoding, multi-tenant adapter banks) and the training loop
report through one `MetricsRegistry`: labeled counters/gauges/histograms
with p50/p95/p99, structured events (retraces, bank pressure, slow
ticks), per-request lifecycle trace spans, JSONL/Prometheus/JSON
exporters, the serving tick's host phase spans, the model step's device
scopes, and JAX profiler capture helpers. See the README "Observability"
section for the metric catalog and schemas.
"""
from repro.obs.aggregate import (merge_snapshots, mergeable_snapshot,
                                 merged_histogram)
from repro.obs.export import JsonlSink, render_prometheus, write_snapshot
from repro.obs.metrics import (DEFAULT_BUCKETS, Counter, Gauge, Histogram,
                               MetricsRegistry, format_key)
from repro.obs.profile import (ProfiledTicks, annotate, phase_counters,
                               scope, span)
from repro.obs.slo import (Objective, SLOMonitor, SLOSpec, SLOVerdict,
                           accept_floor, kv_free_floor, queue_depth_max,
                           tpot_target, ttft_target)
from repro.obs.trace import NULL_TRACE, RequestTrace, RequestTracer
from repro.obs.watchdog import StepWatchdog

__all__ = [
    "Counter", "DEFAULT_BUCKETS", "Gauge", "Histogram", "JsonlSink",
    "MetricsRegistry", "NULL_TRACE", "Objective", "ProfiledTicks",
    "RequestTrace", "RequestTracer", "SLOMonitor", "SLOSpec", "SLOVerdict",
    "StepWatchdog", "accept_floor", "annotate", "format_key",
    "kv_free_floor", "merge_snapshots", "mergeable_snapshot",
    "merged_histogram", "phase_counters", "queue_depth_max",
    "render_prometheus", "scope", "span", "tpot_target", "ttft_target",
    "write_snapshot",
]
