"""repro.obs — the telemetry spine: traces, metrics, exporters, and the
phase-2 consumers (profiler, SLOs, flight recorder, calibration).

One coherent observability layer replacing the three ad-hoc stats
surfaces the stack grew (serve's `_pct` rollup, cluster's inline
percentiles, the PageCache counter dicts):

  * `TRACER`   — hierarchical trace spans over the whole request path
                 (request -> queue -> batch -> dispatch -> shard ->
                 segment -> traversal -> hop -> store-read /
                 device-wait), exported as Chrome/Perfetto trace-event
                 JSON and, while enabled, as `jax.profiler` annotations
                 on the device trace's clock. One attribute check when
                 disabled (the default), sampled when enabled.
  * `REGISTRY` — process-wide metrics (counters / gauges / bounded
                 histograms) every layer publishes into, snapshot behind
                 one call, exported as Prometheus text or JSON.
  * `PROFILER` — continuous per-stage profiling fed at span close,
                 off by default; switched on, it runs with tracing on
                 or off; `profile_report()` is the fig_obs latency
                 attribution, live.
  * `SLOTracker` / `default_slos` — declarative latency / error-rate /
                 recall objectives with multi-window burn-rate breaches.
  * `FlightRecorder` — bounded capture of the N slowest + errored
                 requests, dumpable as Perfetto JSON.
  * `calibrate` / `compare_terms` — fit cost-model HW parameters from a
                 metrics snapshot; `ann_dryrun --calibrated` consumes it.
  * `latency_summary` — the one percentile helper (p50/p99/p999/mean).

See docs/observability.md for the span hierarchy and the metric-name
table (with the paper-figure mapping, e.g. store_block_reads_total <->
Fig. 9).
"""

from repro.obs.calibrate import (Calibration, calibrate, compare_terms,
                                 load_calibration)
from repro.obs.export import (PeriodicExporter, to_json, to_prometheus,
                              write_snapshot)
from repro.obs.flight import FlightRecorder
from repro.obs.metrics import (DEFAULT_MS_BUCKETS, Counter, Gauge,
                               Histogram, MetricsRegistry, REGISTRY)
from repro.obs.profile import PROFILER, Profiler, profile_report
from repro.obs.slo import SLO, SLOTracker, default_slos
from repro.obs.stats import latency_summary
from repro.obs.trace import TRACER, SpanCtx, Tracer

# Close the loop: the global tracer feeds the global profiler at span
# close, so once the profiler is switched on per-stage timings flow with
# tracing disabled too. Private Tracer()/Profiler() instances stay
# unlinked — tests rely on the disabled tracer's shared no-op span.
TRACER.profiler = PROFILER

__all__ = [
    "TRACER", "Tracer", "SpanCtx",
    "REGISTRY", "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "DEFAULT_MS_BUCKETS",
    "PROFILER", "Profiler", "profile_report",
    "SLO", "SLOTracker", "default_slos",
    "FlightRecorder",
    "Calibration", "calibrate", "load_calibration", "compare_terms",
    "latency_summary",
    "to_prometheus", "to_json", "write_snapshot", "PeriodicExporter",
]
