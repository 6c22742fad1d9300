"""Run telemetry subsystem: per-round flight recorder with drop-cause
attribution, counters/timers, pluggable sinks, and a report renderer.

Enable with ``FFTConfig.telemetry=True`` (off by default — the disabled
path is a falsy no-op hub and changes nothing about a run); add
``telemetry_log=<path>`` for a schema-versioned NDJSON event log and
``telemetry_console=True`` for a per-round terminal summary line.  After
``runner.run(...)`` the in-memory flight record is ``runner.report``
(a ``RunReport``); ``reconcile(runner.report, runner)`` cross-checks its
aggregates against the run's own accounting and ``render_markdown`` turns
reports into the ``benchmarks.report run-report`` tables.

Population-scale additions (PR 8): ``telemetry="sketch"`` swaps the
per-client rows for bounded-memory streaming sketches (``SketchReport``,
exact additive totals, ε-approximate quantiles, K-row reservoir);
``HealthMonitors`` watch the round stream online and emit schema'd alarm
records plus a run-end verdict; ``telemetry_trace=<dir>`` runs the job
under the JAX profiler, whose trace holds every ``phase.*`` span (the
timers are ``TraceAnnotation``s, on with or without telemetry) beside the
device's ops, and opens in Perfetto; and ``telemetry_dashboard=True`` /
``benchmarks.report watch`` render a live in-place run dashboard.  ``load_report`` picks the right report type for
any NDJSON log.
"""
from repro.obs.dashboard import (DashboardSink,  # noqa: F401
                                 render_dashboard, sparkline, watch)
from repro.obs.health import (HealthConfig, HealthMonitors,  # noqa: F401
                              health_record)
from repro.obs.report import (ReconcileError, reconcile,  # noqa: F401
                              render_markdown)
from repro.obs.sinks import (ConsoleSink, NdjsonSink, RunReport,  # noqa: F401
                             Sink, TELEMETRY_SCHEMA, TELEMETRY_VERSION,
                             TELEMETRY_VERSIONS_READABLE, load_report,
                             peek_telemetry_mode, read_telemetry_records)
from repro.obs.sketch import (ExactSum, GKQuantiles,  # noqa: F401
                              Reservoir, SKETCH_EPS, SketchReport,
                              SketchState)
from repro.obs.telemetry import (AGGREGATED, BUFFERED,  # noqa: F401
                                 EVICTED, LINK_DOWN, MISSED_DEADLINE,
                                 NOT_SELECTED, NULL_TELEMETRY, OUTCOMES,
                                 SKIPPED_STRAGGLER, NullTelemetry, Telemetry,
                                 beta_row)
