"""Unified telemetry: span tracing, metrics registry, run reports, heartbeat.

The reference fgumi is obsessive about operator visibility — per-step
pipeline timers and queue-occupancy history (base.rs:2853-3379), progress
heartbeats, per-command metric files. This package is that discipline for
fgumi-tpu, as one layer with a zero-overhead-when-disabled contract:

- :mod:`.trace` — thread-aware ``span("name", **attrs)`` context manager
  at every layer boundary (start-up, host stages, chain stages, batch
  engines, router, feeder, resolve, sink), live under ``--trace`` or
  ``--run-report``; each span knows its parent and goes to the run
  report's aggregate (by name, and by thread from each thread's root
  spans: which thread paces a job, and how much of its work was off the
  CPU or in the kernel), onto the profiler's clock
  (``jax.profiler.TraceAnnotation``) and, under ``--trace``, into Chrome
  trace-event JSON loadable in Perfetto.
- :mod:`.metrics` — a process-wide :class:`MetricsRegistry` aggregating the
  scattered ``DeviceStats``, ``StageTimes``, fault/retry counters, and I/O
  byte counts under stable dotted names.
- :mod:`.report` — a schema-versioned machine-readable run report emitted
  atomically at the end of every command (``--run-report`` /
  ``FGUMI_TPU_RUN_REPORT``).
- :mod:`.heartbeat` — a periodic one-line progress heartbeat on the
  standard log stream (``--heartbeat`` / ``FGUMI_TPU_HEARTBEAT_S``).
- :mod:`.logs` — ``--log-level`` logging setup with elapsed time and
  thread name, so multi-threaded stage logs are attributable.
- :mod:`.scope` — job-scoped telemetry: a contextvar-resolved
  :class:`TelemetryScope` gives every top-level command (and every serve-
  daemon job) its own metrics/DeviceStats/tracer, propagated through the
  pipeline's helper threads; replaces the old per-command global reset.
- :mod:`.compilewatch` — folds jax compile/cache-hit monitoring events
  into the owning scope's metrics (``device.backend_compiles``), the
  warm-kernel evidence the serve smoke gate asserts on.
- :mod:`.process` — the process-level record every run report carries:
  start-up spans and each compile / cache load of the process so far.
- :mod:`.alloc` — glibc's allocator counters (arenas, what they hold and
  hold free, mapped blocks) at a job's two ends, for its run report.

Disabled is the default and costs nothing on the hot path: ``span`` returns
a shared no-op context manager, metric folding happens once per command at
report time, and no background thread starts unless asked for.
"""

from .metrics import METRICS, MetricsRegistry  # noqa: F401
from .trace import (NULL_SPAN, arm_spans, instant, span, start_trace,  # noqa: F401
                    stop_trace, tracing_enabled, write_trace)
