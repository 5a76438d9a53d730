"""Pipeline-wide observability: tracing, metrics, health, drift, export.

The TPU-native replacement for the reference's Hadoop/YARN counters and
Guagua master logs (``ShifuCLI`` step timing lines, MR job counters,
per-worker progress RPC): one in-process telemetry layer every step
processor, trainer, and plane reports through, with a JSONL sink under
``<modelset>/telemetry/`` and live + post-hoc CLI surfaces
(``shifu-tpu monitor``, ``shifu-tpu analysis --telemetry [--timeline]``).

Modules:

- :mod:`tracer` — nested wall-clock spans (each also a ``shifu:``
  annotation on the ``jax.profiler`` clock) + point events, thread-safe
  collector with a live-span registry, JSONL sink.  A job's spans by
  name: the step's root > ``setup`` > ``setup.config`` / ``.probe`` /
  ``.columns`` / ``.journal`` / ``.precheck``, then ``process`` > the
  step's phases > ``data.*``, ``nn.*`` or ``tower.*`` (a tower job ends
  in ``tower.save`` > ``.clear`` / ``.fetch`` / ``.write`` / ``.commit``);
- :mod:`registry` — named counters/gauges/histograms (rows, epochs,
  loss, throughput, device-memory high-water, XLA compile accounting);
  instruments are thread-safe (ingest prep thread + trainers + the
  heartbeat/exporter readers share them);
- :mod:`manifest` — THE declaration of every metric name (a lint test
  enforces it: a typo'd name cannot silently mint a new metric);
- :mod:`health` — per-process heartbeat files under
  ``<modelset>/telemetry/health/`` (atomic, background thread) with
  live step/phase/progress and a staleness model;
- :mod:`monitor` — the ``shifu-tpu monitor`` renderer tailing those
  heartbeats (stale/stalled/straggler flags, quorum summary);
- :mod:`timeline` — span JSONL -> Chrome/Perfetto ``trace_event`` JSON,
  ingest-thread spans on their own track;
- :mod:`exporter` — periodic OpenMetrics-text + JSON registry snapshots
  (``telemetry/metrics.prom`` / ``metrics.json``);
- :mod:`slo` — live SLO plane for the serving path: sliding-window
  latency quantiles from a fixed-bin log histogram sketch (no
  per-request storage), availability tracking and multi-window
  error-budget burn-rate alerts against declared objectives
  (``-Dshifu.serve.sloP99Ms`` / ``-Dshifu.serve.sloAvailability``),
  surfaced via ``/slo``, SERVE heartbeats and ``metrics.prom``;
- :mod:`drift` — streaming per-column PSI of live binned windows vs the
  training-time ColumnConfig snapshot (ROADMAP #5's promotion signal);
- :mod:`scorelog` — sampled, bounded prediction logging from the serve
  path (crash-safe append-only segments with atomic rotation and a
  disk budget under ``<modelset>/telemetry/scorelog/``);
- :mod:`outcomes` — delayed-label join: outcome records (``POST
  /outcome`` or a drop directory) meet logged predictions by request
  id inside a bounded watermark window;
- :mod:`quality` — streaming model-quality monitor: per-generation
  live AUC / reliability-bin calibration over joined windows +
  score-distribution PSI vs the ``posttrain.json`` training snapshot
  (the refresh controller's third trigger source);
- :mod:`profiler` — opt-in ``jax.profiler.trace()`` capture around any
  step (``shifu-tpu <step> --profile [dir]``);
- :mod:`report` — renders the last run's spans/metrics as a tree with
  per-step self-time, rows/sec, ingest-stall / tail / drift sections;
- :mod:`costs` — device cost attribution: ``costed_jit`` captures
  FLOPs / bytes / memory per named executable, counts compiles,
  launches and RECOMPILES (the shape-churn sentinel), analytic models
  cover Pallas kernels XLA cannot see through; ``op_scopes`` maps a
  ``jax.named_scope`` to the compiled step's instructions (what it
  builds is keyed in the compile cache with its ops' names, so the
  names read are this source's);
- :mod:`utilization` — joins executable costs against span wall times:
  achieved FLOP/s, bytes/s, percent-of-peak and a roofline verdict per
  plane (``analysis --telemetry --utilization``).

Everything is ZERO-COST when disabled (the default): ``span()`` returns
a shared no-op singleton, instruments are no-op singletons, heartbeat /
exporter / drift factories return ``None``, no threads, no annotations,
no files.  Enable with env ``SHIFU_TPU_TELEMETRY=1``, property
``-Dshifu.telemetry=on``, or the per-step ``--telemetry`` flag.
"""

from .registry import (counter, gauge, histogram,             # noqa: F401
                       sample_device_memory, ensure_compile_listener,
                       snapshot, get_registry)
from .tracer import (SCHEMA_VERSION, enabled, set_enabled,    # noqa: F401
                     span, event, flush, record_span, pending_records,
                     live_spans, reset_for_tests)
from .manifest import (MANIFEST, PREFIXES, SPANS,             # noqa: F401
                       is_declared, is_declared_span)
from .slo import (SLOTracker, LogBins, LOG_BINS,              # noqa: F401
                  quantile_from_counts, slo_objectives,
                  BrownoutGovernor)
from .health import (HeartbeatWriter, start_heartbeat,        # noqa: F401
                     read_health, classify, health_dir_for,
                     heartbeat_interval_s)
from .exporter import (MetricsExporter, start_exporter,       # noqa: F401
                       render_openmetrics, write_metrics_files,
                       metric_name)
from .drift import (DriftMonitor, start_drift_monitor,        # noqa: F401
                    psi_threshold)
from .scorelog import (ScoreLog, read_score_records,          # noqa: F401
                       scorelog_dir, scorelog_sample_rate)
from .outcomes import (OutcomeJoiner, outcomes_drop_dir,      # noqa: F401
                       outcome_watermark_s)
from .quality import (QualityMonitor, start_quality_monitor,  # noqa: F401
                      write_posttrain_snapshot,
                      load_posttrain_snapshot,
                      posttrain_snapshot_path, quality_artifact_path)
from .costs import (costed_jit, record_executable,            # noqa: F401
                    register_cost_model, record_model_launch,
                    cost_snapshot, resolve_peaks, backend_info)

__all__ = [
    # tracer
    "SCHEMA_VERSION", "enabled", "set_enabled", "span", "event", "flush",
    "record_span", "pending_records", "live_spans", "reset_for_tests",
    # registry
    "counter", "gauge", "histogram", "sample_device_memory",
    "ensure_compile_listener", "snapshot", "get_registry",
    # manifest
    "MANIFEST", "PREFIXES", "SPANS", "is_declared", "is_declared_span",
    # SLO plane
    "SLOTracker", "LogBins", "LOG_BINS", "quantile_from_counts",
    "slo_objectives", "BrownoutGovernor",
    # health / monitor plane
    "HeartbeatWriter", "start_heartbeat", "read_health", "classify",
    "health_dir_for", "heartbeat_interval_s",
    # exporter
    "MetricsExporter", "start_exporter", "render_openmetrics",
    "write_metrics_files", "metric_name",
    # drift
    "DriftMonitor", "start_drift_monitor", "psi_threshold",
    # model-quality plane
    "ScoreLog", "read_score_records", "scorelog_dir",
    "scorelog_sample_rate", "OutcomeJoiner", "outcomes_drop_dir",
    "outcome_watermark_s", "QualityMonitor", "start_quality_monitor",
    "write_posttrain_snapshot", "load_posttrain_snapshot",
    "posttrain_snapshot_path", "quality_artifact_path",
    # cost-attribution plane
    "costed_jit", "record_executable", "register_cost_model",
    "record_model_launch", "cost_snapshot", "resolve_peaks",
    "backend_info",
]
