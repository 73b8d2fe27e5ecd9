"""repro.engine — asynchronous round-0 execution engine.

Six layers (see each module's docstring):

  * :mod:`repro.engine.scheduler` — sync reference + double-buffered
    pipelined wave drivers with bounded in-flight backpressure and
    dynamic (planner-driven) wave iteration.
  * :mod:`repro.engine.autotune` — rate-tuned wave autoscaler: bucket-
    ladder width planners fed by the live per-wave trace stream.
  * :mod:`repro.engine.checkpoint` — async double-buffered round-boundary
    checkpoint writer with an explicit write barrier, plus keep-k rotation
    and crash-safe tmp cleanup of the round-checkpoint file layout.
  * :mod:`repro.engine.planner` — multi-host sharding of the round-0
    gather (single-process emulation with enforced locality for CI),
    including lossless re-routing around permanently lost hosts.
  * :mod:`repro.engine.faults` — fault supervision: retry with backoff,
    hedged re-gathers of stragglers, host eviction, bounded graceful
    degradation (Lemma 3.4 budget), and the seeded chaos injector.
  * :mod:`repro.engine.stats` — per-wave trace + overlap accounting, the
    checkpoint-overlap record, and the fault/straggler records, surfaced
    on ``TreeResult``.
  * :mod:`repro.engine.telemetry` — the unified observation layer: span
    helper over every seam above, written to the profiler's trace and to
    an optional span tracer (Chrome trace exporter),
    labelled metrics registry the stats dataclasses feed, and the
    atomically written ``RunManifest`` + consolidated CLI report
    formatter.
"""
from repro.engine.autotune import (AutotuneCache, AutotunePlanner,
                                   FixedWidthPlanner, ScheduledWidthPlanner,
                                   WavePlanner, bucket_ladder, shape_bound,
                                   snap_down, suggest_prefetch_depth)
from repro.engine.checkpoint import (AsyncCheckpointWriter, clean_stale_tmp,
                                     latest_round_checkpoint,
                                     list_round_checkpoints,
                                     load_round_checkpoint,
                                     write_round_checkpoint)
from repro.engine.faults import (DroppedFractionExceeded, FaultInjector,
                                 FaultPolicy, FaultProfile, FaultSupervisor,
                                 PermanentGatherError, TransientIOError)
from repro.engine.planner import HostShard, IngestionPlan
from repro.engine.scheduler import (ENGINES, EngineConfig, HostWave,
                                    run_waves)
from repro.engine.stats import (CheckpointStats, EngineStats, FaultEvent,
                                FaultStats, RoundCheckpoint,
                                StragglerMonitor, WaveTrace,
                                overlap_from_traces, overlap_ratio)
from repro.engine.telemetry import (MetricsRegistry, RunManifest, SpanEvent,
                                    Tracer, build_manifest, dtype_label,
                                    feed_result_metrics, format_report,
                                    profiler_session, span, top_spans,
                                    wave_overlap_from_spans)

__all__ = [
    "ENGINES", "AsyncCheckpointWriter", "AutotuneCache", "AutotunePlanner",
    "CheckpointStats",
    "DroppedFractionExceeded", "EngineConfig", "EngineStats", "FaultEvent",
    "FaultInjector", "FaultPolicy", "FaultProfile", "FaultStats",
    "FaultSupervisor", "FixedWidthPlanner", "HostShard", "HostWave",
    "IngestionPlan", "MetricsRegistry", "PermanentGatherError",
    "RoundCheckpoint", "RunManifest", "ScheduledWidthPlanner", "SpanEvent",
    "StragglerMonitor", "Tracer", "TransientIOError",
    "WavePlanner", "WaveTrace", "bucket_ladder", "build_manifest",
    "clean_stale_tmp", "dtype_label", "feed_result_metrics",
    "format_report", "latest_round_checkpoint", "list_round_checkpoints",
    "load_round_checkpoint", "overlap_from_traces", "overlap_ratio",
    "profiler_session", "run_waves", "shape_bound", "snap_down", "span",
    "suggest_prefetch_depth", "top_spans",
    "wave_overlap_from_spans", "write_round_checkpoint",
]
