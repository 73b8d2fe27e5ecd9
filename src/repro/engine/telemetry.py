"""Unified telemetry layer — span tracing, metrics, and run manifests.

Every engine layer used to report through its own ad-hoc accounting
(``EngineStats``, ``FaultStats``, ``CheckpointStats``, ``IngestStats``)
plus one-off CLI print lines, so a single round-0 run could never be seen
as one timeline.  This module is the one event stream they all feed:

  * :class:`span` — the one span helper every program seam uses.  It
    always writes a ``jax.profiler.TraceAnnotation`` named
    ``<cat>.<name>``, so the program's spans sit in any active profile on
    the device trace's clock, and it records the same span in a
    :class:`Tracer` when one is given.  Names: ``wave.gather`` (``read``,
    ``mask``), ``wave.solve`` (``stage``, ``dispatch``, ``fold``,
    ``block``), ``stall.sem-block``, ``stall.queue-wait``,
    ``host.host-gather``, ``round.round`` (``repartition``, ``dispatch``,
    ``sync``), ``ckpt.snapshot``, ``ckpt.write``, ``run.run``, and on the
    serving path ``serve.submit``, ``serve.drain``, ``serve.reply``,
    ``serve.prepare``, ``serve.group``, ``serve.round0`` (``.fetch``,
    ``.partial``), ``serve.tail`` (``.stack``, ``.upload``, ``.fetch``),
    ``serve.check`` and ``serve.delta``.
  * :class:`Tracer` — thread-safe **spans** (monotonic wall-clock,
    per-thread tracks, category, structured attrs) and instant events:
    the spans above when attached, plus the fault supervisor's retries,
    hedges and evictions, autotuner rung decisions and the async
    checkpoint writer.
  * :class:`MetricsRegistry` — counters / gauges / histograms with
    labels; :func:`feed_result_metrics` projects the existing stats
    dataclasses onto it, so those dataclasses are *views* over the same
    per-wave trace stream the spans are cut from
    (``WaveTrace.t_start/t_end/stall_s`` carry the raw timestamps).
  * Exporters — Chrome ``trace_event`` JSON (loads in Perfetto /
    ``chrome://tracing``, one track per thread and per ingestion host)
    and the :class:`RunManifest` (config fingerprint, source fingerprint,
    dtype, width trajectory, fault replay signature, final value, bytes,
    per-phase walls) written atomically next to the checkpoints.
  * :func:`profiler_session` — optional ``jax.profiler`` start/stop
    bracketing keyed by a ``--profile-dir`` flag; its trace carries the
    spans above beside the device's operations.

Design contract: telemetry is **observation only**.  A span never changes
what work happens, and an instrumented run is bit-identical to an
uninstrumented one (pinned by tests/test_telemetry.py and
tests/test_program_spans.py).  With no Tracer attached and no profile
active a span costs one annotation (about a microsecond); seams guard
every other emission with ``if tracer is not None``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import threading
import time
from typing import Any, Callable, Iterator

import jax
import numpy as np

from repro.engine.stats import (CheckpointStats, EngineStats, FaultStats,
                                WaveTrace)

SCHEMA_VERSION = 1

_DTYPE_LABELS = {"float32": "fp32", "bfloat16": "bf16"}


def dtype_label(dtype) -> str:
    """CLI/manifest label for a storage dtype ('fp32' | 'bf16' | 'int8' |
    the raw numpy name) — the vocabulary ``--dtype`` already uses."""
    name = np.dtype(dtype).name
    return _DTYPE_LABELS.get(name, name)

# span categories the engine emits (tracetool groups by these); "serve" is
# the selection-service track (per-request/per-batch spans, repro.serve)
CATEGORIES = ("wave", "host", "fault", "autotune", "ckpt", "round", "run",
              "stall", "serve")


# ---------------------------------------------------------------------------
# event model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SpanEvent:
    """One finished span (``phase="X"``) or instant (``phase="i"``).

    Timestamps are raw ``time.perf_counter()`` seconds — the same clock
    the engine's ``WaveTrace`` timestamps use, so spans and stats are
    directly comparable without epoch juggling.
    """
    name: str
    cat: str
    t0: float
    t1: float                   # == t0 for instants
    track: int                  # compact track id (thread or named track)
    phase: str = "X"            # "X" complete span | "i" instant
    args: dict = dataclasses.field(default_factory=dict)

    @property
    def dur_s(self) -> float:
        return self.t1 - self.t0


class Tracer:
    """Thread-safe span/instant collector with named tracks.

    All mutation happens under one lock; emission is O(1) appends, cheap
    enough for per-wave granularity (the engine never traces per-row
    work).  Tracks: every emitting thread is auto-registered as its own
    track (Perfetto renders one lane per track); logical actors that are
    not threads — ingestion hosts — get *named* tracks via ``track=``,
    so a host's gathers line up on one lane regardless of which pool
    thread served them.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.epoch = time.perf_counter()     # trace time zero
        self.created_unix = time.time()      # wall-clock anchor (export only)
        self.events: list[SpanEvent] = []
        self._tracks: dict[Any, int] = {}    # key -> compact track id
        self._track_names: dict[int, str] = {}
        self.metrics = MetricsRegistry()

    # -- time --------------------------------------------------------------
    @staticmethod
    def now() -> float:
        return time.perf_counter()

    # -- tracks ------------------------------------------------------------
    def _track_id(self, track: str | None) -> int:
        if track is None:
            th = threading.current_thread()
            key, name = ("thread", th.ident), th.name
        else:
            key, name = ("named", track), track
        with self._lock:
            tid = self._tracks.get(key)
            if tid is None:
                tid = len(self._tracks)
                self._tracks[key] = tid
                self._track_names[tid] = name
            return tid

    def track_names(self) -> dict[int, str]:
        with self._lock:
            return dict(self._track_names)

    # -- emission ----------------------------------------------------------
    def emit(self, name: str, cat: str, t0: float, t1: float, *,
             track: str | None = None, **args) -> None:
        """Record an externally timed span (the engine seams already hold
        their own ``perf_counter`` readings — no double clocking)."""
        ev = SpanEvent(name=name, cat=cat, t0=t0, t1=t1,
                       track=self._track_id(track), args=args)
        with self._lock:
            self.events.append(ev)

    def instant(self, name: str, cat: str, *, track: str | None = None,
                **args) -> None:
        t = time.perf_counter()
        ev = SpanEvent(name=name, cat=cat, t0=t, t1=t,
                       track=self._track_id(track), phase="i", args=args)
        with self._lock:
            self.events.append(ev)

    # -- accessors ---------------------------------------------------------
    def spans(self, cat: str | None = None,
              name: str | None = None) -> list[SpanEvent]:
        with self._lock:
            evs = list(self.events)
        return [e for e in evs
                if (cat is None or e.cat == cat)
                and (name is None or e.name == name)]

    # -- exporters ---------------------------------------------------------
    def export_chrome_trace(self, path: str) -> None:
        """Chrome ``trace_event`` JSON — loads in Perfetto, one track per
        thread/host.  Timestamps are exported as *unrounded* float
        microseconds relative to the trace epoch, so a consumer
        (``launch/tracetool.py``) can reconstruct overlap ratios to
        float precision."""
        pid = os.getpid()
        out: list[dict] = [
            {"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
             "args": {"name": name}}
            for tid, name in sorted(self.track_names().items())]
        with self._lock:
            events = list(self.events)
        for e in sorted(events, key=lambda e: e.t0):
            rec = {"name": e.name, "cat": e.cat, "pid": pid, "tid": e.track,
                   "ts": (e.t0 - self.epoch) * 1e6, "ph": e.phase,
                   "args": e.args}
            if e.phase == "X":
                rec["dur"] = (e.t1 - e.t0) * 1e6
            else:
                rec["s"] = "t"
            out.append(rec)
        _atomic_write_json(path, {"traceEvents": out,
                                  "displayTimeUnit": "ms",
                                  "otherData": {
                                      "schema_version": SCHEMA_VERSION,
                                      "created_unix": self.created_unix}})


class span:
    """One block of program work, timed for the profiler and the Tracer.

    ``name`` is ``"<cat>.<name>"`` (``"wave.gather"``,
    ``"serve.round0.fetch"``).  The block always runs inside a
    ``jax.profiler.TraceAnnotation`` of that name, so it lands in any
    active profile on the device trace's clock; the profiler keeps host
    events per thread, so a span is opened and closed on the thread that
    does the work.  With no profile active the annotation costs about a
    microsecond.  With a ``tracer`` the block is also recorded there as
    span ``name`` of category ``cat``, with ``args`` on the Tracer side
    only (the block may add to ``.args`` before it ends).  ``t0``/``t1``
    hold the block's ``perf_counter`` bounds once it has ended, for
    callers whose own records must agree with the span exactly.
    """
    __slots__ = ("name", "tracer", "track", "args", "t0", "t1", "_ann")

    def __init__(self, name: str, *, tracer: Tracer | None = None,
                 track: str | None = None, **args):
        self.name = name
        self.tracer = tracer
        self.track = track
        self.args = args
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "span":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        if self.tracer is not None:
            cat, _, name = self.name.partition(".")
            self.tracer.emit(name, cat, self.t0, self.t1, track=self.track,
                             **self.args)
        return False


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


class Counter:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def inc(self, v: float = 1.0) -> None:
        self.value += v


class Gauge:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)


class Histogram:
    """Exact small-sample histogram: the engine observes per-wave /
    per-round quantities (bounded counts), so keeping every observation
    is cheaper than getting bucket boundaries wrong."""
    __slots__ = ("samples",)

    def __init__(self):
        self.samples: list[float] = []

    def observe(self, v: float) -> None:
        self.samples.append(float(v))

    def summary(self) -> dict:
        s = sorted(self.samples)
        n = len(s)
        if n == 0:
            return {"count": 0, "sum": 0.0}
        return {"count": n, "sum": sum(s), "min": s[0], "max": s[-1],
                "mean": sum(s) / n, "p50": s[n // 2],
                "p95": s[min(n - 1, int(0.95 * n))]}


class MetricsRegistry:
    """Labelled counters/gauges/histograms behind one lock.

    Instruments are keyed ``name{k=v,...}`` with labels sorted, the
    Prometheus-style flat naming every scrape format understands;
    :meth:`snapshot` is the JSON-able export.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge] = {}
        self._histograms: dict[str, Histogram] = {}

    @staticmethod
    def _key(name: str, labels: dict) -> str:
        if not labels:
            return name
        inner = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
        return f"{name}{{{inner}}}"

    def _get(self, store: dict, cls, name: str, labels: dict):
        key = self._key(name, labels)
        with self._lock:
            inst = store.get(key)
            if inst is None:
                inst = store[key] = cls()
            return inst

    def counter(self, name: str, **labels) -> Counter:
        return self._get(self._counters, Counter, name, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get(self._gauges, Gauge, name, labels)

    def histogram(self, name: str, **labels) -> Histogram:
        return self._get(self._histograms, Histogram, name, labels)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "counters": {k: c.value for k, c in self._counters.items()},
                "gauges": {k: g.value for k, g in self._gauges.items()},
                "histograms": {k: h.summary()
                               for k, h in self._histograms.items()},
            }

    def export_json(self, path: str) -> None:
        _atomic_write_json(path, {"schema_version": SCHEMA_VERSION,
                                  **self.snapshot()})


def feed_result_metrics(registry: MetricsRegistry, result) -> None:
    """Project a ``TreeResult``'s stats dataclasses onto the registry.

    This is what makes ``EngineStats`` / ``FaultStats`` /
    ``CheckpointStats`` *views over one event stream*: all three are
    computed from the same per-wave ``WaveTrace`` records (and per-round
    checkpoint records) the spans were cut from, and this projection
    exposes the identical numbers as labelled metrics.
    """
    es: EngineStats | None = getattr(result, "engine_stats", None)
    if es is not None:
        lab = {"engine": es.engine}
        registry.counter("engine.waves", **lab).inc(es.waves)
        registry.counter("engine.bytes_moved", **lab).inc(es.bytes_moved)
        registry.gauge("engine.overlap_ratio", **lab).set(es.overlap_ratio)
        registry.gauge("engine.max_in_flight", **lab).set(es.max_in_flight)
        registry.gauge("mesh.devices").set(es.mesh_devices)
        for per_device in es.stage_bytes:
            for dev, nbytes in enumerate(per_device):
                registry.counter("engine.stage_bytes", device=dev).inc(nbytes)
        for t in es.traces:
            registry.histogram("engine.gather_s", **lab).observe(t.gather_s)
            registry.histogram("engine.solve_s", **lab).observe(t.solve_s)
            registry.histogram("engine.stall_s", **lab).observe(t.stall_s)
            registry.histogram("engine.wave_machines", **lab).observe(
                t.machines)
    fs: FaultStats | None = getattr(result, "fault_stats", None)
    if fs is not None:
        registry.counter("faults.retries").inc(fs.retries)
        registry.counter("faults.hedges").inc(fs.hedges)
        registry.counter("faults.hedges_won").inc(fs.hedges_won)
        registry.counter("faults.evictions").inc(fs.evictions)
        registry.counter("faults.dropped_rows").inc(fs.dropped_rows)
        registry.counter("faults.backoff_s").inc(fs.backoff_s)
    cs: CheckpointStats | None = getattr(result, "checkpoint_stats", None)
    if cs is not None:
        lab = {"mode": cs.mode}
        for r in cs.rounds:
            registry.histogram("ckpt.write_s", **lab).observe(r.write_s)
            registry.histogram("ckpt.wait_s", **lab).observe(r.wait_s)
        registry.gauge("ckpt.hidden_fraction", **lab).set(cs.hidden_fraction)
    depths = getattr(result, "depth_per_round", None)
    if depths:
        registry.gauge("solve.depth_total").set(
            int(getattr(result, "solve_depth", 0)))
        for dv in depths:
            registry.histogram("solve.depth_per_round").observe(int(dv))


# ---------------------------------------------------------------------------
# span-stream views (tracetool + cross-checks)
# ---------------------------------------------------------------------------


def wave_overlap_from_spans(gathers: list[tuple[float, float]],
                            solves: list[tuple[float, float]]
                            ) -> tuple[float, float]:
    """``(span_wall, overlap_ratio)`` recomputed from raw gather/solve
    span intervals — the exact arithmetic ``EngineStats`` applies to its
    ``WaveTrace`` timestamps, so a trace-file consumer reproduces the
    engine's reported overlap to float precision."""
    if not gathers or not solves:
        return 0.0, 0.0
    g = sum(t1 - t0 for t0, t1 in gathers)
    s = sum(t1 - t0 for t0, t1 in solves)
    wall = max(t1 for _, t1 in solves + gathers) - min(
        t0 for t0, _ in solves + gathers)
    if g <= 0.0:
        return wall, 0.0
    return wall, min(1.0, max(0.0, (g + s - wall) / g))


def top_spans(events: list[SpanEvent], limit: int = 10) -> list[dict]:
    """Aggregate spans by ``(cat, name)``: total seconds, count, mean."""
    agg: dict[tuple[str, str], list[float]] = {}
    for e in events:
        if e.phase == "X":
            agg.setdefault((e.cat, e.name), []).append(e.dur_s)
    rows = [{"cat": c, "name": n, "count": len(d), "total_s": sum(d),
             "mean_s": sum(d) / len(d)} for (c, n), d in agg.items()]
    rows.sort(key=lambda r: -r["total_s"])
    return rows[:limit]


# ---------------------------------------------------------------------------
# run manifest
# ---------------------------------------------------------------------------

MANIFEST_NAME = "run_manifest.json"

# fields a valid manifest must carry (tracetool + CI validate these)
MANIFEST_REQUIRED = ("schema_version", "config", "config_fingerprint",
                     "dtype", "run", "phases")


@dataclasses.dataclass
class RunManifest:
    """One run's identity + outcome, written atomically next to the
    checkpoints.  Everything the grep-able CLI report prints is formatted
    *from* this record (:func:`format_report`), so the manifest and the
    console can never disagree.

    Float fields are stored unrounded — the formatter owns presentation.
    """
    config: dict
    config_fingerprint: str
    run: dict                               # n/d/k/mu/value/rounds/...
    dtype: str = "fp32"
    source_fingerprint: str | None = None
    schema_version: int = SCHEMA_VERSION
    created_unix: float = 0.0
    engine: dict | None = None
    ingest: dict | None = None
    bytes: dict | None = None
    faults: dict | None = None              # counters + replay_signature
    checkpoint: dict | None = None
    phases: dict = dataclasses.field(default_factory=dict)
    feasibility: dict | None = None
    recheck: dict | None = None
    serve: dict | None = None               # selection-service counters
    #                                         (requests/batches/latency/
    #                                         compile-cache/deltas)
    adaptivity: dict | None = None          # sequential solve-depth record
    #                                         (launches per round, τ-ladder
    #                                         totals vs the greedy k·rounds
    #                                         baseline)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def write(self, path: str) -> str:
        if not self.created_unix:
            self.created_unix = time.time()
        _atomic_write_json(path, self.to_dict())
        return path

    @classmethod
    def load(cls, path: str) -> "RunManifest":
        """Tolerant load: unknown keys are dropped and missing required
        sections default to empty so :meth:`validate` can *report* a
        truncated manifest instead of the loader crashing on it."""
        with open(path) as f:
            data = json.load(f)
        known = {f.name for f in dataclasses.fields(cls)}
        merged: dict = {"config": {}, "config_fingerprint": "", "run": {}}
        merged.update({k: v for k, v in data.items() if k in known})
        return cls(**merged)

    def validate(self) -> list[str]:
        """Problems with this manifest (empty list = valid)."""
        problems = []
        d = self.to_dict()
        for field in MANIFEST_REQUIRED:
            if d.get(field) in (None, {}, ""):
                problems.append(f"missing required field {field!r}")
        for field in ("value", "rounds", "oracle_calls"):
            if field not in self.run:
                problems.append(f"run section missing {field!r}")
        if self.engine is not None:
            for field in ("engine", "wall_s", "gather_s", "solve_s",
                          "overlap_ratio", "width_trajectory"):
                if field not in self.engine:
                    problems.append(f"engine section missing {field!r}")
        return problems


def config_fingerprint(cfg) -> str:
    """Stable hash of a ``TreeConfig`` — the run's *configuration*
    identity (telemetry itself is excluded: attaching a tracer must not
    change what run this claims to be)."""
    return hashlib.sha256(json.dumps(
        config_dict(cfg), sort_keys=True).encode()).hexdigest()[:16]


def config_dict(cfg) -> dict:
    """JSON-able view of a ``TreeConfig`` (telemetry field dropped)."""
    out = {}
    for f in dataclasses.fields(cfg):
        if f.name == "telemetry":
            continue
        v = getattr(cfg, f.name)
        if dataclasses.is_dataclass(v):
            v = dataclasses.asdict(v)
        out[f.name] = v
    return out


def build_manifest(cfg, result, *, n: int, d: int, dtype_label: str,
                   itemsize: int = 4, qcols: int = 0,
                   source_fingerprint: str | None = None,
                   dataset: str | None = None) -> RunManifest:
    """Assemble the manifest from a finished ``TreeResult``.

    Works with or without telemetry attached — the CLI report formatter
    is driven by this record on every run, and a :class:`Tracer` only
    adds the trace/metrics exports on top.
    """
    run = {"n": n, "d": d, "k": cfg.k, "mu": cfg.capacity,
           "algorithm": cfg.algorithm, "seed": cfg.seed,
           "value": float(result.value), "rounds": int(result.rounds),
           "oracle_calls": int(result.oracle_calls),
           "machines_per_round": list(result.machines_per_round),
           "round_values": [float(v) for v in result.round_values]}
    if dataset is not None:
        run["dataset"] = dataset
    m = RunManifest(config=config_dict(cfg),
                    config_fingerprint=config_fingerprint(cfg),
                    run=run, dtype=dtype_label,
                    source_fingerprint=source_fingerprint)
    es = result.engine_stats
    if es is not None:
        m.engine = {
            "engine": es.engine, "hosts": es.hosts, "waves": es.waves,
            "wall_s": es.wall_s, "span_wall_s": es.span_wall_s,
            "gather_s": es.gather_s, "solve_s": es.solve_s,
            "stall_s": sum(t.stall_s for t in es.traces),
            "bytes_moved": es.bytes_moved,
            "overlap_ratio": es.overlap_ratio,
            "max_in_flight": es.max_in_flight,
            "width_trajectory": es.width_trajectory,
            "distinct_shapes": es.distinct_shapes,
        }
    ing = result.ingest
    if ing is not None:
        m.ingest = {
            "wave_machines": ing.wave_machines, "waves": ing.waves,
            "peak_wave_rows": ing.peak_wave_rows,
            "peak_wave_bytes": ing.peak_wave_bytes,
            "attr_dim": ing.attr_dim, "total_bytes": ing.total_bytes,
            "wall_seconds": ing.wall_seconds,
        }
        row_bytes = d * itemsize + (ing.attr_dim + qcols) * 4
        fp32_row_bytes = (d + ing.attr_dim) * 4
        m.bytes = {"dtype": dtype_label, "itemsize": itemsize,
                   "qcols": qcols, "row_bytes": row_bytes,
                   "fp32_row_bytes": fp32_row_bytes,
                   "resident_bytes": n * row_bytes}
    fs = result.fault_stats
    if fs is not None:
        m.faults = {**fs.summary(),
                    "recovered_s": fs.recovered_s,        # unrounded for
                    "backoff_s": fs.backoff_s,            # the formatter
                    "replay_signature": fs.replay_signature()}
    cs = result.checkpoint_stats
    if cs is not None:
        m.checkpoint = {"mode": cs.mode, "rounds": len(cs.rounds),
                        "write_s": cs.write_s, "wait_s": cs.wait_s,
                        "hidden_s": cs.hidden_s,
                        "hidden_fraction": cs.hidden_fraction}
    depths = result.depth_per_round
    if depths:
        # the greedy baseline pays k dependent launches per round; the
        # reduction factor is the headline adaptivity win
        greedy_depth = cfg.k * int(result.rounds)
        m.adaptivity = {
            "algorithm": cfg.algorithm, "eps": cfg.eps,
            "solve_depth": int(result.solve_depth),
            "depth_per_round": [int(v) for v in depths],
            "greedy_depth": greedy_depth,
            "reduction": (greedy_depth / result.solve_depth
                          if result.solve_depth else 0.0),
        }
    walls = result.round_walls or []
    m.phases = {
        "total_wall_s": float(result.total_wall_s or 0.0),
        "round0_wall_s": float(walls[0]) if walls else 0.0,
        "later_rounds_wall_s": float(sum(walls[1:])),
        "checkpoint_write_s": cs.write_s if cs is not None else 0.0,
        "checkpoint_wait_s": cs.wait_s if cs is not None else 0.0,
    }
    return m


# ---------------------------------------------------------------------------
# consolidated CLI report — every grep-able line in one place
# ---------------------------------------------------------------------------


def format_report(m: RunManifest) -> list[str]:
    """The CLI report lines, byte-compatible with the historical per-PR
    print statements (CI greps ``engine:`` / ``faults:`` / ``bytes:`` /
    ``recheck:`` / ``autotune:`` / ``checkpoint:`` prefixes) — now all
    driven by the one :class:`RunManifest` record."""
    r, lines = m.run, []
    lines.append(f"TREE: f={r['value']:.6f} rounds={r['rounds']} "
                 f"machines/round={r['machines_per_round']} "
                 f"oracle_calls={r['oracle_calls']}")
    if m.ingest is not None and m.bytes is not None:
        ing, by = m.ingest, m.bytes
        lines.append(
            f"ingest: W={ing['wave_machines']} waves={ing['waves']} "
            f"peak_wave_rows={ing['peak_wave_rows']} "
            f"peak_wave_bytes={ing['peak_wave_bytes']} "
            f"attr_dim={ing['attr_dim']} "
            f"(resident would hold {by['resident_bytes']} bytes)")
        lines.append(
            f"bytes: dtype={by['dtype']} itemsize={by['itemsize']} "
            f"row_bytes={by['row_bytes']} "
            f"fp32_row_bytes={by['fp32_row_bytes']} "
            f"saved={1.0 - by['row_bytes'] / by['fp32_row_bytes']:.1%} "
            f"peak_wave_bytes={ing['peak_wave_bytes']} "
            f"total_bytes={ing['total_bytes']}")
    if m.engine is not None:
        es = m.engine
        lines.append(
            f"engine: {es['engine']} hosts={es['hosts']} "
            f"wall={es['wall_s']:.3f}s gather={es['gather_s']:.3f}s "
            f"solve={es['solve_s']:.3f}s overlap={es['overlap_ratio']:.2%} "
            f"bytes={es['bytes_moved']} "
            f"max_in_flight={es['max_in_flight']}")
        if m.config.get("wave_autotune"):
            lines.append(f"autotune: widths={es['width_trajectory']} "
                         f"distinct_shapes={es['distinct_shapes']}")
    if m.faults is not None:
        fs = m.faults
        lines.append(
            f"faults: retries={fs['retries']} hedges={fs['hedges']} "
            f"hedges_won={fs['hedges_won']} evictions={fs['evictions']} "
            f"dropped_waves={fs['dropped_waves']} "
            f"dropped_rows={fs['dropped_rows']}/{fs['total_rows']} "
            f"dropped_fraction={fs['dropped_fraction']:.4f} "
            f"recovered={fs['recovered_s']:.3f}s "
            f"backoff={fs['backoff_s']:.3f}s")
    if m.checkpoint is not None:
        ck = m.checkpoint
        lines.append(
            f"checkpoint: {ck['mode']} rounds={ck['rounds']} "
            f"write={ck['write_s']:.3f}s stalled={ck['wait_s']:.3f}s "
            f"hidden={ck['hidden_fraction']:.2%}")
    if m.adaptivity is not None:
        ad = m.adaptivity
        lines.append(
            f"adaptivity: alg={ad['algorithm']} eps={ad['eps']} "
            f"solve_depth={ad['solve_depth']} "
            f"depth/round={ad['depth_per_round']} "
            f"greedy_depth={ad['greedy_depth']} "
            f"reduction={ad['reduction']:.1f}x")
    if m.feasibility is not None:
        fz = m.feasibility
        lines.append(f"feasibility: {'OK' if fz['ok'] else 'VIOLATED'} "
                     f"({fz['detail']})")
    if m.recheck is not None:
        rc = m.recheck
        lines.append(f"recheck: fp32={rc['fp32']:.6f} "
                     f"solve={rc['solve']:.6f} "
                     f"rel_gap={rc['rel_gap']:.2e} {rc['status']}")
    if m.serve is not None:
        sv = m.serve
        lines.append(
            f"serve: requests={sv['requests']} batches={sv['batches']} "
            f"p50_ms={sv['latency_p50_ms']:.3f} "
            f"p95_ms={sv['latency_p95_ms']:.3f} "
            f"qdepth_max={sv['queue_depth_max']}")
        lines.append(
            f"serve: compile-cache keys={sv['cache_keys']} "
            f"compiles={sv['compiles']} hits={sv['cache_hits']} "
            f"steady_retraces={sv['steady_retraces']}")
        lines.append(
            f"serve: deltas={sv['deltas']} "
            f"changed_machines={sv['changed_machines']} "
            f"rebuilds={sv['rebuilds']}")
    return lines


# ---------------------------------------------------------------------------
# jax.profiler bracketing
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def profiler_session(profile_dir: str | None) -> Iterator[None]:
    """Bracket a block with ``jax.profiler`` start/stop when a directory
    is given (the ``--profile-dir`` flag); no-op otherwise.  A profiler
    that cannot start raises: a run asked for a device profile must not
    finish without one."""
    if not profile_dir:
        yield
        return
    import jax
    os.makedirs(profile_dir, exist_ok=True)
    jax.profiler.start_trace(profile_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# shared atomic-write helpers
# ---------------------------------------------------------------------------


def _atomic_write_text(path: str, text: str) -> None:
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(text)
    os.replace(tmp, path)


def _atomic_write_json(path: str, obj) -> None:
    _atomic_write_text(path, json.dumps(obj, indent=1, sort_keys=True))
