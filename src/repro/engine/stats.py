"""Engine stats / trace layer — per-wave timings, bytes moved, overlap.

Every ingestion engine (sync reference and pipelined) emits one
:class:`WaveTrace` per dispatched wave and one :class:`EngineStats` per
round-0 run.  The traces let benchmarks and tests reason about the
pipeline honestly:

  * ``gather_s`` is host work — source reads + numpy assembly of the wave's
    ``(W·μ, d+a)`` candidate matrix (the part the pipelined engine hides
    under device compute).
  * ``solve_s`` is device work — host→device upload, the wave's
    ``run_round`` dispatch, and the best-solution fold, measured by
    blocking on the folded wave value (both engines block identically, so
    the columns are comparable).
  * ``overlap_ratio`` is the fraction of total gather time hidden under
    solve time: ``(Σgather + Σsolve − wall) / Σgather``, clamped to
    [0, 1].  The synchronous engine serializes gather→solve, so its ratio
    is ~0 by construction; the upper bound for the pipelined engine is
    ``min(Σgather, Σsolve) / Σgather``.
"""
from __future__ import annotations

import dataclasses
import time


@dataclasses.dataclass
class WaveTrace:
    """Accounting for one dispatched ingestion wave.

    ``t_start``/``t_end`` are raw ``time.perf_counter()`` readings — the
    wave's gather begin and solve end on the shared monotonic clock — so
    wave ordering and cross-wave overlap can be reconstructed post-hoc
    (durations alone cannot place waves on a timeline).  ``stall_s`` is
    honest backpressure: producer time blocked on the 2-buffer semaphore
    plus consumer time waiting on the queue (0 for the sync engine, where
    neither wait exists).
    """
    wave: int                   # wave index (fold order)
    machines: int               # machine blocks in this wave (≤ W)
    rows: int                   # candidate rows materialized (machines · μ)
    bytes_moved: int            # host→device bytes for the wave's blocks
    gather_s: float             # host: source read + block assembly
    solve_s: float              # device: upload + dispatch + fold (blocked)
    per_host_rows: list[int] | None = None  # rows served by each ingestion host
    t_start: float = 0.0        # perf_counter at gather begin
    t_end: float = 0.0          # perf_counter at solve end
    stall_s: float = 0.0        # backpressure: sem-block + queue-wait


@dataclasses.dataclass
class EngineStats:
    """Round-0 ingestion engine summary (surfaced on ``TreeResult``)."""
    engine: str                 # "sync" | "pipelined"
    hosts: int                  # ingestion hosts (1 = single-process gather)
    waves: int
    wall_s: float               # whole-round-0 wall clock (gather+solve+fold)
    gather_s: float             # Σ per-wave host gather time
    solve_s: float              # Σ per-wave device time
    bytes_moved: int            # Σ host→device candidate bytes
    overlap_ratio: float        # fraction of gather hidden under solve
    max_in_flight: int          # high-water mark of live host wave buffers
    traces: list[WaveTrace] = dataclasses.field(default_factory=list)
    fault_stats: "FaultStats | None" = None  # set when supervision was active
    span_wall_s: float = 0.0    # max(t_end) − min(t_start) over the traces
    #                             (the wall the span-based overlap uses; 0.0
    #                             when the engine predates timestamped traces)
    mesh_devices: int = 1       # devices the waves were sharded over
    stage_bytes: list[list[int]] = dataclasses.field(default_factory=list)
    #                             host→device bytes staged to each device
    #                             (by mesh position) per wave, in wave order;
    #                             a dropped wave stages nothing

    @property
    def width_trajectory(self) -> list[int]:
        """Machines per dispatched wave, in wave order — the autoscaler's
        decision record (constant under the fixed-W policy)."""
        return [t.machines for t in self.traces]

    @property
    def distinct_shapes(self) -> int:
        """Distinct wave widths dispatched = distinct XLA wave shapes this
        run compiled (the autotuner's bucket ladder bounds this by
        ``⌊log2(W_max/ndev)⌋ + 2`` — see repro.engine.autotune)."""
        return len(set(self.width_trajectory))

    def summary(self) -> dict:
        """JSON-able record for benchmark trajectory files."""
        return {
            "engine": self.engine, "hosts": self.hosts, "waves": self.waves,
            "wall_s": round(self.wall_s, 4),
            "gather_s": round(self.gather_s, 4),
            "solve_s": round(self.solve_s, 4),
            "bytes_moved": self.bytes_moved,
            "overlap_ratio": round(self.overlap_ratio, 4),
            "span_wall_s": round(self.span_wall_s, 4),
            "stall_s": round(sum(t.stall_s for t in self.traces), 4),
            "max_in_flight": self.max_in_flight,
            "width_trajectory": self.width_trajectory,
            "distinct_shapes": self.distinct_shapes,
            **({"faults": self.fault_stats.summary()}
               if self.fault_stats is not None else {}),
        }


# ---------------------------------------------------------------------------
# Fault supervision accounting (PR 6).  Lives here — not in engine/faults.py —
# so core/tree.py and the CLI can consume fault records without importing the
# supervisor machinery (and faults.py can import the planner freely).
# ---------------------------------------------------------------------------

FAULT_KINDS = ("transient-retry", "latency", "straggler", "hedge",
               "evict", "drop")


@dataclasses.dataclass
class FaultEvent:
    """One supervision decision, in the order the supervisor made it."""
    kind: str                   # one of FAULT_KINDS
    wave: int                   # wave index the event belongs to
    attempt: int                # gather attempt number (0 = first try)
    detail: str = ""            # human-readable specifics (host id, error, …)
    seconds: float = 0.0        # time attributable to the event (backoff,
    #                             straggler overrun, recovered wall, …)

    def __post_init__(self):
        assert self.kind in FAULT_KINDS, self.kind


@dataclasses.dataclass
class FaultStats:
    """Per-run fault supervision record (on ``EngineStats``/``TreeResult``).

    ``dropped_rows / total_rows`` is the empirical dropped fraction the
    Lemma 3.4 budget is checked against: each dropped machine forfeits at
    most its μ-slice of the round's candidate pool, so the additive quality
    loss is bounded by the dropped fraction of OPT's items (PERF.md §PR6).
    """
    retries: int = 0            # transient gather retries that were issued
    hedges: int = 0             # speculative re-gathers launched
    hedges_won: int = 0         # hedges that finished before the original
    evictions: int = 0          # permanent host losses re-routed to survivors
    dropped_waves: int = 0      # waves folded as dead past the retry budget
    dropped_machines: int = 0   # machine blocks inside dropped waves
    dropped_rows: int = 0       # candidate rows forfeited by dropped waves
    total_rows: int = 0         # round-0 candidate rows (drop denominator)
    recovered_s: float = 0.0    # wall spent inside successful recoveries
    backoff_s: float = 0.0      # wall spent sleeping between retry attempts
    events: list[FaultEvent] = dataclasses.field(default_factory=list)

    @property
    def dropped_fraction(self) -> float:
        return 0.0 if self.total_rows <= 0 else (
            self.dropped_rows / self.total_rows)

    def record(self, event: FaultEvent) -> None:
        self.events.append(event)

    def summary(self) -> dict:
        return {
            "retries": self.retries,
            "hedges": self.hedges, "hedges_won": self.hedges_won,
            "evictions": self.evictions,
            "dropped_waves": self.dropped_waves,
            "dropped_machines": self.dropped_machines,
            "dropped_rows": self.dropped_rows,
            "total_rows": self.total_rows,
            "dropped_fraction": round(self.dropped_fraction, 6),
            "recovered_s": round(self.recovered_s, 4),
            "backoff_s": round(self.backoff_s, 4),
            "events": len(self.events),
        }

    def replay_signature(self) -> dict:
        """The deterministic slice of the record: counters that must be
        bit-identical across replays of the same seeded chaos profile.
        Hedges are excluded — they fire on wall-clock thresholds."""
        return {
            "retries": self.retries, "evictions": self.evictions,
            "dropped_waves": self.dropped_waves,
            "dropped_machines": self.dropped_machines,
            "dropped_rows": self.dropped_rows,
        }


class StragglerMonitor:
    """Per-wave gather-rate tracker feeding the hedge policy.

    Ported from ``repro.train.fault_tolerance.StragglerMonitor`` (per-step
    wall flagging for the training loop) into the engine stats path: waves
    vary in width, so the monitor normalizes to seconds *per machine* and
    keeps both a windowed median (robust flagging, as in train) and an EWMA
    (the hedge threshold's estimate, matching the autotuner's smoothing).
    The supervisor asks :meth:`threshold` for "how long should a W-machine
    gather take before we hedge it?" — ``None`` until ``min_samples`` waves
    have been observed, so cold starts never hedge.
    """

    def __init__(self, factor: float = 3.0, window: int = 50,
                 min_samples: int = 3, alpha: float = 0.3):
        assert factor > 1.0, factor
        self.factor = factor
        self.window = window
        self.min_samples = min_samples
        self.alpha = alpha
        self.rates: list[float] = []    # seconds per machine, recent window
        self.ewma: float | None = None
        self._t0: float | None = None

    def observe(self, seconds: float, machines: int) -> None:
        rate = seconds / max(1, machines)
        self.rates.append(rate)
        self.rates = self.rates[-self.window:]
        self.ewma = rate if self.ewma is None else (
            self.alpha * rate + (1.0 - self.alpha) * self.ewma)

    # train-style start/stop face, kept for drivers that time externally
    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self, machines: int = 1) -> bool:
        assert self._t0 is not None, "stop() without start()"
        dt = time.perf_counter() - self._t0
        self._t0 = None
        flagged = self.flag(dt, machines)
        self.observe(dt, machines)
        return flagged

    def threshold(self, machines: int,
                  rate_hint: float | None = None) -> float | None:
        """Hedge deadline (seconds) for a ``machines``-wide gather, or
        ``None`` while too few waves have been seen to judge.  An external
        ``rate_hint`` (the autotuner's EWMA, measured on the same stream)
        takes precedence over the monitor's own estimate."""
        if len(self.rates) < self.min_samples and rate_hint is None:
            return None
        rate = rate_hint if rate_hint is not None else self._robust_rate()
        return self.factor * rate * max(1, machines)

    def flag(self, seconds: float, machines: int) -> bool:
        """Would this wall time be flagged as a straggler?"""
        thr = self.threshold(machines)
        return thr is not None and seconds > thr

    def _robust_rate(self) -> float:
        med = sorted(self.rates)[len(self.rates) // 2]
        # median guards against the stragglers themselves polluting the
        # estimate; EWMA tracks drift — take the larger to avoid hair-
        # trigger hedging when the stream is genuinely slowing down
        return max(med, self.ewma or 0.0)


@dataclasses.dataclass
class RoundCheckpoint:
    """Accounting for one round-boundary checkpoint write."""
    round: int                  # round index the checkpoint snapshots
    write_s: float              # serialize + file write (background thread
    #                             under the async writer, inline otherwise)
    wait_s: float               # caller stall attributable to this write:
    #                             the barrier wait before the NEXT snapshot
    #                             (async) or the whole write (sync)

    @property
    def hidden_s(self) -> float:
        """Write seconds overlapped with the next round's compute."""
        return max(0.0, self.write_s - self.wait_s)


@dataclasses.dataclass
class CheckpointStats:
    """Per-run checkpoint-overlap record (surfaced on ``TreeResult``).

    The async writer overlaps round t's serialized write with round t+1's
    repartition + solves; ``wall ≈ max(round_{t+1}, ckpt_t)`` instead of
    the synchronous ``round_{t+1} + ckpt_t`` (PERF.md §PR5).  ``wait_s``
    is the only checkpoint time the round loop actually *paid*; the rest
    of ``write_s`` was hidden.
    """
    mode: str                   # "sync" | "async"
    rounds: list[RoundCheckpoint] = dataclasses.field(default_factory=list)

    @property
    def write_s(self) -> float:
        return sum(r.write_s for r in self.rounds)

    @property
    def wait_s(self) -> float:
        return sum(r.wait_s for r in self.rounds)

    @property
    def hidden_s(self) -> float:
        return sum(r.hidden_s for r in self.rounds)

    @property
    def hidden_fraction(self) -> float:
        """Fraction of the total write wall hidden under compute."""
        w = self.write_s
        return 0.0 if w <= 0.0 else min(1.0, self.hidden_s / w)

    def summary(self) -> dict:
        return {
            "mode": self.mode, "rounds": len(self.rounds),
            "write_s": round(self.write_s, 4),
            "wait_s": round(self.wait_s, 4),
            "hidden_s": round(self.hidden_s, 4),
            "hidden_fraction": round(self.hidden_fraction, 4),
        }


def overlap_from_traces(traces: list[WaveTrace]) -> tuple[float, float]:
    """``(span_wall, overlap_ratio)`` recomputed from the per-wave
    ``t_start``/``t_end`` timestamps.

    ``span_wall = max(t_end) − min(t_start)`` is the wall the waves
    themselves occupied, excluding scheduler loop overhead outside any
    wave — exactly what an exported trace file reconstructs, so
    ``EngineStats.overlap_ratio`` and ``launch/tracetool.py`` agree to
    float precision.  Falls back to ``(0, 0)`` for legacy traces that
    never carried timestamps (all-zero ``t_end``).
    """
    stamped = [t for t in traces if t.t_end > 0.0]
    if not stamped:
        return 0.0, 0.0
    span_wall = (max(t.t_end for t in stamped)
                 - min(t.t_start for t in stamped))
    g = sum(t.gather_s for t in stamped)
    s = sum(t.solve_s for t in stamped)
    return span_wall, overlap_ratio(g, s, span_wall)


def overlap_ratio(gather_s: float, solve_s: float, wall_s: float) -> float:
    """Fraction of total gather time hidden under solve time.

    ``Σgather + Σsolve − wall`` is the time the two tracks ran concurrently;
    dividing by ``Σgather`` expresses it as "how much of the gather bill was
    free".  Clamped to [0, 1]: measurement jitter can push the raw value
    slightly outside on tiny waves.
    """
    if gather_s <= 0.0:
        return 0.0
    return min(1.0, max(0.0, (gather_s + solve_s - wall_s) / gather_s))
