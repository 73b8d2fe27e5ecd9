"""Prefetching wave scheduler — the asynchronous round-0 execution engine.

Round-0 ingestion is a sequence of waves; each wave is (1) a host *gather*
(source reads + numpy assembly of the ``(W·μ, d+a)`` candidate matrix) and
(2) a device *solve* (upload, ``run_round`` dispatch, best-solution fold).
The synchronous reference serializes the two per wave:

    g0 → s0 → g1 → s1 → g2 → s2 ...          wall = Σg + Σs

The pipelined engine double-buffers: a producer thread gathers wave t+1
while the consumer (caller thread) solves wave t, with a bounded in-flight
buffer budget providing backpressure:

    g0 → s0  s1  s2 ...
          g1  g2  g3 ...                     wall ≈ g0 + max(Σg, Σs)

Wave *count* may be dynamic: with the PR 5 adaptive autoscaler
(:mod:`repro.engine.autotune`) each wave's width — and therefore how many
waves a round takes — is decided while the round runs, so ``run_waves``
accepts either a static wave count or open-ended iteration where
``gather(i)`` returns ``None`` once the machine range is exhausted.  The
``on_trace`` hook feeds each completed :class:`WaveTrace` back to the
caller (always on the caller thread, in wave order) — that is the
autotuner's measurement stream.

Correctness contract (pinned by tests/test_engine.py + test_autotune.py):

  * **Bit-identity** — the consumer invokes ``solve`` strictly in wave
    order on exactly the host buffers ``gather`` produced, so fold order,
    PRNG key alignment, and failure injection are untouched; pipelined
    output is bit-identical to the sync engine's for any gather/solve
    pair that is itself deterministic, under ANY width trajectory.
  * **Backpressure** — at most ``max_in_flight`` gathered host wave
    buffers exist at any instant (a counting semaphore is acquired before
    a gather starts and released once the wave's buffers have been handed
    to the device); the observed high-water mark is recorded on
    :class:`EngineStats` and asserted ≤ the bound in tests.
  * **All JAX work stays on the caller thread** — the producer touches
    only the source and numpy, so device order is identical to the sync
    engine even under a mesh.

``solve`` returns a device value the engine blocks on; both engines block
identically, which is what makes their per-wave ``solve_s`` columns (and
therefore the measured overlap ratio) comparable.
"""
from __future__ import annotations

import dataclasses
import queue
import sys
import threading
import time
import warnings
from typing import Any, Callable, NamedTuple

import jax

from repro.engine.stats import EngineStats, WaveTrace, overlap_from_traces
from repro.engine.telemetry import span

ENGINES = ("sync", "pipelined")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """How round-0 ingestion executes (orthogonal to *what* it computes).

    The chunk-prefetch depth deliberately is NOT here: the engine never
    touches sources — that knob lives on
    :class:`repro.core.sources.GroundSetSource.prefetch_depth` (set from
    ``TreeConfig.prefetch_depth`` by the tree driver).
    """
    mode: str = "sync"          # sync | pipelined
    max_in_flight: int = 2      # host wave buffers alive at once (pipelined)
    hosts: int = 1              # ingestion hosts sharding the gather
    join_timeout_s: float = 30.0  # producer shutdown grace before the leak
    #                               is reported instead of silently ignored

    def __post_init__(self):
        assert self.mode in ENGINES, self.mode
        assert self.max_in_flight >= 2, (
            f"pipelining needs ≥ 2 wave buffers (got {self.max_in_flight})")
        assert self.hosts >= 1, self.hosts
        assert self.join_timeout_s > 0, self.join_timeout_s


class HostWave(NamedTuple):
    """One gathered wave: host payload + accounting, produced by ``gather``."""
    payload: Any                # opaque to the engine; consumed by ``solve``
    machines: int
    rows: int
    bytes_moved: int
    per_host_rows: list[int] | None = None
    last: bool = False          # no wave follows: the engine asks for none


class _Abort(Exception):
    """Producer-side signal that the consumer bailed; never escapes."""


def run_waves(n_waves: int | None,
              gather: Callable[[int], HostWave | None],
              solve: Callable[[int, Any], Any],
              cfg: EngineConfig,
              on_trace: Callable[[WaveTrace], None] | None = None,
              tracer=None,
              ) -> EngineStats:
    """Drive gather→solve wave pairs under ``cfg.mode``.

    ``gather(i)`` produces wave i's host buffers (called from a background
    thread in pipelined mode — it must not touch JAX); ``solve(i, payload)``
    uploads and dispatches wave i (always called on the caller thread, in
    wave order) and returns a device value to block on.

    ``n_waves=None`` selects open-ended iteration: ``gather`` is called
    with increasing ``i`` until it returns ``None`` or a wave marked
    ``last`` (the adaptive planner deciding widths on the fly cannot know
    the wave count up front).  With an int, exactly that many waves run
    and ``gather`` never returns None.

    ``on_trace`` (if given) receives each completed :class:`WaveTrace` on
    the caller thread, in wave order, *before* the next solve starts —
    the autotuner's feedback point.

    Every wave opens a ``wave.gather`` span around ``gather`` and a
    ``wave.solve`` span around ``solve`` and the block on its result
    (``wave.block``), each on the thread that did the work, so producer
    and consumer land on separate profiler lines and Tracer tracks; the
    pipelined engine adds ``stall.sem-block`` / ``stall.queue-wait``
    spans for backpressure.  ``tracer`` (a
    :class:`repro.engine.telemetry.Tracer`, or None) records them too.
    The waves' ``WaveTrace`` stamps are the spans' own readings.
    Telemetry is observation only: the engine's scheduling decisions and
    outputs are identical with or without it.
    """
    if cfg.mode == "sync":
        return _run_sync(n_waves, gather, solve, cfg, on_trace, tracer)
    return _run_pipelined(n_waves, gather, solve, cfg, on_trace, tracer)


def _block(x, tracer, wave: int) -> None:
    with span("wave.block", tracer=tracer, wave=wave):
        if x is not None:
            jax.block_until_ready(x)


def _finalize(engine: str, cfg: EngineConfig, traces: list[WaveTrace],
              wall_s: float, max_live: int) -> EngineStats:
    g = sum(t.gather_s for t in traces)
    s = sum(t.solve_s for t in traces)
    # overlap is recomputed from the waves' t_start/t_end timestamps (the
    # reconstruction a trace-file consumer performs)
    span_wall, span_overlap = overlap_from_traces(traces)
    return EngineStats(
        engine=engine, hosts=cfg.hosts, waves=len(traces), wall_s=wall_s,
        gather_s=g, solve_s=s,
        bytes_moved=sum(t.bytes_moved for t in traces),
        overlap_ratio=span_overlap if engine == "pipelined" else 0.0,
        max_in_flight=max_live, traces=traces, span_wall_s=span_wall)


def _run_sync(n_waves, gather, solve, cfg, on_trace, tracer=None
              ) -> EngineStats:
    """The bit-identity reference: gather and solve strictly serialized."""
    traces: list[WaveTrace] = []
    t_start = time.perf_counter()
    i = 0
    while n_waves is None or i < n_waves:
        with span("wave.gather", tracer=tracer, wave=i) as g:
            hw = gather(i)
            if hw is not None:
                g.args.update(machines=hw.machines, rows=hw.rows,
                              bytes=hw.bytes_moved)
        if hw is None:
            assert n_waves is None, f"gather({i}) returned None mid-count"
            break
        with span("wave.solve", tracer=tracer, wave=i,
                  machines=hw.machines) as s:
            _block(solve(i, hw.payload), tracer, i)
        traces.append(WaveTrace(
            wave=i, machines=hw.machines, rows=hw.rows,
            bytes_moved=hw.bytes_moved, gather_s=g.t1 - g.t0,
            solve_s=s.t1 - s.t0, per_host_rows=hw.per_host_rows,
            t_start=g.t0, t_end=s.t1))
        if on_trace is not None:
            on_trace(traces[-1])
        i += 1
        if hw.last:
            break
    return _finalize("sync", cfg, traces,
                     time.perf_counter() - t_start, max_live=1)


class _BufferGauge:
    """Counts live gathered wave buffers; enforces and records the bound."""

    def __init__(self, limit: int):
        self._sem = threading.Semaphore(limit)
        self._lock = threading.Lock()
        self._live = 0
        self.high_water = 0

    def acquire(self, abort: threading.Event) -> bool:
        while not self._sem.acquire(timeout=0.1):
            if abort.is_set():
                return False
        with self._lock:
            self._live += 1
            self.high_water = max(self.high_water, self._live)
        return True

    def release(self) -> None:
        with self._lock:
            self._live -= 1
        self._sem.release()


_DONE = object()   # producer → consumer: no more waves (dynamic mode)
_FAILED = object()  # producer → consumer: exception parked in the slot


def _run_pipelined(n_waves, gather, solve, cfg, on_trace, tracer=None
                   ) -> EngineStats:
    """Double-buffered engine: wave t+1 gathers while wave t solves."""
    out: queue.Queue = queue.Queue(maxsize=max(1, cfg.max_in_flight - 1))
    abort = threading.Event()
    gauge = _BufferGauge(cfg.max_in_flight)
    # producer exception lands HERE first, before any queue traffic: the
    # queue wake-up below is best-effort (the consumer may have bailed and
    # set abort, making _put give up), but the slot is plain shared state —
    # as long as the consumer is alive it re-checks the slot and the
    # exception cannot be lost to a queue race.
    exc_slot: list[BaseException] = []

    def _put(item) -> bool:
        """Bounded put that honors the abort flag (never blocks forever)."""
        while not abort.is_set():
            try:
                out.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    _IDLE = (0.0, 0.0, 0.0)  # (t_gather0, t_gather1, stall) for sentinels

    def produce():
        try:
            i = 0
            while n_waves is None or i < n_waves:
                # backpressure: a wave's buffer is born here and freed by
                # the consumer only after its payload reached the device.
                # Time spent blocked on the semaphore is the producer-side
                # stall — the device is the bottleneck while it grows.
                with span("stall.sem-block", tracer=tracer, wave=i,
                          side="producer") as sb:
                    acquired = gauge.acquire(abort)
                if not acquired:
                    raise _Abort
                stall = sb.t1 - sb.t0
                with span("wave.gather", tracer=tracer, wave=i) as g:
                    hw = gather(i)
                    if hw is not None:
                        g.args.update(machines=hw.machines, rows=hw.rows,
                                      bytes=hw.bytes_moved)
                if hw is None:
                    assert n_waves is None, f"gather({i}) None mid-count"
                    gauge.release()
                    break
                if tracer is not None:
                    tracer.metrics.histogram(
                        "scheduler.stall_s", side="producer").observe(stall)
                if not _put((i, hw, g.t1 - g.t0, (g.t0, g.t1, stall))):
                    raise _Abort
                i += 1
                if hw.last:
                    break
            _put((_DONE, None, 0.0, _IDLE))
        except _Abort:
            pass
        except BaseException as exc:  # surface source errors on the caller
            exc_slot.append(exc)
            _put((_FAILED, None, 0.0, _IDLE))

    producer = threading.Thread(target=produce, name="wave-prefetch",
                                daemon=True)
    traces: list[WaveTrace] = []
    t_start = time.perf_counter()
    producer.start()
    try:
        expect = 0
        while True:
            # consumer-side stall: waiting for the producer to deliver the
            # next gathered wave — the gather is the bottleneck while it
            # grows (for wave 0 this is the unavoidable pipeline fill, g0)
            with span("stall.queue-wait", tracer=tracer, wave=expect,
                      side="consumer") as qw:
                i, hw, gather_s, (g0, g1, p_stall) = out.get()
            if i is _FAILED:
                raise exc_slot[0]
            if i is _DONE:
                break
            assert i == expect, f"wave order broke: got {i}, want {expect}"
            wait = qw.t1 - qw.t0
            with span("wave.solve", tracer=tracer, wave=i,
                      machines=hw.machines) as s:
                handle = solve(i, hw.payload)
                # payload is on device once solve returns — free its buffer
                # credit so the producer may gather the wave after next
                gauge.release()
                _block(handle, tracer, i)
            if tracer is not None:
                tracer.metrics.histogram(
                    "scheduler.stall_s", side="consumer").observe(wait)
            traces.append(WaveTrace(
                wave=i, machines=hw.machines, rows=hw.rows,
                bytes_moved=hw.bytes_moved, gather_s=gather_s,
                solve_s=s.t1 - s.t0, per_host_rows=hw.per_host_rows,
                t_start=g0, t_end=s.t1, stall_s=p_stall + wait))
            if on_trace is not None:
                on_trace(traces[-1])
            expect += 1
    finally:
        abort.set()
        producer.join(timeout=cfg.join_timeout_s)
        if producer.is_alive():
            # a gather is stuck past the shutdown grace: the thread is
            # leaked.  Raise when nothing else is propagating; otherwise
            # annotate the in-flight exception instead of masking it.
            msg = (f"wave-prefetch producer failed to stop within "
                   f"{cfg.join_timeout_s}s of shutdown — a gather call is "
                   f"hung and its thread is leaked (wrap the source in the "
                   f"fault supervisor's deadline to bound gathers)")
            in_flight = sys.exc_info()[1]
            if in_flight is None:
                raise RuntimeError(msg)
            if hasattr(in_flight, "add_note"):        # py ≥ 3.11
                in_flight.add_note(msg)
            else:
                warnings.warn(msg, RuntimeWarning)
        elif exc_slot and sys.exc_info()[1] is None:
            # producer failed after the consumer finished draining (its
            # queue wake-up lost the race with a completed loop): the
            # slot guarantees the error still surfaces
            raise exc_slot[0]
    return _finalize("pipelined", cfg, traces,
                     time.perf_counter() - t_start,
                     max_live=gauge.high_water)
