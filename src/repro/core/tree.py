"""TREE-BASED COMPRESSION — Algorithm 1 of the paper, end to end.

Host-level driver around :mod:`repro.core.distributed`:

  A₀ = V;  repeat: partition A_t into m_t = ⌈|A_t|/μ⌉ balanced parts →
  run the β-nice algorithm on every part in parallel → keep the best
  partial solution seen → A_{t+1} = union of partial solutions;
  until |A_t| ≤ μ, then solve the final block on one machine.

Production features beyond the pseudo-code:
  * **device-resident rounds** (default): the candidate rows A_t, the
    repartition (:func:`repro.core.partition.repartition_rows`), and the
    best-solution tracking all stay on device between rounds — the only
    values that cross the device→host boundary inside the round loop are
    scalars (|A_t| for the next round's machine count, and the per-round
    best value for logging).  Round boundaries therefore never serialize
    on array transfers.  The legacy host-NumPy loop is kept as
    ``host_rounds=True`` (bit-identical output; used by tests and as the
    checkpoint-compatibility reference).
  * **hereditary constraints** (``constraint=`` + per-item ``attrs``):
    each machine's solve respects the constraint (Theorem 3.5's α/r then
    holds for the returned solution); the per-item attribute columns
    (knapsack weights, partition ids) are carried *with* their rows through
    every layer — partition gather, ingestion waves, between-round
    repartition, best-solution fold, checkpoints — as trailing columns of
    the candidate matrix, so streaming and all-resident stay bit-identical
    under every constraint class.  The returned coreset is re-verified by
    the independent pure-NumPy checker (:func:`constraints.check_feasible`).
  * round-level checkpointing (A_t is ≤ m_t·k rows — restartable at any
    round boundary; `checkpoint_dir=` + `resume=True`),
  * failure injection (`fail_machines`: solutions dropped, run continues),
  * oracle-call and round accounting (validates Prop. 3.1 and Table 1),
  * identical semantics serial (vmap) / distributed (shard_map over mesh).
"""
from __future__ import annotations

import dataclasses
import math
import os
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import constraints as cons_lib
from repro.core import partition as part_lib
from repro.core.distributed import (RoundResult, dead_wave_result,
                                    device_bytes, run_round,
                                    shard_round_inputs, stage_wave_inputs)
from repro.core.permute import FeistelPermutation, feistel_slot_items
from repro.core.sources import (ArraySource, GroundSetSource, as_source,
                                dtype_itemsize)
from repro.engine.autotune import (AutotuneCache, AutotunePlanner,
                                   FixedWidthPlanner, ScheduledWidthPlanner,
                                   WavePlanner, bucket_ladder, shape_bound,
                                   snap_down)
from repro.engine.checkpoint import (AsyncCheckpointWriter, clean_stale_tmp,
                                     latest_round_checkpoint,
                                     load_round_checkpoint,
                                     write_round_checkpoint)
from repro.engine.faults import FaultInjector, FaultPolicy, FaultSupervisor
from repro.engine.planner import IngestionPlan
from repro.engine.scheduler import (ENGINES, EngineConfig, HostWave,
                                    run_waves)
from repro.engine.stats import (CheckpointStats, EngineStats, FaultStats,
                                RoundCheckpoint)
from repro.engine.telemetry import (MANIFEST_NAME, build_manifest,
                                    dtype_label, feed_result_metrics, span)

PERMUTATIONS = ("dense", "feistel")


@dataclasses.dataclass(frozen=True)
class TreeConfig:
    k: int
    capacity: int                      # μ — max items per machine
    algorithm: str = "greedy"          # greedy | stochastic_greedy |
    #                                    threshold_greedy | threshold_batch
    eps: float = 0.5                   # for stochastic/threshold variants
    #                                    (threshold_batch: τ-ladder decay —
    #                                    the CLI's --batch-eps lands here)
    seed: int = 0
    checkpoint_dir: str | None = None
    resume: bool = False
    permutation: str = "dense"         # round-0 slot scheme: dense | feistel
    engine: str = "sync"               # round-0 wave engine: sync | pipelined
    hosts: int = 1                     # ingestion hosts sharding the gather
    max_in_flight: int = 2             # pipelined host wave buffers (≥ 2)
    capacity_bytes: int | None = None  # device-byte wave budget (derives W)
    wave_autotune: bool = False        # rate-tuned per-wave width controller
    async_checkpoint: bool = False     # background round-boundary writes
    prefetch_depth: int | None = None  # chunk-prefetch depth (None = default
    #                                    2, or autotuner-suggested downstream)
    fault_policy: FaultPolicy | None = None  # wave-gather supervision
    #                                    (retries/hedges/eviction/drops);
    #                                    None = legacy abort-on-first-error
    checkpoint_keep: int = 3           # rotated round checkpoints retained
    #                                    (≤ 0 keeps every round)
    checkpoint_delta_every: int = 0    # K > 0: full snapshot every K rounds,
    #                                    row-index deltas between (A_{t+1}
    #                                    rows are verbatim copies of A_t
    #                                    rows, so a delta is one int per
    #                                    row); 0 = every round full (legacy)
    autotune_cache: str | None = None  # JSON path persisting the
    #                                    autotuner's converged rung per
    #                                    (source fingerprint, μ, ndev) so
    #                                    reruns start at the knee
    telemetry: Any = None              # repro.engine.telemetry.Tracer, or
    #                                    None (default): records the spans
    #                                    every engine seam writes to the
    #                                    profiler, + a RunManifest next to
    #                                    the checkpoints.  Observation only —
    #                                    outputs are bit-identical either
    #                                    way

    def __post_init__(self):
        assert self.capacity > self.k, (
            f"paper requires μ > k (got μ={self.capacity}, k={self.k})")
        assert self.permutation in PERMUTATIONS, self.permutation
        assert self.engine in ENGINES, self.engine
        assert self.hosts >= 1, self.hosts
        assert self.max_in_flight >= 2, self.max_in_flight
        assert self.capacity_bytes is None or self.capacity_bytes > 0, (
            self.capacity_bytes)
        assert self.prefetch_depth is None or self.prefetch_depth >= 1, (
            self.prefetch_depth)
        assert self.checkpoint_delta_every >= 0, self.checkpoint_delta_every
        assert not self.async_checkpoint or self.checkpoint_dir, (
            "async_checkpoint=True without checkpoint_dir would silently "
            "write nothing — pass checkpoint_dir (CLI: --ckpt-dir)")

    def round_bound(self, n: int) -> int:
        """Prop. 3.1: r ≤ ⌈log_{μ/k}(n/μ)⌉ + 1."""
        mu, k = self.capacity, self.k
        if mu >= n:
            return 1
        return math.ceil(math.log(n / mu) / math.log(mu / k)) + 1

    def round_bound_exact(self, n: int) -> int:
        """Worst-case rounds from the exact recurrence
        |A_{t+1}| = ⌈|A_t|/μ⌉·k — tight even when μ ≈ k, where the ceil
        term slows the μ/k shrink that Prop 3.1 assumes."""
        mu, k = self.capacity, self.k
        t, cur = 0, n
        while cur > mu and t < 100_000:
            cur = math.ceil(cur / mu) * k
            t += 1
        return t + 1


@dataclasses.dataclass
class IngestStats:
    """Round-0 streaming-ingestion accounting (footprint guard evidence).

    Besides the footprint counters, every wave records its work time and
    host→device bytes — for the *synchronous* engine too, so the pipelined
    engine's overlap claims always have an honest same-struct baseline.

    ``wave_seconds[i]`` is wave i's gather + solve *work* time.  Under the
    sync engine the two are serialized, so it equals the wave's wall-clock
    and ``sum(wave_seconds) ≈ wall_seconds``; under the pipelined engine
    gathers overlap earlier solves, so the sum deliberately *exceeds*
    ``wall_seconds`` — that gap is exactly the hidden work the engine's
    ``overlap_ratio`` reports.
    """
    wave_machines: int          # W — starting machines per wave (the fixed
    #                             width, or the autotuner's initial rung;
    #                             per-wave widths: engine_stats trajectory)
    waves: int                  # number of waves in round 0
    peak_wave_rows: int         # max candidate rows materialized per wave
    peak_wave_bytes: int        # peak_wave_rows · (d + attr_dim) · itemsize
    total_machines: int         # Mp — mesh-padded machine count of round 0
    attr_dim: int = 0           # a — attribute columns riding with each row
    wave_seconds: list[float] = dataclasses.field(default_factory=list)
    wave_bytes: list[int] = dataclasses.field(default_factory=list)
    total_bytes: int = 0        # Σ wave_bytes (host→device candidate bytes)
    wall_seconds: float = 0.0   # whole-round-0 wall clock


@dataclasses.dataclass
class TreeResult:
    sel_rows: np.ndarray        # (k, d) best solution rows (zero-padded)
    sel_mask: np.ndarray        # (k,)
    value: float
    rounds: int
    oracle_calls: int
    machines_per_round: list[int]
    round_values: list[float]   # best machine value per round
    ingest: IngestStats | None = None   # set by the streaming round-0 path
    sel_attrs: np.ndarray | None = None  # (k, a) attrs of the selection
    engine_stats: EngineStats | None = None  # wave engine trace (round 0)
    checkpoint_stats: CheckpointStats | None = None  # per-round ckpt overlap
    fault_stats: FaultStats | None = None  # supervision record (retries,
    #                                        hedges, evictions, drops)
    round_walls: list[float] | None = None  # wall seconds per round, in
    #                                         round order (round 0 first)
    depth_per_round: list[int] | None = None  # per-round sequential solve
    #                             depth: max over the round's machines of
    #                             the dependent kernel launches their solve
    #                             paid (machines run in parallel)
    solve_depth: int = 0        # Σ depth_per_round — the tree's end-to-end
    #                             adaptive depth on the solve track (greedy
    #                             pays k per round; threshold_batch pays
    #                             one τ-ladder per round)
    total_wall_s: float = 0.0   # whole tree_maximize wall clock
    manifest: Any = None        # repro.engine.telemetry.RunManifest when
    #                             cfg.telemetry was attached (also written
    #                             atomically next to the checkpoints)


# ---------------------------------------------------------------------------
# host-boundary helpers — the ONLY device→host crossings of the round loop.
# Tests monkeypatch / guard these to certify the loop is device-resident.
# ---------------------------------------------------------------------------


def _host_scalar(x) -> float:
    """Pull a 0-d device value to host (round-loop sanctioned crossing)."""
    assert jnp.ndim(x) == 0, f"round loop may only transfer scalars, got {jnp.shape(x)}"
    with jax.transfer_guard_device_to_host("allow"):
        return float(x)


def _host_array(x) -> np.ndarray:
    """Bulk device→host pull — final result + checkpoint writes only."""
    with jax.transfer_guard_device_to_host("allow"):
        return np.asarray(x)


def _ckpt_path(d: str) -> str:
    return os.path.join(d, "tree_round.npz")


def _save_round(d: str, round_idx: int, rows, mask, best_rows, best_mask,
                best_val, calls, keep: int = 3, delta_every: int = 0):
    """One round-boundary snapshot: rotated per-round file + the legacy
    ``tree_round.npz`` latest pointer, both atomic; only the newest ``keep``
    rotated rounds survive (engine/checkpoint.py owns the file layout).
    ``delta_every`` > 0 writes row-index deltas against the previous round
    with a full snapshot every ``delta_every`` rounds (resume bit-identical;
    rotation keeps every retained delta's ancestor chain)."""
    write_round_checkpoint(d, round_idx, keep=keep, delta_every=delta_every,
                           rows=rows, mask=mask,
                           best_rows=best_rows, best_mask=best_mask,
                           best_val=best_val, calls=calls)


def _resume_path(d: str) -> str | None:
    """Newest complete checkpoint; sweeps crashed writers' tmp litter first."""
    removed = clean_stale_tmp(d)
    if removed:
        import warnings
        warnings.warn(f"removed {len(removed)} stale checkpoint tmp file(s) "
                      f"left by a crashed writer in {d}", RuntimeWarning)
    return latest_round_checkpoint(d)


def _round_plan(kalg, M: int, t: int, fail_machines, mesh):
    """Mesh-padded machine count, per-machine PRNG keys, and failure mask
    for one round.  The one-shot dispatch and the streaming wave loop both
    consume this — their bit-identity depends on it staying one copy."""
    ndev = mesh.devices.size if mesh is not None else 1
    Mp = math.ceil(M / ndev) * ndev
    keys = jax.random.split(kalg, Mp)
    dead = np.zeros((Mp,), bool)
    for mid in fail_machines.get(t, []):
        if mid < Mp:
            dead[mid] = True
    return Mp, keys, dead


def _dispatch_blocks(obj, blocks, bmask, keys, dead, cfg: TreeConfig,
                     mesh, attr_dim=0, constraint=None,
                     meta=None) -> RoundResult:
    """Shard and solve one contiguous slab of machine blocks (a full round
    or one ingestion wave) with its pre-split keys and failure mask.
    ``meta`` is the quantized waves' out-of-band fp32 [attrs | qmeta]
    operand (None on the fp32 path — dispatch byte-identical to PR 6)."""
    if mesh is not None:
        if meta is None:
            blocks, bmask, keys = shard_round_inputs(mesh, blocks, bmask,
                                                     keys)
        else:
            blocks, bmask, keys, meta = shard_round_inputs(
                mesh, blocks, bmask, keys, meta)
    return run_round(obj, blocks, bmask, keys, k=cfg.k, alg=cfg.algorithm,
                     eps=cfg.eps, dead_mask=jnp.asarray(dead), mesh=mesh,
                     attr_dim=attr_dim, constraint=constraint, meta=meta)


def _dispatch_round(obj, blocks, bmask, kalg, t, cfg: TreeConfig, mesh,
                    fail_machines, attr_dim=0, constraint=None) -> RoundResult:
    """Mesh-pad the machine axis, split keys, apply failure injection and
    solve one round.  Shared verbatim by the device-resident and legacy
    host drivers."""
    M = blocks.shape[0]
    Mp, keys, dead = _round_plan(kalg, M, t, fail_machines, mesh)
    if Mp != M:
        blocks = jnp.pad(blocks, ((0, Mp - M), (0, 0), (0, 0)))
        bmask = jnp.pad(bmask, ((0, Mp - M), (0, 0)))
    return _dispatch_blocks(obj, blocks, bmask, keys, dead, cfg, mesh,
                            attr_dim=attr_dim, constraint=constraint)


@jax.jit
def _fold_round(res_rows, res_mask, res_vals, res_calls, res_depth,
                best_rows, best_mask, best_val, total_calls, round_depth):
    """Device-side best-solution tracking (old host argmax, jitted).

    ``round_depth`` is the running max of per-machine sequential solve
    depth across the folds of one round (machines — and waves — run in
    parallel, so a round's adaptive depth is a max, not a sum)."""
    i_best = jnp.argmax(res_vals)                  # lowest index on ties
    v_best = res_vals[i_best]
    improved = v_best > best_val
    best_rows = jnp.where(improved, res_rows[i_best], best_rows)
    best_mask = jnp.where(improved, res_mask[i_best], best_mask)
    best_val = jnp.where(improved, v_best, best_val)
    total_calls = total_calls + jnp.sum(res_calls)
    round_depth = jnp.maximum(round_depth, jnp.max(res_depth))
    return best_rows, best_mask, best_val, total_calls, round_depth, v_best


def _fast_forward_key(key, start_round: int):
    """Replay the per-round key-chain splits consumed before ``start_round``
    so a resumed run partitions round t exactly like an uninterrupted one."""
    for _ in range(start_round):
        key, _, _ = jax.random.split(key, 3)
    return key


def _round0_slot_blocks(kpart, n: int, L: int, Mp: int, mu: int,
                        scheme: str):
    """Round-0 virtual-location assignment as a sliceable provider.

    Returns ``slot_block(w0, w1) -> (w1-w0, μ) int32`` of item indices
    (-1 on empty/padded slots) for machines ``[w0, w1)``.

      * ``dense`` — materializes :func:`partition.balanced_partition`'s
        permutation on host (O(n_slots) int32, the legacy scheme; also the
        cross-check path for the Feistel scheme in tests).
      * ``feistel`` — a counter-based keyed bijection evaluated per slice
        (:mod:`repro.core.permute`): O(1) host state regardless of n, so
        the last n-sized host buffer of the streaming path disappears.
    """
    if scheme == "feistel":
        perm = FeistelPermutation.from_key(kpart, L * mu)

        def slot_block(w0: int, w1: int) -> np.ndarray:
            mids = np.arange(w0, w1, dtype=np.int64)
            slots = (mids[:, None] * mu + np.arange(mu)[None, :])
            out = np.full((w1 - w0, mu), -1, np.int32)
            live = mids < L                       # mesh-padded machines empty
            if live.any():
                out[live] = feistel_slot_items(perm, n, slots[live])
            return out
    else:
        part = part_lib.balanced_partition(kpart, n, L, cap=mu)
        slot_item = _host_array(part.idx)                   # (L, cap) int32
        if Mp != L:                                         # padded machines
            slot_item = np.concatenate(
                [slot_item, np.full((Mp - L, mu), -1, slot_item.dtype)])

        def slot_block(w0: int, w1: int) -> np.ndarray:
            return slot_item[w0:w1]

    return slot_block


def _round0_partition(kpart, n: int, L: int, mu: int,
                      scheme: str) -> part_lib.Partition:
    """Round-0 partition for the all-resident drivers.

    ``dense`` is :func:`partition.balanced_partition` unchanged; ``feistel``
    materializes the same keyed bijection the streaming path evaluates per
    wave, so resident and streaming stay bit-identical under either scheme
    (and the materialization doubles as the cross-check in tests).
    """
    if scheme != "feistel":
        return part_lib.balanced_partition(kpart, n, L, cap=mu)
    perm = FeistelPermutation.from_key(kpart, L * mu)
    slot_item = feistel_slot_items(
        perm, n, np.arange(L * mu, dtype=np.int64)).reshape(L, mu)
    idx = jnp.asarray(slot_item)
    return part_lib.Partition(idx, idx >= 0)


def _wave_row_bytes(mu: int, width: int, itemsize: int = 4,
                    meta_cols: int = 0) -> int:
    """Device bytes one machine's block costs: μ rows of ``width`` feature
    columns at the storage itemsize plus ``meta_cols`` fp32 out-of-band
    columns (attrs + dequant params of quantized waves).  The fp32
    unquantized path reduces to exactly the historical ``μ·(d+a)·4``."""
    return mu * (width * itemsize + meta_cols * 4)


def _wave_size(cfg: TreeConfig, wave_machines, ndev: int, Mp: int,
               mu: int, width: int, itemsize: int = 4,
               meta_cols: int = 0) -> int:
    """Resolve the wave size W (machines per wave, a device multiple).

    Precedence: explicit ``wave_machines`` (rounded *up* to a device
    multiple, legacy semantics; validated against ``cfg.capacity_bytes``
    up front when both are given — the byte budget is always a hard
    bound) → ``cfg.capacity_bytes`` alone (weighted-μ capacity: the
    largest device-multiple W whose wave matrix — ``width`` feature
    columns at the storage ``itemsize`` plus ``meta_cols`` fp32 metadata
    columns — fits the budget, rounded *down*) → one mesh sweep (W=ndev).
    Narrow storage dtypes shrink the per-row bytes, so the same byte
    budget admits proportionally wider waves (the bytes-lean win).
    """
    row_bytes = _wave_row_bytes(mu, width, itemsize, meta_cols)
    if wave_machines is not None:
        W = min(Mp, math.ceil(wave_machines / ndev) * ndev)
        if cfg.capacity_bytes is not None and W * row_bytes > cfg.capacity_bytes:
            raise ValueError(
                f"wave_machines={wave_machines} (W={W} after device "
                f"rounding) needs {W * row_bytes} bytes/wave, over the "
                f"capacity_bytes={cfg.capacity_bytes} budget — drop one "
                f"of the two or raise the budget")
        return W
    if cfg.capacity_bytes is not None:
        min_wave = ndev * row_bytes
        if cfg.capacity_bytes < min_wave:
            raise ValueError(
                f"capacity_bytes={cfg.capacity_bytes} cannot fit one "
                f"device-multiple wave: {ndev} devices × μ={mu} rows × "
                f"({width}×{itemsize}B + {meta_cols}×4B) columns = "
                f"{min_wave} bytes")
        return min(Mp, (cfg.capacity_bytes // row_bytes) // ndev * ndev)
    return min(Mp, ndev)


def _wave_planner(cfg: TreeConfig, W0: int, ndev: int, Mp: int, mu: int,
                  width: int, wave_machines, wave_schedule,
                  itemsize: int = 4, meta_cols: int = 0
                  ) -> tuple[WavePlanner, list[int] | None]:
    """Width policy for one round-0 run: ``(planner, ladder_or_None)``.

    Precedence: an explicit ``wave_schedule`` (test hook — adversarial
    trajectories) → ``cfg.wave_autotune`` (EWMA rate controller on the
    bucket ladder) → the legacy fixed width.

    The autoscaler's ladder cap is the caller's *capacity statement*:
    ``capacity_bytes`` when given (derived by the same :func:`_wave_size`
    the fixed path uses, so the weighted-μ byte semantics can never
    diverge), else an explicit ``wave_machines`` (the user bounded device
    rows at W·μ — retuning may only shrink waves below that, never grow
    past it), else the machine count Mp (no bound stated).  The ladder is
    returned so the caller can assert the re-jit bound; fixed/scheduled
    policies return None (fixed dispatches ≤ 2 shapes by construction,
    schedules are test-owned).
    """
    if wave_schedule is not None:
        return ScheduledWidthPlanner(list(wave_schedule)), None
    if not cfg.wave_autotune:
        return FixedWidthPlanner(W0), None
    if cfg.capacity_bytes is not None:
        w_cap = _wave_size(cfg, None, ndev, Mp, mu, width, itemsize,
                           meta_cols)
    elif wave_machines is not None:
        w_cap = W0                 # W·μ rows is the stated device budget
    else:
        w_cap = Mp
    ladder = bucket_ladder(ndev, max(w_cap, ndev))
    return AutotunePlanner(ladder, snap_down(ladder, max(W0, ndev))), ladder


def _stream_round0(obj, source: GroundSetSource, kpart, kalg, L: int,
                   cfg: TreeConfig, mesh, fail_machines, wave_machines,
                   best_rows, best_mask, best_val, total_calls,
                   constraint=None, attrs_np: np.ndarray | None = None,
                   wave_schedule=None, fault_injector=None):
    """Wave-scheduled round-0 ingestion: capacity-bounded replacement for
    ``gather_partition`` over an all-resident ground set.

    The virtual-location permutation assigns every item a (machine, slot)
    exactly as :func:`repro.core.partition.balanced_partition` does (or via
    the O(1)-state Feistel scheme, ``cfg.permutation="feistel"``); machine
    blocks are then filled from the source — per-item attribute rows
    re-gathered alongside and appended as trailing block columns — and
    dispatched in waves of W = mesh-device multiples, folding each wave's
    solutions into the running best via :func:`_fold_round`.  Peak device
    footprint is O(W·μ·(d+a)) candidate rows instead of O(n·(d+a)); for the
    same seed the per-machine blocks, PRNG keys, fold order, and the union
    A_1 are bit-identical to the all-resident dispatch.

    Wave *execution* is delegated to :mod:`repro.engine`: ``cfg.engine``
    picks the synchronous reference or the double-buffered pipelined
    scheduler (gather of wave t+1 overlaps solve of wave t), and
    ``cfg.hosts`` shards every wave's gather across ingestion hosts via
    the :class:`repro.engine.planner.IngestionPlan`.  Wave *widths* come
    from a :mod:`repro.engine.autotune` planner — fixed W (legacy), the
    rate-tuned autoscaler (``cfg.wave_autotune``), or an injected test
    schedule — decided per wave while the round runs.  All of these are
    execution knobs only — the blocks, keys, fold order and outputs stay
    bit-identical across every engine × hosts × width-trajectory
    combination (machine→wave batching is pure execution policy).
    """
    n, d, mu = source.n, source.d, cfg.capacity
    a = 0
    if constraint is not None:
        a = attrs_np.shape[1] if attrs_np is not None else source.a
    ndev = mesh.devices.size if mesh is not None else 1
    # bytes-lean ingestion: a narrow-storage source ships its wire dtype
    # to device (bf16/int8 feature blocks) with attrs + dequant params
    # riding out-of-band as one fp32 meta matrix; the solve dequantizes
    # in-kernel.  fp32 sources take the legacy path — byte-identical
    # blocks, no meta operand anywhere.
    feat_dtype = np.dtype(source.dtype)
    narrow = feat_dtype != np.dtype(np.float32)
    qcols = source.qcols if narrow else 0
    itemsize = dtype_itemsize(feat_dtype) if narrow else 4
    meta_cols = (a + qcols) if narrow else 0
    blk_width = d if narrow else d + a    # feature-block columns shipped
    # the full round's plan (padded count, key split, failure injection),
    # sliced per wave — machine i sees the same key and dead bit as in the
    # one-shot dispatch.
    Mp, keys, dead = _round_plan(kalg, L, 0, fail_machines, mesh)
    W = _wave_size(cfg, wave_machines, ndev, Mp, mu, blk_width, itemsize,
                   meta_cols)

    slot_block = _round0_slot_blocks(kpart, n, L, Mp, mu, cfg.permutation)
    ecfg = EngineConfig(mode=cfg.engine, max_in_flight=cfg.max_in_flight,
                        hosts=cfg.hosts)
    # the depth knob lands on the source: its default re-stream gathers
    # prefetch chunks at this depth (sliced host views delegate to the
    # parent, so one assignment covers every shard's gathers).  Only an
    # explicit config value overrides — a depth the caller already set on
    # the source object itself must survive the run
    if cfg.prefetch_depth is not None:
        source.prefetch_depth = cfg.prefetch_depth
    plan = IngestionPlan.build(source, cfg.hosts) if cfg.hosts > 1 else None
    planner, ladder = _wave_planner(cfg, W, ndev, Mp, mu, blk_width,
                                    wave_machines, wave_schedule,
                                    itemsize, meta_cols)
    tracer = cfg.telemetry
    if tracer is not None and isinstance(planner, AutotunePlanner):
        planner.tracer = tracer       # rung decisions → "autotune" instants
    # seed the autoscaler from a persisted converged rung (same source
    # fingerprint — n, d, storage dtype — μ and device count), and record
    # the rung it lands on for the next run
    cache: AutotuneCache | None = None
    cache_key: str | None = None
    if cfg.autotune_cache and isinstance(planner, AutotunePlanner):
        cache = AutotuneCache(cfg.autotune_cache)
        cache_key = f"{source.fingerprint()}|mu={mu}|ndev={ndev}"
        seeded = cache.get(cache_key)
        if seeded is not None and seeded >= ladder[0]:
            planner.seed(snap_down(ladder, min(int(seeded), ladder[-1])))
    cursor = {"w0": 0}    # wave spans are decided per wave by the planner;
    #                       gather runs on one thread in wave order, so a
    #                       plain dict cursor is race-free by construction
    plan_state = {"plan": plan}   # swapped on host eviction (re-plan); only
    #                               ever touched from the gather side

    # ---- fault supervision (PR 6): active only when asked for — the
    # legacy abort-on-first-error path is byte-for-byte untouched otherwise
    supervisor: FaultSupervisor | None = None
    if cfg.fault_policy is not None or fault_injector is not None:
        def evict_host(host: int) -> bool:
            p = plan_state["plan"]
            if p is None or p.hosts < 2 or host not in p.host_ids:
                return False
            plan_state["plan"] = p.evict(host)
            return True

        supervisor = FaultSupervisor(
            cfg.fault_policy or FaultPolicy(), total_rows=n,
            injector=fault_injector, rate_hint=planner.gather_rate,
            concurrent_ok=source.supports_concurrent_gather,
            evict_cb=evict_host, tracer=tracer)

    def next_span():
        w0 = cursor["w0"]
        if w0 >= Mp:
            return None
        w = min(planner.next_width(Mp - w0), Mp - w0)
        assert w >= 1, w
        cursor["w0"] = w0 + w
        return w0, w0 + w

    def gather_rows(idx_flat: np.ndarray, fault_hook=None,
                    wave: int | None = None):
        """Rows (+ attrs when constrained) for one wave, a single source
        pass: sequential sources must not be re-streamed once per matrix.
        With ``hosts > 1`` the pass is sharded: each ingestion host serves
        the indices it owns and the planner stitches them in index order.
        ``fault_hook`` is the injector's per-host chaos seam."""
        p = plan_state["plan"]
        if p is not None:
            rows, src_attrs, per_host = p.gather(
                idx_flat, with_attrs=bool(a) and attrs_np is None,
                parallel=ecfg.mode == "pipelined", fault_hook=fault_hook,
                tracer=tracer, wave=wave)
            row_attrs = (attrs_np[idx_flat] if a and attrs_np is not None
                         else src_attrs)
            return rows, row_attrs, per_host
        if not a:
            return source.gather(idx_flat), None, None
        if attrs_np is not None:
            return source.gather(idx_flat), attrs_np[idx_flat], None
        rows, row_attrs = source.gather_with_attrs(idx_flat)
        return rows, row_attrs, None

    def gather(i: int) -> HostWave | None:
        """Host side of wave i: source reads (``wave.read``) + numpy block
        assembly (``wave.mask``).  Runs on the prefetch thread under the
        pipelined engine — no device work."""
        bounds = next_span()
        if bounds is None:
            return None                                     # machines done
        w0, w1 = bounds
        last = w1 >= Mp
        idx_w = slot_block(w0, w1)                          # (Wb, cap)
        idx_flat = np.maximum(idx_w, 0).reshape(-1)
        valid = idx_w >= 0
        with span("wave.read", tracer=tracer, wave=i):
            if supervisor is None:
                rows, row_attrs, per_host = gather_rows(idx_flat, wave=i)
                dropped = False
            else:
                def attempt_fn(attempt: int):
                    hook = (fault_injector.host_hook(i, attempt)
                            if fault_injector is not None else None)
                    return gather_rows(idx_flat, fault_hook=hook, wave=i)

                gathered, dropped = supervisor.gather(
                    i, machines=w1 - w0, rows=int(valid.sum()),
                    attempt_fn=attempt_fn)
                if not dropped:
                    rows, row_attrs, per_host = gathered
            if narrow and qcols and not dropped:
                qmeta = source.gather_qmeta(idx_flat)
        if dropped:
            # wave forfeited (Lemma 3.4 budget already checked): its
            # machines fold as dead downstream — no rows move
            return HostWave(payload=(None, None, valid, w0, w1, True),
                            machines=w1 - w0, rows=(w1 - w0) * mu,
                            bytes_moved=0, per_host_rows=None, last=last)
        with span("wave.mask", tracer=tracer, wave=i):
            payload = mask_wave(rows, row_attrs,
                                qmeta if narrow and qcols else None,
                                valid, w0, w1)
        return HostWave(payload=payload, machines=w1 - w0,
                        rows=(w1 - w0) * mu,
                        bytes_moved=sum(x.nbytes for x in payload[:2]
                                        if x is not None),
                        per_host_rows=per_host, last=last)

    def mask_wave(rows, row_attrs, qmeta, valid, w0, w1):
        """The wave's payload from its gathered rows: machine blocks with
        padded slots zeroed (plus the narrow path's fp32 meta matrix)."""
        if narrow:
            # narrow wire format: the feature block keeps the storage
            # dtype end-to-end; attrs + per-row dequant params ship as one
            # fp32 meta matrix.  Padded slots are zeroed in both (masked
            # rows dequantize to 0·0+0 = 0, matching the fp32 path's
            # zeroed rows exactly).
            feat = np.asarray(rows).reshape(w1 - w0, mu, d).copy()
            feat[~valid] = feat_dtype.type(0)
            cols = []
            if a:
                cols.append(np.asarray(row_attrs, np.float32))
            if qcols:
                cols.append(qmeta)
            if cols:
                meta = np.concatenate(cols, axis=1).reshape(
                    w1 - w0, mu, meta_cols)
                meta = np.where(valid[..., None], meta, np.float32(0.0))
            else:
                meta = np.zeros((w1 - w0, mu, 0), np.float32)
            return feat, meta, valid, w0, w1, False
        rows = np.asarray(rows, np.float32)
        if a:
            rows = np.concatenate(
                [rows, np.asarray(row_attrs, np.float32)], axis=1)
        # zero padded slots on host (gathers may return read-only buffers);
        # bit-identical to the device-side jnp.where masking it replaces
        blocks = np.where(valid[..., None],
                          rows.reshape(w1 - w0, mu, d + a), np.float32(0.0))
        return blocks, None, valid, w0, w1, False

    sol_rows, sol_mask = [], []
    stage_bytes: dict[int, list[int]] = {}    # wave -> bytes per device
    carry = [best_rows, best_mask, best_val, total_calls,
             jnp.int32(0),                                 # round-depth max
             jnp.float32(-jnp.inf)]                        # [..., v_round]

    def solve(i: int, payload) -> jax.Array:
        """Device side of wave i: upload, dispatch, fold.  Always called on
        the caller thread in wave order, so the sequential strict-
        improvement fold over waves == the one-shot argmax over all Mp
        machines (lowest machine index on ties)."""
        blocks_np, meta_np, valid, w0, w1, wave_dropped = payload
        if wave_dropped:
            # the gather never succeeded, so these machines never ran:
            # fold the dead_mask placeholder (−inf values can never win,
            # masked solutions contribute nothing to A_1, zero oracle
            # calls — honest accounting) and skip the dispatch entirely
            res = dead_wave_result(w1 - w0, cfg.k, d + a)
        else:
            with span("wave.stage", tracer=tracer, wave=i):
                staged = stage_wave_inputs(mesh, blocks_np, valid, meta_np,
                                           tracer, i)
                # the upload returns before the copy lands; the round
                # program waits on these arrays anyway
                jax.block_until_ready(staged)
            stage_bytes[i] = device_bytes(mesh, staged)
            blocks, bmask, *meta = staged      # meta: narrow waves only
            with span("wave.dispatch", tracer=tracer, wave=i):
                res = _dispatch_blocks(obj, blocks, bmask, keys[w0:w1],
                                       dead[w0:w1], cfg, mesh, attr_dim=a,
                                       constraint=constraint,
                                       meta=meta[0] if meta else None)
        with span("wave.fold", tracer=tracer, wave=i):
            (carry[0], carry[1], carry[2], carry[3], carry[4],
             v_wave) = _fold_round(
                res.sol_rows, res.sol_mask, res.values, res.oracle_calls,
                res.depth, *carry[:5])
        carry[5] = jnp.maximum(carry[5], v_wave)
        sol_rows.append(res.sol_rows)
        sol_mask.append(res.sol_mask)
        return v_wave

    estats = run_waves(None, gather, solve, ecfg, on_trace=planner.observe,
                       tracer=tracer)
    if supervisor is not None:
        estats.fault_stats = supervisor.stats
    estats.mesh_devices = ndev
    estats.stage_bytes = [stage_bytes.get(t.wave, [0] * ndev)
                          for t in estats.traces]
    (best_rows, best_mask, best_val, total_calls, round_depth,
     v_round) = carry

    assert cursor["w0"] == Mp and sum(
        t.machines for t in estats.traces) == Mp, (cursor["w0"], Mp)
    if ladder is not None:
        # the re-jit bound: every dispatched width is a ladder rung, so a
        # run compiles at most ⌊log2(W_max/ndev)⌋ + 2 distinct wave shapes
        assert set(estats.width_trajectory) <= set(ladder), (
            estats.width_trajectory, ladder)
        assert estats.distinct_shapes <= shape_bound(ndev, ladder[-1]), (
            estats.distinct_shapes, ladder)

    if cache is not None:
        cache.put(cache_key, planner.converged_width())

    rows_in = jnp.concatenate(sol_rows).reshape(-1, d + a)  # union A_1
    mask_in = jnp.concatenate(sol_mask).reshape(-1)
    peak_rows = max(t.rows for t in estats.traces)
    stats = IngestStats(
        wave_machines=W, waves=estats.waves, peak_wave_rows=peak_rows,
        peak_wave_bytes=peak_rows * (blk_width * itemsize + meta_cols * 4),
        total_machines=Mp,
        attr_dim=a,
        wave_seconds=[t.gather_s + t.solve_s for t in estats.traces],
        wave_bytes=[t.bytes_moved for t in estats.traces],
        total_bytes=estats.bytes_moved, wall_seconds=estats.wall_s)
    if cfg.capacity_bytes is not None:
        assert stats.peak_wave_bytes <= cfg.capacity_bytes, (
            stats.peak_wave_bytes, cfg.capacity_bytes)
    return (best_rows, best_mask, best_val, total_calls, round_depth,
            v_round, rows_in, mask_in, stats, estats)


def _attr_setup(data, constraint, attrs, streaming: bool):
    """Resolve the attribute plan: width ``a`` and a host ``(n, a)`` matrix
    (or None when attrs flow through the source's gather_attrs)."""
    if constraint is None:
        assert attrs is None, "attrs without a constraint have no consumer"
        return 0, None
    need = cons_lib.attr_dim(constraint)
    attrs_np = None if attrs is None else np.asarray(attrs, np.float32)
    if attrs_np is not None:
        assert attrs_np.ndim == 2, f"attrs must be (n, a), got {attrs_np.shape}"
        a = attrs_np.shape[1]
    elif streaming and isinstance(data, GroundSetSource):
        a = data.a
    else:
        a = 0
    assert a >= max(1, need), (
        f"constraint needs attrs with ≥ {max(1, need)} columns, got {a} "
        "(pass attrs= or an attributed source)")
    return a, attrs_np


def tree_maximize(
    obj,
    data: jax.Array | GroundSetSource,  # (n, d) ground set V, array or source
    cfg: TreeConfig,
    *,
    mesh=None,
    fail_machines: dict[int, list[int]] | None = None,  # round -> dead ids
    host_rounds: bool = False,
    wave_machines: int | None = None,   # streaming round-0 wave size W
    constraint=None,                    # hereditary constraint (constraints.*)
    attrs: np.ndarray | None = None,    # (n, a) per-item attribute rows
    wave_schedule: list[int] | None = None,  # test hook: forced per-wave
    #                                     widths (adversarial trajectories)
    fault_injector: FaultInjector | None = None,  # seeded chaos harness
    #                                     (implies supervision even without
    #                                     an explicit cfg.fault_policy)
) -> TreeResult:
    """Run Algorithm 1. With ``mesh``, machines shard over devices.

    ``data`` may be an all-resident ``(n, d)`` array (legacy path, kept as
    the equivalence reference) or any :class:`GroundSetSource`.  A source —
    or an explicit ``wave_machines`` — selects streaming round-0 ingestion:
    machine blocks are filled from the source and dispatched in waves of
    W machines, so no more than W·μ candidate rows are ever device-resident
    at once, with output bit-identical to the all-resident driver for the
    same seed.  Rounds t ≥ 1 operate on A_t (≤ m_t·k rows) and are already
    capacity-bounded.

    How those waves *execute* is the :mod:`repro.engine` subsystem's job:
    ``cfg.engine="pipelined"`` double-buffers so wave t+1's gather overlaps
    wave t's solve (bounded by ``cfg.max_in_flight`` host buffers),
    ``cfg.hosts > 1`` shards each gather across ingestion hosts, and
    ``cfg.capacity_bytes`` sizes W by a device-byte budget (weighted-μ:
    bytes include the attribute columns) instead of a machine count.
    ``cfg.wave_autotune`` hands the per-wave width to the rate-tuned
    autoscaler (:mod:`repro.engine.autotune`): widths move on a power-of-
    two bucket ladder, driven by EWMA gather/solve rates from the live
    wave traces, still hard-capped by the byte budget.
    ``cfg.async_checkpoint`` overlaps each round-boundary checkpoint write
    with the next round's repartition + solves (write barrier before the
    next snapshot and the final result — exact resume preserved;
    per-round overlap record on ``TreeResult.checkpoint_stats``).  All
    of these are execution knobs only — outputs are bit-identical to the
    synchronous single-host fixed-W engine, which stays the reference
    path, for every width trajectory.

    ``constraint`` applies a hereditary constraint from
    :mod:`repro.core.constraints` to every machine's solve (Theorem 3.5).
    Per-item attributes come from ``attrs`` (host ``(n, a)`` matrix) or an
    attributed source; they are appended as trailing candidate-matrix
    columns so rows and attributes move together through partitioning,
    waves, repartitioning, folding, and checkpoints.  The returned coreset
    carries ``sel_attrs`` and is verified feasible by the independent
    NumPy checker before returning.

    Default is the device-resident round loop; ``host_rounds=True`` selects
    the legacy NumPy-between-rounds driver (identical results, kept as the
    comparison baseline).
    """
    streaming = (isinstance(data, GroundSetSource)
                 or wave_machines is not None
                 or cfg.engine != "sync" or cfg.hosts > 1
                 or cfg.capacity_bytes is not None
                 or cfg.wave_autotune or wave_schedule is not None
                 or cfg.fault_policy is not None
                 or fault_injector is not None)
    if host_rounds:
        if streaming:
            raise ValueError("host_rounds=True supports only all-resident "
                             "arrays; pass the streaming source to the "
                             "default device driver")
        return _tree_maximize_host(obj, data, cfg, mesh=mesh,
                                   fail_machines=fail_machines,
                                   constraint=constraint, attrs=attrs)

    a, attrs_np = _attr_setup(data, constraint, attrs, streaming)
    source = as_source(data) if streaming else None
    n, d = (source.n, source.d) if streaming else data.shape
    if not streaming and a:
        # attributes ride as trailing columns of the resident candidate matrix
        data = jnp.concatenate(
            [jnp.asarray(data, jnp.float32), jnp.asarray(attrs_np)], axis=1)
    mu, k = cfg.capacity, cfg.k
    key = jax.random.PRNGKey(cfg.seed)
    fail_machines = fail_machines or {}

    # --- round 0 input: the full ground set, randomly partitioned ---------
    start_round = 0
    best_rows = jnp.zeros((k, d + a), jnp.float32)
    best_mask = jnp.zeros((k,), bool)
    best_val = jnp.float32(-jnp.inf)
    total_calls = jnp.int32(0)
    rows_in: jax.Array | None = None    # carry between rounds (device rows)
    mask_in: jax.Array | None = None
    n_items = n

    if cfg.resume and cfg.checkpoint_dir:
        resume_from = _resume_path(cfg.checkpoint_dir)
        if resume_from is not None:
            ck = load_round_checkpoint(resume_from)
            start_round = int(ck["round"])
            rows_in, mask_in = jnp.asarray(ck["rows"]), jnp.asarray(ck["mask"])
            best_rows, best_mask = jnp.asarray(ck["best_rows"]), jnp.asarray(ck["best_mask"])
            best_val = jnp.float32(float(ck["best_val"]))
            total_calls = jnp.int32(int(ck["calls"]))
    elif cfg.checkpoint_dir:
        clean_stale_tmp(cfg.checkpoint_dir)   # crashed-writer litter

    key = _fast_forward_key(key, start_round)
    machines_per_round: list[int] = []
    round_values: list[float] = []
    depth_per_round: list[int] = []
    r_bound = cfg.round_bound_exact(n)
    t = start_round
    ingest: IngestStats | None = None
    engine_stats: EngineStats | None = None
    # -- checkpoint policy: inline (timed) vs async double-buffered --------
    # the writer is handed the module-global _save_round lazily so the two
    # paths share one serializer (and tests may monkeypatch it for both)
    writer = (AsyncCheckpointWriter(lambda *wa: _save_round(*wa),
                                    tracer=cfg.telemetry)
              if cfg.async_checkpoint and cfg.checkpoint_dir else None)
    ckpt_rounds: list[RoundCheckpoint] = []
    tracer = cfg.telemetry
    round_walls: list[float] = []

    with span("run.run", tracer=tracer) as run_span:
        try:
            while True:
                with span("round.round", tracer=tracer, round=t) as rs:
                    key, kpart, kalg = jax.random.split(key, 3)
                    if t != 0:
                        with span("round.sync", tracer=tracer, round=t):
                            n_items = int(_host_scalar(
                                jnp.sum(mask_in.astype(jnp.int32))))
                    L = part_lib.n_parts(n_items, mu)

                    if t == 0 and streaming:
                        # ---- wave-scheduled ingestion: ≤ W·μ rows on device
                        machines_per_round.append(L)
                        (best_rows, best_mask, best_val, total_calls,
                         round_depth, v_best, rows_in, mask_in, ingest,
                         engine_stats) = _stream_round0(
                            obj, source, kpart, kalg, L, cfg, mesh,
                            fail_machines, wave_machines, best_rows,
                            best_mask, best_val, total_calls,
                            constraint=constraint, attrs_np=attrs_np,
                            wave_schedule=wave_schedule,
                            fault_injector=fault_injector)
                        round_values.append(_host_scalar(v_best))
                        depth_per_round.append(
                            int(_host_scalar(round_depth)))
                    else:
                        # ---- partition A_t into L balanced parts
                        if t == 0:
                            part = _round0_partition(kpart, n, L, mu,
                                                     cfg.permutation)
                            blocks, bmask = part_lib.gather_partition(data,
                                                                      part)
                        else:
                            with span("round.repartition", tracer=tracer,
                                      round=t):
                                blocks, bmask = part_lib.repartition_rows(
                                    rows_in, mask_in, kpart, L, mu)

                        machines_per_round.append(blocks.shape[0])
                        with span("round.dispatch", tracer=tracer, round=t):
                            res = _dispatch_round(obj, blocks, bmask, kalg, t,
                                                  cfg, mesh, fail_machines,
                                                  attr_dim=a,
                                                  constraint=constraint)

                        (best_rows, best_mask, best_val, total_calls,
                         round_depth, v_best) = _fold_round(
                            res.sol_rows, res.sol_mask, res.values,
                            res.oracle_calls, res.depth, best_rows, best_mask,
                            best_val, total_calls, jnp.int32(0))
                        with span("round.sync", tracer=tracer, round=t):
                            round_values.append(_host_scalar(v_best))
                            depth_per_round.append(
                                int(_host_scalar(round_depth)))

                        # ---- union of partial solutions = next A (device)
                        rows_in = res.sol_rows.reshape(-1, d + a)
                        mask_in = res.sol_mask.reshape(-1)
                    t += 1

                    if cfg.checkpoint_dir:
                        # snapshot on the caller thread (device→host pulls
                        # produce fresh buffers the writer owns outright) ...
                        with span("ckpt.snapshot", tracer=tracer, round=t):
                            snap = (cfg.checkpoint_dir, t,
                                    _host_array(rows_in),
                                    _host_array(mask_in),
                                    _host_array(best_rows),
                                    _host_array(best_mask),
                                    _host_scalar(best_val),
                                    int(_host_scalar(total_calls)),
                                    cfg.checkpoint_keep,
                                    cfg.checkpoint_delta_every)
                        if writer is not None:
                            # ... then overlap the serialize+write with round
                            # t+1 (submit's barrier drained write t-1 already)
                            writer.submit(t, *snap)
                        else:
                            with span("ckpt.write", tracer=tracer,
                                      round=t) as cw:
                                _save_round(*snap)
                            dt = cw.t1 - cw.t0
                            ckpt_rounds.append(RoundCheckpoint(
                                round=t, write_s=dt, wait_s=dt))
                    # depth rides on the round span: τ-levels run inside the
                    # fused launch (device while_loop), so per-level spans
                    # are reported as the measured ladder length, not host
                    # timings
                    rs.args.update(machines=machines_per_round[-1],
                                   depth=depth_per_round[-1])
                round_walls.append(rs.t1 - rs.t0)

                if L == 1:        # that was the final single-machine round
                    break
                assert t <= r_bound + 1, (
                    f"round bound violated: {t} > {r_bound} (Prop 3.1)")
        except BaseException:
            if writer is not None:
                writer.abort()  # drain in-flight write; keep the root cause
            raise
        ckpt_stats: CheckpointStats | None = None
        if writer is not None:
            writer.wait()       # final write barrier: resume-complete on disk
            ckpt_stats = writer.stats()
        elif cfg.checkpoint_dir:
            ckpt_stats = CheckpointStats(mode="sync", rounds=ckpt_rounds)

        sel_wide = _host_array(best_rows)
        sel_mask_np = _host_array(best_mask)
        value = _host_scalar(best_val)
        run_span.args.update(rounds=t, value=value)
    result = _finish_result(
        sel_wide, sel_mask_np, d, a, constraint,
        value=value, rounds=t,
        oracle_calls=int(_host_scalar(total_calls)),
        machines_per_round=machines_per_round, round_values=round_values,
        ingest=ingest, engine_stats=engine_stats,
        checkpoint_stats=ckpt_stats,
        fault_stats=engine_stats.fault_stats if engine_stats else None,
        round_walls=round_walls, total_wall_s=run_span.t1 - run_span.t0,
        depth_per_round=depth_per_round,
        solve_depth=sum(depth_per_round))
    if tracer is not None:
        result.manifest = _build_run_manifest(cfg, result, n, d, source,
                                              streaming, tracer)
    return result


def _build_run_manifest(cfg: TreeConfig, result: TreeResult, n: int, d: int,
                        source, streaming: bool, tracer):
    """Assemble the run's :class:`repro.engine.telemetry.RunManifest`,
    project the stats dataclasses onto the tracer's metrics registry, and
    write the manifest atomically next to the checkpoints (when a
    checkpoint directory exists).  The CLI extends the same record with
    its feasibility / fp32-recheck sections and re-writes it."""
    if streaming:
        feat_dtype = np.dtype(source.dtype)
        narrow = feat_dtype != np.dtype(np.float32)
        itemsize = dtype_itemsize(feat_dtype) if narrow else 4
        qcols = source.qcols if narrow else 0
        label, fingerprint = dtype_label(feat_dtype), source.fingerprint()
    else:
        itemsize, qcols, label, fingerprint = 4, 0, "fp32", None
    manifest = build_manifest(cfg, result, n=n, d=d, dtype_label=label,
                              itemsize=itemsize, qcols=qcols,
                              source_fingerprint=fingerprint)
    feed_result_metrics(tracer.metrics, result)
    if cfg.checkpoint_dir:
        manifest.write(os.path.join(cfg.checkpoint_dir, MANIFEST_NAME))
    return manifest


def _finish_result(sel_wide: np.ndarray, sel_mask: np.ndarray, d: int,
                   a: int, constraint, **kw) -> TreeResult:
    """Split the carried wide rows back into (features, attrs) and verify
    the coreset against the independent NumPy feasibility checker."""
    sel_rows = sel_wide[:, :d] if a else sel_wide
    sel_attrs = sel_wide[:, d:] if a else None
    if constraint is not None:
        ok, detail = cons_lib.check_feasible(
            constraint, sel_attrs if a else np.zeros((len(sel_mask), 0)),
            sel_mask)
        assert ok, f"returned coreset violates the constraint: {detail}"
    return TreeResult(sel_rows=sel_rows, sel_mask=sel_mask,
                      sel_attrs=sel_attrs, **kw)


# ---------------------------------------------------------------------------
# legacy host-NumPy round loop — bit-identical reference for the device path
# ---------------------------------------------------------------------------


def _tree_maximize_host(
    obj,
    data: jax.Array,
    cfg: TreeConfig,
    *,
    mesh=None,
    fail_machines: dict[int, list[int]] | None = None,
    constraint=None,
    attrs: np.ndarray | None = None,
) -> TreeResult:
    n, d = data.shape
    a, attrs_np = _attr_setup(data, constraint, attrs, streaming=False)
    if a:
        data = jnp.concatenate(
            [jnp.asarray(data, jnp.float32), jnp.asarray(attrs_np)], axis=1)
    mu, k = cfg.capacity, cfg.k
    key = jax.random.PRNGKey(cfg.seed)
    fail_machines = fail_machines or {}

    start_round = 0
    best_rows = np.zeros((k, d + a), np.float32)
    best_mask = np.zeros((k,), bool)
    best_val = -np.inf
    total_calls = 0
    rows_in: np.ndarray | None = None   # carry between rounds (item rows)
    mask_in: np.ndarray | None = None

    if cfg.resume and cfg.checkpoint_dir:
        resume_from = _resume_path(cfg.checkpoint_dir)
        if resume_from is not None:
            ck = load_round_checkpoint(resume_from)
            start_round = int(ck["round"])
            rows_in, mask_in = ck["rows"], ck["mask"]
            best_rows, best_mask = ck["best_rows"], ck["best_mask"]
            best_val = float(ck["best_val"])
            total_calls = int(ck["calls"])
    elif cfg.checkpoint_dir:
        clean_stale_tmp(cfg.checkpoint_dir)   # crashed-writer litter

    key = _fast_forward_key(key, start_round)
    machines_per_round: list[int] = []
    round_values: list[float] = []
    depth_per_round: list[int] = []
    r_bound = cfg.round_bound_exact(n)
    t = start_round

    while True:
        key, kpart, kalg = jax.random.split(key, 3)
        if t == 0:
            n_items = n
        else:
            n_items = int(mask_in.sum())
        L = part_lib.n_parts(n_items, mu)

        # ---- partition A_t into L balanced parts (virtual-location) ------
        if t == 0:
            part = _round0_partition(kpart, n, L, mu, cfg.permutation)
            blocks, bmask = part_lib.gather_partition(data, part)
        else:
            valid = np.flatnonzero(mask_in)
            items = jnp.asarray(rows_in[valid])
            blocks, bmask = part_lib.scatter_rows(
                items, jnp.ones((len(valid),), bool), kpart, L, mu)

        machines_per_round.append(blocks.shape[0])
        res = _dispatch_round(obj, blocks, bmask, kalg, t, cfg, mesh,
                              fail_machines, attr_dim=a,
                              constraint=constraint)

        vals = np.asarray(res.values)
        calls = int(np.asarray(res.oracle_calls).sum())
        total_calls += calls
        depth_per_round.append(int(np.asarray(res.depth).max()))
        i_best = int(np.argmax(vals))
        round_values.append(float(vals[i_best]))
        if vals[i_best] > best_val:
            best_val = float(vals[i_best])
            best_rows = np.asarray(res.sol_rows[i_best])
            best_mask = np.asarray(res.sol_mask[i_best])

        # ---- union of partial solutions = next A ------------------------
        rows_in = np.asarray(res.sol_rows).reshape(-1, d + a)
        mask_in = np.asarray(res.sol_mask).reshape(-1)
        t += 1

        if cfg.checkpoint_dir:
            _save_round(cfg.checkpoint_dir, t, rows_in, mask_in, best_rows,
                        best_mask, best_val, total_calls,
                        cfg.checkpoint_keep, cfg.checkpoint_delta_every)

        if L == 1:        # that was the final single-machine round
            break
        assert t <= r_bound + 1, (
            f"round bound violated: {t} > {r_bound} (Prop 3.1)")

    return _finish_result(
        best_rows, best_mask, d, a, constraint,
        value=best_val, rounds=t, oracle_calls=total_calls,
        machines_per_round=machines_per_round, round_values=round_values,
        depth_per_round=depth_per_round,
        solve_depth=sum(depth_per_round))
