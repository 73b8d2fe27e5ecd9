"""Production driver for distributed submodular maximization.

    PYTHONPATH=src python -m repro.launch.submod \
        --dataset csn-20k --k 50 --capacity 400 \
        [--algorithm greedy|stochastic_greedy|threshold_greedy|threshold-batch] \
        [--batch-eps E] \
        [--source resident|chunked|sharded] [--wave-machines W] \
        [--engine sync|pipelined] [--hosts P] [--capacity-bytes B] \
        [--wave-autotune] [--async-checkpoint] [--prefetch-depth D] \
        [--constraint knapsack:budget=2.5 | partition:caps=4,4,4 | ...] \
        [--permutation dense|feistel] \
        [--dtype fp32|bf16|int8] [--q-block-rows B] \
        [--autotune-cache [PATH]] [--ckpt-delta-every K] \
        [--ckpt-dir DIR --resume] [--fail round:ids] \
        [--fault-profile 'transient=0.3,seed=7,...'] [--fault-retries N] \
        [--fault-backoff S] [--no-hedge] [--max-dropped-fraction F] \
        [--trace-out trace.json] [--metrics-out metrics.json] \
        [--manifest-out manifest.json] [--profile-dir PROFDIR] \
        [--serve-smoke [--serve-requests N]]

Runs TREE-BASED COMPRESSION over all visible devices (machines sharded via
shard_map), reports value vs centralized greedy + rounds + oracle calls.

``--source chunked|sharded`` (or an explicit ``--wave-machines``) selects
streaming round-0 ingestion: the ground set is read through a
GroundSetSource and dispatched in capacity-bounded waves, so the device
footprint is O(W·μ·(d+a)) instead of O(n·(d+a)) — output bit-identical to
the resident path for the same seed.  ``--permutation feistel`` swaps the
O(n) host slot permutation for the O(1)-state counter-based cipher.

``--engine pipelined`` runs the waves through the asynchronous execution
engine (``repro.engine``): wave t+1's gather overlaps wave t's solve under
a 2-buffer backpressure bound, ``--hosts P`` shards every gather across P
ingestion hosts (emulated in one process, locality asserted), and
``--capacity-bytes B`` sizes W from a device-byte budget (weighted-μ
capacity: bytes include attribute columns) instead of a machine count.
All of it is bit-identical to ``--engine sync``; the reported engine line
gives per-run gather/solve seconds and the measured overlap ratio.  With a
non-resident source the centralized comparison column also streams (the
chunked lazy-greedy pass — no all-resident array anywhere in the run).

``--wave-autotune`` turns the static W into a measurement-driven policy:
the rate-tuned autoscaler (``repro.engine.autotune``) retunes the wave
width per wave from EWMA gather/solve rates, quantized to a power-of-two
bucket ladder (re-jits stay log2-bounded, asserted) and still hard-capped
by ``--capacity-bytes``.  ``--async-checkpoint`` (with ``--ckpt-dir``)
hands each round-boundary checkpoint write to a background thread so it
overlaps the next round's work — exact resume semantics preserved by a
write barrier before every snapshot and the final result.  Both are pure
execution policy: output stays bit-identical to the fixed-W synchronous
run.  ``--prefetch-depth`` pins the chunk-prefetch depth of the streamed
centralized column; unset, it defaults from the autotuner's measured
gather/solve rates when those exist.

``--dtype bf16|int8`` runs bytes-lean ingestion: the ground set is wrapped
in a :class:`QuantizedSource`, every wave ships narrow feature rows to
device (attrs + per-block dequant params ride out-of-band as fp32
metadata), and the Pallas megakernel dequantizes in-kernel so gain math
stays fp32.  The same ``--capacity-bytes`` budget then admits
proportionally wider waves (grep the ``bytes:`` line).  The reported
coreset is re-gathered from the unquantized parent at fp32 and exactly
re-scored (``recheck:`` line, PASS/FAIL) — quality claims never rest on
narrow arithmetic.  ``--autotune-cache`` persists the wave autoscaler's
converged rung per (source fingerprint, μ, devices) so reruns start at
the knee; ``--ckpt-delta-every K`` shrinks round checkpoints to row-index
deltas with a full snapshot every K rounds (resume bit-identical).

``--fault-profile`` arms the seeded chaos injector
(``repro.engine.faults.FaultInjector``) on the wave-gather path — e.g.
``transient=0.3,seed=7`` fails ~30% of gather attempts with a retryable IO
error, ``dead_host=1,dead_host_wave=2`` kills ingestion host 1 permanently
from wave 2 on (losslessly evicted: the planner re-routes its shard to
survivors), ``kill=3`` makes wave 3 fail past any retry budget (dropped and
folded as dead machines under the Lemma 3.4 degradation bound),
``slow=2,latency=0.5`` injects straggler latency that the hedged re-gather
races.  ``--fault-retries`` / ``--fault-backoff`` / ``--no-hedge`` /
``--max-dropped-fraction`` tune the :class:`FaultPolicy`; a ``faults:``
report line gives grep-able recovery counters (retries, hedges, evictions,
dropped rows vs the budget).  Transient-only and evicted runs stay
bit-identical to the fault-free run; only *dropped* waves change output.

``--algorithm threshold-batch`` selects the low-adaptivity solve tier:
each per-machine solve runs the threshold-batch megakernel, which scores
the whole candidate block against a threshold τ per launch and
batch-accepts every qualifying prefix-feasible item, lowering τ
geometrically (τ ← τ(1−ε)) between launches.  Sequential solve depth per
machine drops from k kernel launches to O(log(2k/ε)/ε) — the quality
floor is f(S) ≥ (1−ε)·f(greedy) on the same block.  ``--batch-eps`` sets
the ladder decay ε (overrides ``--eps`` for this tier; default 0.5).
The report gains a grep-able ``adaptivity:`` line with the measured
per-round launch depth, the equivalent greedy depth (k·rounds), and the
reduction factor.

``--constraint`` applies a hereditary constraint to every machine's solve
(grammar: ``knapsack:budget=F[:col=I]``, ``partition:caps=I,I,..[:col=I]``,
``intersection:<spec>+<spec>``).  Per-item attributes are synthesized
deterministically from ``--seed`` (uniform weights in [0.2, 1.0) for
knapsack columns, uniform group ids for partition columns), travel with the
rows through the whole pipeline, and both comparison columns — centralized
greedy and two-round RandGreedI — run under the *same* constraint so the
quality ratios stay honest.  Every reported coreset is re-verified by the
independent NumPy feasibility checker.

``--trace-out`` / ``--metrics-out`` / ``--manifest-out`` attach the
unified telemetry layer (:mod:`repro.engine.telemetry`): a span tracer
over every engine seam exported as Perfetto-loadable Chrome trace JSON,
the labelled metrics registry snapshot, and the atomically written
``RunManifest`` (config + source fingerprints, dtype, width trajectory,
fault replay signature, per-phase walls).  All report lines above are
formatted *from* the manifest, so console and manifest can never
disagree; inspect traces with ``python -m repro.launch.tracetool``.
Telemetry is observation only — outputs stay bit-identical to an
uninstrumented run.  ``--profile-dir`` additionally brackets the run
with ``jax.profiler`` start/stop.

``--serve-smoke`` swaps the one-shot solve for the selection service
(:mod:`repro.serve`): the dataset is ingested once into a resident
session, a mixed request stream (two cardinalities × unconstrained /
knapsack / partition / query-reweighted) is answered twice as identical
fused batches — the warm pass is asserted retrace-free and bit-identical
to the cold pass — plus a burst through the micro-batching dispatcher,
then a ~1% ground-set delta triggers a block-local re-solve.  Reports
the ``serve:`` counter lines, a NumPy
``recheck:`` of a served coreset, and a validated manifest; CI greps all
three.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (STORAGE_DTYPES, ArraySource, ChunkedSource,
                        ExemplarClustering, Intersection, Knapsack,
                        PartitionMatroid, QuantizedSource, TreeConfig,
                        centralized_greedy, check_feasible,
                        constraint_from_spec, dtype_itemsize,
                        make_submod_mesh, randgreedi, tree_maximize)
from repro.core.sources import GroundSetSource
from repro.core.tree import PERMUTATIONS
from repro.data.selection import fp32_recheck
from repro.engine import (ENGINES, FaultInjector, FaultPolicy, FaultProfile,
                          Tracer, build_manifest, format_report,
                          profiler_session, suggest_prefetch_depth)
from repro.data import datasets
from repro.data.sources import ShardedSource
from repro.launch.compile_cache import enable_compile_cache


def synth_attrs(constraint, n: int, seed: int) -> np.ndarray | None:
    """Deterministic per-item attributes matching the constraint's columns.

    Knapsack columns get uniform weights in [0.2, 1.0); partition columns
    get uniform group ids in [0, len(caps)) — reproducible from ``--seed``
    alone.  (The constrained benchmark generates its *own* shard-keyed
    attribute streams so shards stay independently loadable; CLI runs and
    ``BENCH_PR3.json`` sweeps are therefore not attribute-comparable.)
    """
    if constraint is None:
        return None

    def walk(c, cols: dict):
        if isinstance(c, Intersection):
            for p in c.parts:
                walk(p, cols)
        elif isinstance(c, (Knapsack, PartitionMatroid)):
            kind = "w" if isinstance(c, Knapsack) else len(c.caps)
            prev = cols.setdefault(c.col, kind)
            assert prev == kind, f"column {c.col} reused with a different role"
        return cols

    cols = walk(constraint, {})
    a = max(cols) + 1
    r = np.random.default_rng((seed, 0xA7725))
    attrs = np.zeros((n, a), np.float32)
    for col in range(a):
        kind = cols.get(col, "w")
        if kind == "w":
            attrs[:, col] = r.uniform(0.2, 1.0, n).astype(np.float32)
        else:
            attrs[:, col] = r.integers(0, kind, n).astype(np.float32)
    return attrs


def _np_exemplar_value(E, rows, mask) -> float:
    """Independent NumPy re-score of a served coreset under the exemplar
    objective — the serve smoke's recheck column (fp64 accumulate)."""
    E = np.asarray(E, np.float64)
    S = np.asarray(rows, np.float64)[np.asarray(mask, bool)]
    e0 = np.sum(E * E, axis=1)
    if len(S) == 0:
        return 0.0
    d2 = (e0[:, None] - 2.0 * E @ S.T
          + np.sum(S * S, axis=1)[None, :])
    cur = np.minimum(e0, d2.min(axis=1))
    return float(np.mean(e0) - np.mean(cur))


@dataclasses.dataclass
class ServeExercise:
    """What :func:`serve_exercise` did, for its callers to report."""
    service: Any
    session: Any
    cold: list
    after: Any                  # warm re-query after the delta
    delta: Any                  # DeltaReport
    ingest_s: float
    cold_s: float
    warm_s: float
    burst_s: float
    delta_s: float
    recheck: dict               # NumPy fp64 re-score of ``after``


def serve_requests(data: np.ndarray, k: int, n_requests: int,
                   budget: float, n_groups: int = 3) -> list:
    """The smoke's request mix: two cardinalities × {unconstrained,
    knapsack on attribute column 0, partition on column 1, query-weighted},
    cycled over ``n_requests`` slots."""
    from repro.serve import SelectionRequest
    k2 = max(2, k // 2)
    cap = max(1, k // n_groups + 1)
    caps = ",".join([str(cap)] * n_groups)
    reqs = []
    for i in range(n_requests):
        k_i = k if i % 2 == 0 else k2
        kind = i % 4
        if kind == 0:
            reqs.append(SelectionRequest(k=k_i))
        elif kind == 1:
            reqs.append(SelectionRequest(
                k=k_i, constraint=f"knapsack:budget={budget:.4f}"))
        elif kind == 2:
            reqs.append(SelectionRequest(
                k=k_i, constraint=f"partition:caps={caps}:col=1"))
        else:
            reqs.append(SelectionRequest(k=k_i,
                                         query=data[(7 * i) % len(data)]))
    return reqs


def serve_exercise(data: np.ndarray, E: np.ndarray, cfg: TreeConfig, *,
                   n_requests: int, rng: np.random.Generator, tracer=None,
                   log=lambda _msg: None) -> ServeExercise:
    """Drive the selection service once, asserting its contract.

    Ingest ``data`` (with a knapsack-weight and a 3-group attribute column)
    into a resident session through the wave engine; answer the
    :func:`serve_requests` mix twice as identical synchronous batches —
    the warm pass must ride the compile cache with zero retraces and
    answer bit-identically; send the mix again through the threaded
    dispatcher; apply a ~1% ground-set delta and re-query.  Every answer must be
    feasible.  The re-query is re-scored in NumPy fp64 (``recheck``).
    ``log`` receives one line as each stage ends.
    """
    from repro.serve import Dispatcher, SelectionService, ingest, serve_batch

    n, d = data.shape
    attrs = np.zeros((n, 2), np.float32)
    attrs[:, 0] = rng.uniform(0.2, 1.0, n).astype(np.float32)
    attrs[:, 1] = rng.integers(0, 3, n).astype(np.float32)

    t0 = time.perf_counter()
    st = ingest(ArraySource(data), cfg, attrs=attrs)
    t_ingest = time.perf_counter() - t0
    log(f"ingested machines={st.Mp} ingest_s={t_ingest:.3f}")
    svc = SelectionService(st, E, algorithm=cfg.algorithm, eps=cfg.eps,
                           tracer=tracer)
    budget = float(np.quantile(attrs[:, 0], 0.6)) * min(cfg.k, 8)
    reqs = serve_requests(data, cfg.k, n_requests, budget)

    t1 = time.perf_counter()
    cold = serve_batch(svc, reqs)
    t2 = time.perf_counter()
    compiles_after_cold = svc.cache.compiles
    log(f"cold requests={len(reqs)} cold_s={t2 - t1:.3f} "
        f"entries={compiles_after_cold}")
    warm = serve_batch(svc, reqs)
    t3 = time.perf_counter()
    for c, w in zip(cold, warm):
        assert c.value == w.value and np.array_equal(c.rows, w.rows), \
            "warm-cache answers diverged from cold answers"
    assert svc.cache.compiles == compiles_after_cold, \
        "steady-state request retraced a warm compile-cache entry"
    assert svc.cache.steady_retraces() == 0
    for res in cold:
        assert res.feasible, res.detail

    # threaded burst: opportunistic micro-batching under backpressure —
    # exercises the dispatcher and records true queue depth (compositions
    # are timing-dependent, so assert feasibility, not bit equality).  Any
    # four consecutive requests of the mix have distinct fuse keys, so with
    # at most four per batch every batch compiles the same single-request
    # entries whatever its timing.
    dp = Dispatcher(svc, max_batch=4)
    try:
        for res in dp.map(reqs):
            assert res.feasible, res.detail
    finally:
        dp.close()
    assert svc.queue_depth_max >= 1
    t4 = time.perf_counter()
    log(f"warm_s={t3 - t2:.3f} burst_s={t4 - t3:.3f} "
        f"entries={svc.cache.compiles}")

    # ~1% churn delta: block-local re-solve, then a warm re-query
    n_del = max(1, n // 100)
    del_ids = [int(x) for x in rng.choice(n, n_del, replace=False)]
    ins_rows = data[rng.choice(n, n_del, replace=False)] * np.float32(0.5)
    ins_attrs = np.zeros((n_del, 2), np.float32)
    ins_attrs[:, 0] = rng.uniform(0.2, 1.0, n_del).astype(np.float32)
    ins_attrs[:, 1] = rng.integers(0, 3, n_del).astype(np.float32)
    rep = svc.apply_delta(insert_rows=ins_rows, insert_attrs=ins_attrs,
                          delete_ids=del_ids)
    after = svc.query(reqs[0])
    t5 = time.perf_counter()
    assert after.feasible, after.detail

    npv = _np_exemplar_value(E, after.rows, after.mask)
    rel = abs(npv - after.value) / max(abs(npv), 1e-12)
    status = "PASS" if np.isfinite(after.value) and rel < 1e-3 else "FAIL"
    return ServeExercise(
        service=svc, session=st, cold=cold, after=after, delta=rep,
        ingest_s=t_ingest, cold_s=t2 - t1, warm_s=t3 - t2, burst_s=t4 - t3,
        delta_s=t5 - t4,
        recheck={"fp32": npv, "solve": float(after.value),
                 "rel_gap": float(rel), "status": status})


def serve_smoke(args) -> None:
    """CI-grepable exercise of the selection service without a daemon:
    :func:`serve_exercise` on a registry dataset, then the ``serve:`` /
    ``recheck:`` report lines and a validated manifest."""
    from repro.engine.telemetry import (RunManifest, config_dict,
                                        config_fingerprint)
    from repro.serve import round_ladder

    data = np.asarray(datasets.REGISTRY[args.dataset](), np.float32)
    n, d = data.shape
    r = np.random.default_rng(args.seed)
    E = data[r.choice(n, min(args.n_eval, n), replace=False)]

    tracer = (Tracer() if (args.trace_out or args.metrics_out
                           or args.manifest_out) else None)
    cfg = TreeConfig(k=args.k, capacity=args.capacity,
                     algorithm=args.algorithm, eps=args.eps, seed=args.seed,
                     permutation=args.permutation, engine=args.engine,
                     hosts=args.hosts, telemetry=tracer)
    print(f"serve-smoke: n={n} d={d} k={args.k} mu={args.capacity} "
          f"requests={args.serve_requests} engine={args.engine}")
    ex = serve_exercise(data, E, cfg, n_requests=args.serve_requests, rng=r,
                        tracer=tracer)
    svc, st, after, rep = ex.service, ex.session, ex.after, ex.delta

    ladder = round_ladder(st.Mp, args.k, st.mu)
    run = {"n": n, "d": d, "k": args.k, "mu": args.capacity,
           "algorithm": args.algorithm, "seed": args.seed,
           "value": float(after.value), "rounds": len(ladder),
           "oracle_calls": int(after.oracle_calls),
           "machines_per_round": list(ladder),
           "round_values": [], "dataset": args.dataset}
    manifest = RunManifest(config=config_dict(cfg),
                           config_fingerprint=config_fingerprint(cfg),
                           run=run, dtype="fp32")
    manifest.phases = {"ingest_s": ex.ingest_s,
                       "serve_s": ex.cold_s + ex.warm_s}
    manifest.serve = svc.serve_stats()
    manifest.recheck = ex.recheck
    for line in format_report(manifest):
        print(line)
    print(f"delta: inserted={rep.inserted} deleted={rep.deleted} "
          f"changed_machines={len(rep.changed_machines)}/{st.Mp} "
          f"rebuilt={rep.rebuilt}")

    if tracer is not None:
        if args.trace_out:
            tracer.export_chrome_trace(args.trace_out)
        if args.metrics_out:
            tracer.metrics.export_json(args.metrics_out)
    if args.manifest_out:
        manifest.write(args.manifest_out)
    problems = manifest.validate()
    assert ex.recheck["status"] == "PASS", ex.recheck
    print("manifest: OK" if not problems
          else f"manifest: INVALID {problems}")
    assert not problems, problems


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="csn-20k",
                    choices=sorted(datasets.REGISTRY))
    ap.add_argument("--k", type=int, default=50)
    ap.add_argument("--capacity", type=int, default=400)
    ap.add_argument("--algorithm", default="greedy",
                    help="per-machine selection tier: greedy, "
                         "stochastic_greedy, threshold_greedy, or "
                         "threshold-batch (low-adaptivity τ-ladder)")
    ap.add_argument("--eps", type=float, default=0.5)
    ap.add_argument("--batch-eps", type=float, default=None,
                    help="τ-ladder decay ε for --algorithm threshold-batch "
                         "(overrides --eps for that tier; smaller ε = "
                         "tighter quality floor, deeper ladder)")
    ap.add_argument("--n-eval", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--source", default="resident",
                    choices=("resident", "chunked", "sharded"),
                    help="ground-set access path; non-resident streams "
                         "round 0 in capacity-bounded waves")
    ap.add_argument("--wave-machines", type=int, default=None,
                    help="streaming wave size W (default: one mesh sweep)")
    ap.add_argument("--engine", default="sync", choices=ENGINES,
                    help="wave execution engine; pipelined overlaps wave "
                         "t+1's gather with wave t's solve (bit-identical)")
    ap.add_argument("--hosts", type=int, default=1,
                    help="ingestion hosts sharding the round-0 gather "
                         "(emulated in-process; locality asserted)")
    ap.add_argument("--capacity-bytes", type=int, default=None,
                    help="device-byte wave budget; derives W from bytes "
                         "including attribute columns (weighted-μ capacity)")
    ap.add_argument("--wave-autotune", action="store_true",
                    help="rate-tuned wave autoscaler: retune W per wave "
                         "from measured gather/solve rates (bucket ladder, "
                         "log2-bounded re-jits, bit-identical output)")
    ap.add_argument("--async-checkpoint", action="store_true",
                    help="background round-boundary checkpoint writes "
                         "overlapping the next round (needs --ckpt-dir; "
                         "exact resume preserved)")
    ap.add_argument("--prefetch-depth", type=int, default=None,
                    help="chunk-prefetch depth for streamed source passes "
                         "(default: 2, or autotuner-suggested when "
                         "--wave-autotune measured the rates)")
    ap.add_argument("--chunk-rows", type=int, default=4096,
                    help="rows per chunk/shard for --source chunked|sharded")
    ap.add_argument("--dtype", default="fp32", choices=STORAGE_DTYPES,
                    help="ground-set storage dtype: bf16/int8 ship narrow "
                         "rows to device (dequantized in-kernel, same byte "
                         "budget admits wider waves); the reported coreset "
                         "is re-gathered at fp32 and exactly re-scored")
    ap.add_argument("--q-block-rows", type=int, default=4096,
                    help="int8 quantization block size (rows per "
                         "scale/zero-point block on the global index grid)")
    ap.add_argument("--autotune-cache", nargs="?", const="auto", default=None,
                    help="persist the autoscaler's converged rung to this "
                         "JSON file (bare flag: autotune_cache.json next to "
                         "--ckpt-dir); reruns seed the planner at the knee")
    ap.add_argument("--ckpt-delta-every", type=int, default=0,
                    help="K > 0: round checkpoints store row-index deltas "
                         "vs the previous round, full snapshot every K "
                         "rounds (resume bit-identical)")
    ap.add_argument("--constraint", default=None,
                    help="hereditary constraint spec, e.g. "
                         "'knapsack:budget=2.5' or 'partition:caps=4,4,4'")
    ap.add_argument("--permutation", default="dense", choices=PERMUTATIONS,
                    help="round-0 slot scheme: dense host permutation or "
                         "O(1)-state Feistel cipher")
    ap.add_argument("--baseline-machines", type=int, default=None,
                    help="RandGreedI machine count (default: ⌈n/μ⌉)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail", default=None,
                    help="inject failures, e.g. '0:0,1,2' (round 0, ids)")
    ap.add_argument("--fault-profile", default=None,
                    help="seeded chaos spec for the wave-gather path, e.g. "
                         "'transient=0.3,seed=7,dead_host=1,kill=3,"
                         "slow=2,latency=0.5' (see FaultProfile.from_spec)")
    ap.add_argument("--fault-retries", type=int, default=None,
                    help="transient gather retry budget per wave "
                         "(default: FaultPolicy.max_retries)")
    ap.add_argument("--fault-backoff", type=float, default=None,
                    help="base retry backoff seconds (doubles per attempt)")
    ap.add_argument("--no-hedge", action="store_true",
                    help="disable hedged re-gathers of straggler waves")
    ap.add_argument("--max-dropped-fraction", type=float, default=None,
                    help="Lemma 3.4 degradation budget: abort once the "
                         "dropped row fraction exceeds this")
    ap.add_argument("--trace-out", default=None,
                    help="export the run's span stream as Chrome "
                         "trace_event JSON (loads in Perfetto / "
                         "chrome://tracing; one lane per thread and per "
                         "ingestion host)")
    ap.add_argument("--metrics-out", default=None,
                    help="export the labelled metrics registry snapshot "
                         "(counters/gauges/histograms) as JSON")
    ap.add_argument("--manifest-out", default=None,
                    help="write the RunManifest JSON here (with --ckpt-dir "
                         "and telemetry on it is also written next to the "
                         "checkpoints automatically)")
    ap.add_argument("--profile-dir", default=None,
                    help="bracket the run with jax.profiler start/stop and "
                         "dump the device profile into this directory")
    ap.add_argument("--no-centralized", action="store_true")
    ap.add_argument("--serve-smoke", action="store_true",
                    help="exercise the selection service instead of one "
                         "offline solve: ingest once, answer a mixed "
                         "k/constraint/query request stream twice (warm "
                         "compile cache asserted retrace-free), apply a "
                         "~1%% ground-set delta, print grep-able serve:/"
                         "recheck:/manifest lines")
    ap.add_argument("--serve-requests", type=int, default=12,
                    help="request-stream length for --serve-smoke")
    args = ap.parse_args()
    # CLI spells the tier with a hyphen; internal names use underscores
    args.algorithm = args.algorithm.replace("-", "_")
    if args.algorithm == "threshold_batch" and args.batch_eps is not None:
        args.eps = args.batch_eps

    if args.serve_smoke:
        serve_smoke(args)
        return

    data = datasets.REGISTRY[args.dataset]()
    r = np.random.default_rng(args.seed)
    E = data[r.choice(len(data), min(args.n_eval, len(data)), replace=False)]
    obj = ExemplarClustering(jnp.asarray(E))
    dj = jnp.asarray(data)

    constraint = constraint_from_spec(args.constraint) if args.constraint else None
    attrs = synth_attrs(constraint, len(data), args.seed)

    fail = None
    if args.fail:
        rd, ids = args.fail.split(":")
        fail = {int(rd): [int(i) for i in ids.split(",")]}

    injector = None
    if args.fault_profile:
        injector = FaultInjector(FaultProfile.from_spec(args.fault_profile))
    fault_policy = None
    overrides = {
        k: v for k, v in (("max_retries", args.fault_retries),
                          ("backoff_s", args.fault_backoff),
                          ("hedge", False if args.no_hedge else None),
                          ("max_dropped_fraction", args.max_dropped_fraction))
        if v is not None}
    if overrides or injector is not None:
        fault_policy = FaultPolicy(**overrides)

    if args.source == "chunked":
        ground = ChunkedSource.from_array(data, args.chunk_rows, attrs=attrs)
        attrs_arg = None          # attrs flow through the source's gathers
    elif args.source == "sharded":
        cr = args.chunk_rows
        shards = [data[s:s + cr] for s in range(0, len(data), cr)]
        ashards = (None if attrs is None else
                   [attrs[s:s + cr] for s in range(0, len(data), cr)])
        ground = ShardedSource.from_arrays(shards, attrs=ashards)
        attrs_arg = None
    else:
        ground = dj
        attrs_arg = attrs

    if args.dtype != "fp32":
        # narrow-storage run: wrap whatever access path was chosen in the
        # quantizing view — the wire format of every gather/chunk becomes
        # the storage dtype, and the tree solve dequantizes in-kernel
        base = (ArraySource(data, attrs=attrs) if args.source == "resident"
                else ground)
        ground = QuantizedSource(base, store_dtype=args.dtype,
                                 q_block_rows=args.q_block_rows)
        attrs_arg = None          # attrs flow through the source's gathers

    at_cache = args.autotune_cache
    if at_cache == "auto":
        at_cache = os.path.join(args.ckpt_dir or ".", "autotune_cache.json")

    mesh = make_submod_mesh()
    print(f"n={len(data)} d={data.shape[1]} k={args.k} mu={args.capacity} "
          f"devices={mesh.devices.size} alg={args.algorithm} "
          f"source={args.source} dtype={args.dtype} "
          f"permutation={args.permutation} "
          f"engine={args.engine} hosts={args.hosts} "
          f"constraint={args.constraint or 'none'}")
    # telemetry: observation only — attaching a tracer never changes the
    # run's outputs (pinned bit-identical by tests/test_telemetry.py)
    tracer = (Tracer() if (args.trace_out or args.metrics_out
                           or args.manifest_out) else None)
    cfg = TreeConfig(k=args.k, capacity=args.capacity,
                     algorithm=args.algorithm, eps=args.eps, seed=args.seed,
                     checkpoint_dir=args.ckpt_dir, resume=args.resume,
                     permutation=args.permutation, engine=args.engine,
                     hosts=args.hosts, capacity_bytes=args.capacity_bytes,
                     wave_autotune=args.wave_autotune,
                     async_checkpoint=args.async_checkpoint,
                     prefetch_depth=args.prefetch_depth,
                     fault_policy=fault_policy,
                     checkpoint_delta_every=args.ckpt_delta_every,
                     autotune_cache=at_cache, telemetry=tracer)
    with profiler_session(args.profile_dir):
        res = tree_maximize(obj, ground, cfg, mesh=mesh, fail_machines=fail,
                            wave_machines=args.wave_machines,
                            constraint=constraint, attrs=attrs_arg,
                            fault_injector=injector)

    manifest = res.manifest
    if manifest is None:
        # telemetry off: the report below is still manifest-driven — build
        # the same record the instrumented path gets, just don't export it
        qcols = ground.qcols if isinstance(ground, QuantizedSource) else 0
        fp = (ground.fingerprint()
              if isinstance(ground, GroundSetSource) else None)
        manifest = build_manifest(cfg, res, n=len(data), d=data.shape[1],
                                  dtype_label=args.dtype,
                                  itemsize=dtype_itemsize(args.dtype),
                                  qcols=qcols, source_fingerprint=fp)
    manifest.run["dataset"] = args.dataset

    if constraint is not None:
        ok, detail = check_feasible(constraint, res.sel_attrs, res.sel_mask)
        manifest.feasibility = {"ok": bool(ok), "detail": detail}
    if args.dtype != "fp32":
        # Barbosa-style exact validation: re-gather the selection from the
        # unquantized parent at fp32 and re-score with the exact objective
        rc = fp32_recheck(obj, ground, res.sel_rows, res.sel_mask,
                          solve_value=res.value)
        rel = abs(rc.value - res.value) / max(abs(rc.value), 1e-12)
        status = "PASS" if np.isfinite(rc.value) and rel < 5e-2 else "FAIL"
        manifest.recheck = {"fp32": float(rc.value),
                            "solve": float(res.value),
                            "rel_gap": float(rel), "status": status}

    # every grep-able report line (TREE/ingest/bytes/engine/autotune/
    # faults/checkpoint/feasibility/recheck) formats from the one manifest
    for line in format_report(manifest):
        print(line)

    if tracer is not None:
        if args.trace_out:
            tracer.export_chrome_trace(args.trace_out)
        if args.metrics_out:
            tracer.metrics.export_json(args.metrics_out)
    if args.manifest_out:
        manifest.write(args.manifest_out)

    if manifest.feasibility is not None:
        assert manifest.feasibility["ok"], manifest.feasibility["detail"]
    if manifest.recheck is not None:
        assert manifest.recheck["status"] == "PASS", manifest.recheck
    if not args.no_centralized:
        # non-resident runs stream the centralized column too (chunked lazy
        # greedy) — nothing in the comparison needs the all-resident array.
        # prefetch depth: explicit flag, else the autotuner's measured rates
        depth = args.prefetch_depth
        if depth is None and args.wave_autotune and res.engine_stats is not None:
            depth = suggest_prefetch_depth(res.engine_stats.gather_s,
                                           res.engine_stats.solve_s)
            print(f"prefetch-depth: {depth} (from autotuned gather/solve "
                  f"rates)")
        cg = centralized_greedy(
            obj, dj if args.source == "resident" else ground, args.k,
            constraint=constraint,
            attrs=attrs if args.source == "resident" else None,
            chunk_rows=args.chunk_rows, prefetch_depth=depth or 2)
        print(f"centralized greedy{' (constrained)' if constraint else ''}"
              f"{' [streamed]' if args.source != 'resident' else ''}: "
              f"f={float(cg.value):.6f} "
              f"(TREE at {res.value / float(cg.value):.2%})")
        m_base = args.baseline_machines or max(
            1, -(-len(data) // args.capacity))
        rg = randgreedi(obj, ground if args.source != "resident" else dj,
                        args.k, m_base, jax.random.PRNGKey(args.seed),
                        constraint=constraint,
                        attrs=attrs if args.source == "resident" else None)
        if constraint is not None:
            ok, detail = check_feasible(constraint,
                                        np.asarray(rg.sel_attrs),
                                        np.asarray(rg.sel_mask))
            assert ok, detail
        print(f"randgreedi (m={m_base}"
              f"{', constrained' if constraint else ''}): "
              f"f={float(rg.value):.6f} "
              f"(TREE at {res.value / float(rg.value):.2%})")


if __name__ == "__main__":
    main()
