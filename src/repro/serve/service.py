"""Submodular selection as a service: queries against a resident tree.

The offline driver (:func:`repro.core.tree.tree_maximize`) answers one
``(k, constraint)`` instance per full pass over the ground set.  A
:class:`SelectionService` amortizes that pass: the ground set is ingested
once into a resident :class:`repro.serve.session.SessionState`, and each
:class:`SelectionRequest` — its own cardinality ``k``, its own constraint,
optionally a query vector that reweights the exemplar objective toward
query-relevant evaluation points — is answered by re-running the tree's
*solve* rounds over the resident machine blocks.  Three properties make
that cheap at steady state:

* **Static round geometry.**  Per fuse key ``(k, algorithm, eps,
  constraint signature, weighted?, Mp, mu, d, a, n_eval)`` the machine
  ladder is fixed up front — round 0 over all ``Mp`` resident blocks,
  then ``m_{t+1} = ceil(m_t * k / mu)`` (strictly decreasing, else the
  request is rejected) down to one machine — so every request with the
  same fuse key replays the same shapes and the same compiled programs.
* **Dynamic constraint/query parameters.**  Budgets, partition caps, and
  query weights enter the trace as *operands* (``DynamicKnapsack`` /
  ``DynamicPartitionMatroid`` pytrees, ``WeightedExemplarClustering``
  eval weights), so a new budget value or a new query vector re-uses the
  compiled program — only a genuinely novel fuse key compiles.  The
  :class:`CompileCache` counts traces from inside the traced body, which
  is what lets tests pin "steady state never retraces" directly.
* **Per-machine solution reuse.**  Round-0 solutions are independent
  across machines and independent of the request seed (the seed perturbs
  only the post-round-0 key chain), so the service caches them per
  ``(fuse key, request fingerprint)`` and, after a ground-set delta,
  re-solves only the machine blocks whose membership version moved —
  folding the refreshed per-machine solutions through the same tail is
  then bit-identical to a full re-solve, which is the delta-vs-rebuild
  pin :mod:`tests.test_serve` holds.

PRNG contract: with ``key = PRNGKey(session.seed)`` and ``key1, kpart,
kalg = split(key, 3)`` (the exact round-0 split of ``tree_maximize``),
round-0 machine keys are ``split(kalg, Mp)`` — request-independent — and
rounds ≥ 1 chain from ``fold_in(key1, request.seed)``.  Two requests
differing only in ``seed`` therefore share cached round-0 solutions.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.constraints import (DynamicKnapsack, DynamicPartitionMatroid,
                                    Intersection, Knapsack, PartitionMatroid,
                                    Unconstrained, check_feasible, from_spec)
from repro.core.distributed import RoundResult, run_round, upload
from repro.core.objectives import (ExemplarClustering,
                                   WeightedExemplarClustering)
from repro.core.partition import n_parts, repartition_rows
from repro.core.tree import _fold_round
from repro.engine.telemetry import Histogram, span
from repro.serve.session import SessionState


# ---------------------------------------------------------------------------
# requests / results
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SelectionRequest:
    """One query against the resident ground set.

    ``constraint`` is a static constraint object from
    :mod:`repro.core.constraints`, a CLI spec string
    (``"knapsack:budget=2.5"``), or None; ``query`` is an optional (d,)
    vector — when given, the exemplar objective is reweighted toward
    evaluation points near the query (:func:`query_relevance_weights`).
    ``seed`` perturbs only the repartition chain of rounds ≥ 1.

    ``algorithm``/``eps`` select the request's solve tier (e.g. the
    low-adaptivity ``"threshold_batch"`` ladder for latency-bound
    requests); None inherits the service defaults.  Both are fuse-key
    dimensions, so mixed-tier batches split into per-tier fused launches.
    """
    k: int
    constraint: Any = None
    query: Any = None
    seed: int = 0
    algorithm: str | None = None
    eps: float | None = None


@dataclasses.dataclass
class SelectionResult:
    rows: np.ndarray            # (k, d) selected feature rows (masked→0)
    attrs: np.ndarray           # (k, a) their attribute rows
    mask: np.ndarray            # (k,) validity
    value: float                # objective value (reweighted if queried)
    oracle_calls: int
    feasible: bool
    detail: str
    latency_s: float = 0.0
    batch_size: int = 1
    solve_depth: int = 0        # sequential kernel-launch depth of the solve
    #                             (Σ over rounds of the per-round machine max)


# ---------------------------------------------------------------------------
# query → evaluation-point relevance weights
# ---------------------------------------------------------------------------


def query_relevance_weights(query, eval_set) -> np.ndarray:
    """RBF relevance of each evaluation point to the query, mean-normalized.

    ``w_j = n * exp(-||e_j - q||² / s) / Σ_i exp(-||e_i - q||² / s)`` with
    ``s`` the median squared distance (a parameter-free bandwidth).  The
    weights are normalized to **mean 1** — not sum 1 — so the reweighted
    objective stays on the unweighted objective's scale and a uniform
    relevance profile degenerates to exactly ``w = 1`` everywhere, which
    the weighted kernel treats bit-identically to the unweighted path
    (an IEEE-exact multiply by 1.0 with unchanged reduction order).
    """
    E = np.asarray(eval_set, np.float32)
    q = np.asarray(query, np.float32).reshape(-1)
    assert q.shape[0] == E.shape[1], (q.shape, E.shape)
    d2 = np.sum((E - q[None, :]) ** 2, axis=1, dtype=np.float64)
    scale = float(np.median(d2))
    if scale <= 0.0:
        return np.ones((E.shape[0],), np.float32)
    rel = np.exp(-d2 / scale)
    w = rel * (rel.shape[0] / rel.sum())
    return np.asarray(w, np.float32)


# ---------------------------------------------------------------------------
# constraint (signature, params) packing — class shape static, values traced
# ---------------------------------------------------------------------------


def constraint_signature(c) -> tuple:
    """Static identity of a constraint: class structure + columns + group
    count, everything that shapes the trace.  Parameter *values* (budget,
    caps) are deliberately excluded — they travel as traced operands."""
    if c is None or isinstance(c, Unconstrained):
        return ("none",)
    if isinstance(c, (Knapsack, DynamicKnapsack)):
        return ("knapsack", int(c.col))
    if isinstance(c, (PartitionMatroid, DynamicPartitionMatroid)):
        return ("partition", int(c.col), int(np.asarray(c.caps).shape[0]))
    if isinstance(c, Intersection):
        return ("intersection",) + tuple(
            constraint_signature(p) for p in c.parts)
    raise TypeError(f"unsupported constraint {type(c).__name__}")


def constraint_params(c) -> np.ndarray:
    """The constraint's parameter values flattened to one fp32 vector, in
    signature order — the traced operand paired with the static sig."""
    if c is None or isinstance(c, Unconstrained):
        return np.zeros((0,), np.float32)
    if isinstance(c, (Knapsack, DynamicKnapsack)):
        return np.asarray([c.budget], np.float32).reshape(1)
    if isinstance(c, (PartitionMatroid, DynamicPartitionMatroid)):
        return np.asarray(c.caps, np.float32).reshape(-1)
    if isinstance(c, Intersection):
        parts = [constraint_params(p) for p in c.parts]
        return (np.concatenate(parts) if parts
                else np.zeros((0,), np.float32))
    raise TypeError(f"unsupported constraint {type(c).__name__}")


def build_constraint(sig: tuple, params):
    """Rebuild the constraint inside a trace from (static sig, traced
    params) — the inverse of the packing above, producing the Dynamic*
    variants so parameter values never become compile-time constants."""
    c, used = _build_cons(sig, params, 0)
    assert used == params.shape[0], (sig, used, params.shape)
    return c


def _build_cons(sig, params, off):
    kind = sig[0]
    if kind == "none":
        return None, off
    if kind == "knapsack":
        return DynamicKnapsack(budget=params[off], col=sig[1]), off + 1
    if kind == "partition":
        G = sig[2]
        return (DynamicPartitionMatroid(caps=params[off:off + G],
                                        col=sig[1]), off + G)
    assert kind == "intersection", sig
    parts = []
    for sub in sig[1:]:
        p, off = _build_cons(sub, params, off)
        parts.append(p)
    return Intersection(tuple(parts)), off


def _static_constraint(c):
    """The hashable static twin of a (possibly dynamic) constraint — what
    the independent NumPy feasibility recheck consumes."""
    if c is None or isinstance(c, (Unconstrained, Knapsack, PartitionMatroid)):
        return c
    if isinstance(c, DynamicKnapsack):
        return Knapsack(float(np.asarray(c.budget)), c.col)
    if isinstance(c, DynamicPartitionMatroid):
        return PartitionMatroid(tuple(int(v) for v in np.asarray(c.caps)),
                                c.col)
    assert isinstance(c, Intersection), c
    return Intersection(tuple(_static_constraint(p) for p in c.parts))


# ---------------------------------------------------------------------------
# solve bodies — pure functions of (static fuse key) × (traced operands)
# ---------------------------------------------------------------------------

# fuse key layout: (k, alg, eps, cons_sig, weighted, Mp, mu, d, a, n_eval)


def round_ladder(Mp: int, k: int, mu: int) -> tuple[int, ...]:
    """Machine counts per round, fixed by (Mp, k, μ) alone: ``m_0 = Mp``,
    ``m_{t+1} = ⌈m_t k / μ⌉`` until one machine.  Raises when the ladder
    stalls (k too close to μ — Algorithm 1's compression has no progress
    to make), which surfaces at request-validation time, not mid-trace."""
    ms = [Mp]
    while ms[-1] > 1:
        nxt = n_parts(ms[-1] * k, mu)
        if nxt >= ms[-1]:
            raise ValueError(
                f"round ladder stalls at {ms[-1]} machines: k={k} too close "
                f"to capacity mu={mu} (need ceil(m*k/mu) < m)")
        ms.append(nxt)
    return tuple(ms)


def _make_obj(eval_set, ew, weighted: bool):
    if weighted:
        return WeightedExemplarClustering(eval_set, eval_weights=ew)
    return ExemplarClustering(eval_set)


def make_round0_fn(fuse_key):
    """Per-machine round-0 solve over the resident blocks for ONE request's
    (query weights, constraint params).  Returns per-machine results — the
    unit of the service's solution cache and partial re-solve."""
    k, alg, eps, sig, weighted, _Mp, _mu, _d, a, _n_eval = fuse_key

    def round0(blocks, bmask, keys, eval_set, ew, cparams, attrs):
        obj = _make_obj(eval_set, ew, weighted)
        cons = build_constraint(sig, cparams)
        # attributes ride beside the features as run_round's out-of-band
        # ``meta``: no widened (·, d + a) copy of the resident blocks, and
        # no feature slice of one inside the solve
        res = run_round(obj, blocks, bmask, keys, k=k, alg=alg, eps=eps,
                        attr_dim=a, constraint=cons,
                        meta=attrs if a else None)
        return (res.sol_rows, res.sol_mask, res.values, res.oracle_calls,
                res.depth)

    return round0


def _run_round_in_turn(obj, blocks, bmask, keys, **kw) -> RoundResult:
    """``run_round`` solving one machine at a time (``lax.map``).

    The tail's rounds hold few machines, so nothing is lost by solving
    them in turn.  Vmapped, a round of nine 1,000 × 3,072 blocks is one
    105 MiB operand that XLA keeps in VMEM across the greedy loop, and on
    a TPU v5e (libtpu 0.0.34) that program never returned once the picks
    spread over the blocks; one 12 MiB block at a time runs.
    """
    def one(x):
        blk, bm, key = x
        res = run_round(obj, blk[None], bm[None], key[None], **kw)
        return jax.tree.map(lambda v: v[0], res)

    return RoundResult(*jax.lax.map(one, (blocks, bmask, keys)))


def make_tail_fn(fuse_key):
    """Fold + rounds ≥ 1 from one request's per-machine round-0 results.

    The repartition chain is seeded ``fold_in(key1, request.seed)`` with
    ``key1`` the session's post-round-0 key — the request seed perturbs
    only this tail, never the cached round-0 solves."""
    k, alg, eps, sig, weighted, Mp, mu, d, a, _n_eval = fuse_key
    ladder = round_ladder(Mp, k, mu)
    w = d + a

    def tail(sol_rows, sol_mask, values, calls, depth, eval_set, ew,
             cparams, seed, key1):
        obj = _make_obj(eval_set, ew, weighted)
        cons = build_constraint(sig, cparams)
        (best_rows, best_mask, best_val, total_calls, solve_depth,
         _) = _fold_round(
            sol_rows, sol_mask, values, calls, depth,
            jnp.zeros((k, w), jnp.float32), jnp.zeros((k,), bool),
            jnp.float32(-jnp.inf), jnp.int32(0), jnp.int32(0))
        rows_in = sol_rows.reshape(-1, w)
        mask_in = sol_mask.reshape(-1)
        chain = jax.random.fold_in(key1, seed)
        for m in ladder[1:]:
            chain, kpart, kalg = jax.random.split(chain, 3)
            blk, bm = repartition_rows(rows_in, mask_in, kpart, m, mu)
            keys = jax.random.split(kalg, m)
            res = _run_round_in_turn(obj, blk, bm, keys, k=k, alg=alg,
                                     eps=eps, attr_dim=a, constraint=cons)
            (best_rows, best_mask, best_val, total_calls, round_depth,
             _) = _fold_round(
                res.sol_rows, res.sol_mask, res.values, res.oracle_calls,
                res.depth, best_rows, best_mask, best_val, total_calls,
                jnp.int32(0))
            solve_depth = solve_depth + round_depth
            rows_in = res.sol_rows.reshape(-1, w)
            mask_in = res.sol_mask.reshape(-1)
        return best_rows, best_mask, best_val, total_calls, solve_depth

    return tail


# ---------------------------------------------------------------------------
# compile cache — fused entries keyed (kind, fuse key, batch bucket)
# ---------------------------------------------------------------------------


class CompileCache:
    """Jitted solve entries with trace accounting and LRU eviction.

    ``entry`` returns the jitted callable for (kind, fuse key, bucket),
    building + jitting it on first use, named ``serve_<kind>`` so the
    device trace shows ``jit_serve_round0`` / ``jit_serve_tail``.  A
    Python-side counter increments *inside* the traced body — it fires
    exactly when JAX traces (first call per shape signature) and never
    on cached executions, so ``compiles`` is a direct retrace probe:
    steady-state serving must leave it flat, and tests pin that rather
    than inferring it from timings.

    ``capacity`` bounds the entry count: every ``entry`` hit refreshes
    recency, and inserts past the bound evict the least-recently-used
    callable (the hit counters *are* the recency signal — a workload's
    hot fuse keys stay resident).  None (default) keeps the historical
    unbounded behavior.  An evicted entry's trace count is dropped with
    it: rebuilding it later is a fresh compile by decision, not the
    warm-entry retrace ``steady_retraces`` exists to catch.
    """

    def __init__(self, capacity: int | None = None, metrics=None):
        import collections

        assert capacity is None or capacity >= 1, capacity
        self._fns: "collections.OrderedDict[tuple, Any]" = \
            collections.OrderedDict()
        self.capacity = capacity
        self.compiles = 0            # trace events across all entries
        self.hits = 0                # entry() calls served by an existing fn
        self.evictions = 0           # LRU entries dropped at capacity
        self.metrics = metrics       # telemetry MetricsRegistry, or None
        self._trace_counts: dict[tuple, int] = {}

    @property
    def keys(self) -> list[tuple]:
        return list(self._fns)

    def steady_retraces(self) -> int:
        """Traces beyond the first per entry — nonzero means a supposedly
        warm entry re-traced (the bug the cache exists to prevent)."""
        return sum(max(0, c - 1) for c in self._trace_counts.values())

    def entry(self, kind: str, fuse_key: tuple, bucket, build):
        key = (kind, fuse_key, bucket)
        fn = self._fns.get(key)
        if fn is not None:
            self.hits += 1
            self._fns.move_to_end(key)             # refresh LRU recency
            return fn
        inner = build()

        def counted(*operands, _inner=inner, _key=key):
            # body runs at trace time only: count the (re)trace
            self.compiles += 1
            self._trace_counts[_key] = self._trace_counts.get(_key, 0) + 1
            return _inner(*operands)

        counted.__name__ = counted.__qualname__ = f"serve_{kind}"
        fn = jax.jit(counted)
        self._fns[key] = fn
        while self.capacity is not None and len(self._fns) > self.capacity:
            old_key, _ = self._fns.popitem(last=False)
            self._trace_counts.pop(old_key, None)
            self.evictions += 1
            if self.metrics is not None:
                self.metrics.counter("serve_compile_cache_evictions").inc()
        if self.metrics is not None:
            self.metrics.gauge("serve_compile_cache_entries").set(
                len(self._fns))
        return fn


def _device_bytes_limit() -> int | None:
    """The default device's memory limit, where its backend reports one."""
    stats = jax.local_devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


def _bucket(n: int) -> int:
    """Pad counts to powers of two so batch sizes hit few distinct shapes."""
    b = 1
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# the service
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Prep:
    req: SelectionRequest
    cons_static: Any
    sig: tuple
    weighted: bool
    ew: np.ndarray               # (n_eval,) fp32, or (0,) when unweighted
    cparams: np.ndarray          # (P,) fp32
    fuse_key: tuple
    fp: str                      # request fingerprint (sol-cache key part)


class SelectionService:
    """Answers :class:`SelectionRequest`s against a resident session.

    ``serve(requests)`` groups a micro-batch by fuse key, pads each group
    to a power-of-two bucket, and dispatches one fused ``lax.map`` solve
    per group.  Answers are deterministic per *(fuse key, bucket)*: the
    same request in the same bucket always yields the same bits, and the
    bucket-1 path is pinned bit-identical to :func:`offline_solve`.
    Across buckets XLA compiles distinct programs whose float reductions
    can differ in the last bit, and a near-tie in the fold argmax can
    amplify that into a different (equally valid) coreset — so batching
    trades the cross-composition bit-pin for fused-launch throughput
    while keeping feasibility and value accuracy.

    Round-0 solutions stay on the device: the solution cache holds each
    request's per-machine arrays as device arrays, ``Mp·k·(d+a)·4`` bytes
    of HBM an entry (plus ``Mp·(k+12)`` bytes of mask, value, call and
    depth columns), and the tail program stacks a group's entries inside
    its own ``jit``.  Besides ``sol_cache_capacity`` entries, the cache
    keeps its bytes within half of the device's ``bytes_limit`` where the
    backend reports one, evicting least recently used entries first.

    Each ``serve`` call opens spans (:class:`repro.engine.telemetry.span`,
    on the profiler's clock, and in ``tracer`` when given):
    ``serve.prepare`` over the slice, then per fuse-key group
    ``serve.group`` holding the round-0 launch for cache misses
    (``serve.round0``, ended by a block on its outputs, then
    ``serve.round0.fetch`` slicing each request's solutions into the
    cache on the device, or ``serve.round0.partial`` after a delta) and
    the tail: ``serve.tail.stack`` (host padding of the group's small
    per-request operands: query weights, constraint parameters, seeds),
    ``serve.tail.upload``, ``serve.tail`` (the program, which stacks the
    solutions, ended by a block), ``serve.tail.fetch`` and
    ``serve.check``.
    """

    def __init__(self, session: SessionState, eval_set, *,
                 algorithm: str = "greedy", eps: float = 0.5,
                 tracer=None, compile_cache_capacity: int | None = None,
                 sol_cache_capacity: int | None = None):
        import collections

        self.session = session
        self.eval_set = np.asarray(eval_set, np.float32)
        self.algorithm = algorithm
        self.eps = eps
        self.tracer = tracer
        self.cache = CompileCache(
            capacity=compile_cache_capacity,
            metrics=tracer.metrics if tracer is not None else None)
        # round-0 solution cache, LRU-bounded: keys embed the session
        # generation, so entries from superseded generations can never hit
        # again — the recency order drains them first once capacity binds
        self._sol_cache: "collections.OrderedDict[tuple, dict]" = \
            collections.OrderedDict()
        self.sol_cache_capacity = sol_cache_capacity
        assert sol_cache_capacity is None or sol_cache_capacity >= 1, (
            sol_cache_capacity)
        self.sol_evictions = 0
        self._sol_bytes = 0
        limit = _device_bytes_limit()
        self._sol_bytes_cap = limit // 2 if limit else None
        self._dev: dict[str, Any] = {}
        self._geom: tuple | None = None
        self.requests_served = 0
        self.batches = 0
        self.deltas = 0
        self.delta_changed = 0
        self.rebuilds = 0
        self.sol_hits = 0
        self.partial_resolves = 0
        self.queue_depth_max = 0
        self.latencies_s: list[float] = []
        self.last_value = 0.0
        self.last_calls = 0
        self.last_rounds = 0
        self.last_depth = 0
        self._sync_geometry()

    # -- geometry / staging ----------------------------------------------
    def _sync_geometry(self) -> None:
        s = self.session
        geom = (s.generation, s.Mp, s.mu, s.d, s.a)
        if geom == self._geom:
            return
        self._geom = geom
        key = jax.random.PRNGKey(s.seed)
        self._key1, _kpart, kalg = jax.random.split(key, 3)
        self._keys0 = jax.random.split(kalg, s.Mp)
        self._dev = {}

    def _staged(self):
        """Device copies of the resident features, validity and attribute
        columns, refreshed when membership moves, and of the eval set.  One
        feature copy serves every request: constrained solves read the
        attributes beside it."""
        s = self.session
        stamp = (s.generation, s.versions.tobytes())
        if self._dev.get("stamp") != stamp:
            self._dev = {}            # release the stale copy before upload
            self._dev = {"stamp": stamp, "blocks": upload(s.blocks),
                         "valid": jnp.asarray(s.valid),
                         "attrs": jnp.asarray(s.attrs),
                         "eval_set": jnp.asarray(self.eval_set)}
        d = self._dev
        return d["blocks"], d["valid"], d["attrs"], d["eval_set"]

    # -- request preparation ---------------------------------------------
    def _prepare(self, req: SelectionRequest) -> _Prep:
        s = self.session
        if not (0 < req.k < s.mu):
            raise ValueError(f"request k={req.k} must satisfy 0 < k < "
                             f"mu={s.mu}")
        cons = (from_spec(req.constraint) if isinstance(req.constraint, str)
                else req.constraint)
        cons_static = _static_constraint(cons)
        sig = constraint_signature(cons)
        cparams = constraint_params(cons)
        weighted = req.query is not None
        ew = (query_relevance_weights(req.query, self.eval_set) if weighted
              else np.zeros((0,), np.float32))
        a_used = 0 if sig == ("none",) else s.a
        if sig != ("none",):
            assert s.a > 0, "constrained request against an attribute-less " \
                            "session — ingest with attrs"
        alg = self.algorithm if req.algorithm is None else req.algorithm
        eps = self.eps if req.eps is None else req.eps
        fuse_key = (req.k, alg, eps, sig, weighted,
                    s.Mp, s.mu, s.d, a_used, self.eval_set.shape[0])
        round_ladder(s.Mp, req.k, s.mu)       # validate early (may raise)
        h = hashlib.sha1()
        h.update(repr(fuse_key).encode())
        h.update(cparams.tobytes())
        h.update(ew.tobytes())
        return _Prep(req=req, cons_static=cons_static, sig=sig,
                     weighted=weighted, ew=ew, cparams=cparams,
                     fuse_key=fuse_key, fp=h.hexdigest())

    # -- serving ----------------------------------------------------------
    def query(self, req: SelectionRequest) -> SelectionResult:
        return self.serve([req])[0]

    def serve(self, requests: list[SelectionRequest]) -> list[SelectionResult]:
        if not requests:
            return []
        self._sync_geometry()
        results: list[SelectionResult | None] = [None] * len(requests)
        groups: dict[tuple, list[tuple[int, _Prep]]] = {}
        with span("serve.prepare", tracer=self.tracer,
                  requests=len(requests)):
            for i, req in enumerate(requests):
                prep = self._prepare(req)
                groups.setdefault(prep.fuse_key, []).append((i, prep))
        for fk, items in groups.items():
            with span("serve.group", tracer=self.tracer, track="serve",
                      batch=len(items), k=fk[0],
                      constraint=str(fk[3][0])) as grp:
                outs = self._serve_group(fk, items)
            lat = grp.t1 - grp.t0
            for (i, prep), out in zip(items, outs):
                out.latency_s = lat
                out.batch_size = len(items)
                results[i] = out
                self.latencies_s.append(lat)
            self.requests_served += len(items)
            self.batches += 1
            if self.tracer is not None:
                m = self.tracer.metrics
                m.counter("serve_requests").inc(len(items))
                m.counter("serve_batches").inc()
                m.histogram("serve_batch_size").observe(len(items))
                for _ in items:
                    m.histogram("serve_request_latency_s").observe(lat)
        return results                                 # type: ignore[return-value]

    def _serve_group(self, fk, items) -> list[SelectionResult]:
        s = self.session
        k, _alg, _eps, sig, _weighted, Mp, _mu, d, a, n_eval = fk
        blocks, bmask, attrs, eval_set = self._staged()
        gen = s.generation

        # --- per-request round-0 solutions: cache → partial → batched miss
        sols: list[tuple | None] = [None] * len(items)
        misses: list[int] = []
        for j, (_i, prep) in enumerate(items):
            ck = (fk, prep.fp, gen)
            ent = self._sol_cache.get(ck)
            if ent is None:
                misses.append(j)
                continue
            self._sol_cache.move_to_end(ck)        # refresh LRU recency
            changed = np.flatnonzero(ent["versions"] != s.versions)
            if changed.size:
                self._partial_resolve(fk, prep, ent, changed, blocks, bmask,
                                      attrs, eval_set)
            else:
                self.sol_hits += 1
            sols[j] = ent["sols"]
        if misses:
            self._solve_misses(fk, items, misses, sols, blocks, bmask, attrs,
                               eval_set)

        # --- tail: fold + rounds ≥ 1, batched over the group.  The cached
        # solutions enter as one tuple per request, the bucket padded by
        # reference, and are stacked inside the program.
        B = _bucket(len(items))
        pad = lambda arrs: arrs + [arrs[-1]] * (B - len(arrs))
        with span("serve.tail.stack", tracer=self.tracer):
            small = tuple(np.stack(pad(arrs)) for arrs in (
                [p.ew for _i, p in items],
                [p.cparams for _i, p in items],
                [np.int32(p.req.seed) for _i, p in items]))
        with span("serve.tail.upload", tracer=self.tracer):
            ews, cps, seeds = jax.block_until_ready(jax.device_put(small))

        def build_tail():
            body = make_tail_fn(fk)

            def batched(sols, eval_set, ews, cps, seeds, key1):
                stacked = tuple(jnp.stack([sv[f] for sv in sols])
                                for f in range(5))

                def one(x):
                    sr, sm, v, c, dp, ew, cp, sd = x
                    return body(sr, sm, v, c, dp, eval_set, ew, cp, sd,
                                key1)
                return jax.lax.map(one, stacked + (ews, cps, seeds))
            return batched

        fn = self.cache.entry("tail", fk, B, build_tail)
        with span("serve.tail", tracer=self.tracer, batch=B):
            out = jax.block_until_ready(fn(
                tuple(pad(sols)), eval_set, ews, cps, seeds, self._key1))
        with span("serve.tail.fetch", tracer=self.tracer):
            brows, bmasks, bvals, bcalls, bdepth = (np.asarray(x)
                                                    for x in out)

        outs = []
        with span("serve.check", tracer=self.tracer):
            for j, (_i, prep) in enumerate(items):
                rows_w, mask = brows[j], bmasks[j]
                rows, attrs = rows_w[:, :d], rows_w[:, d:]
                ok, detail = check_feasible(prep.cons_static, attrs, mask)
                self.last_value = float(bvals[j])
                self.last_calls = int(bcalls[j])
                self.last_rounds = len(round_ladder(Mp, k, s.mu))
                self.last_depth = int(bdepth[j])
                outs.append(SelectionResult(
                    rows=rows, attrs=attrs, mask=mask, value=float(bvals[j]),
                    oracle_calls=int(bcalls[j]), feasible=bool(ok),
                    detail=detail, solve_depth=int(bdepth[j])))
        return outs

    def _solve_misses(self, fk, items, misses, sols, blocks, bmask,
                      attrs, eval_set) -> None:
        """Round 0 for requests with no cached per-machine solutions, one
        fused batched launch; results land in the solution cache.

        Each miss's entry is its own device slice of the batched output
        (a copy, so evicting it frees its HBM and pins no batch buffer):
        ``Mp·k·(d+a)·4`` bytes of solution rows plus ``Mp·(k+12)`` bytes
        of masks, values, call counts and depths, 100.8 MB at
        Mp = 164, k = 50, d + a = 3,074.  Nothing crosses to the host."""
        s = self.session
        B = _bucket(len(misses))
        pad = lambda arrs: np.stack(arrs + [arrs[-1]] * (B - len(arrs)))
        ews = pad([items[j][1].ew for j in misses])
        cps = pad([items[j][1].cparams for j in misses])

        def build_round0():
            body = make_round0_fn(fk)

            def batched(blocks, bmask, keys, eval_set, ews, cps, attrs):
                def one(x):
                    ew, cp = x
                    return body(blocks, bmask, keys, eval_set, ew, cp, attrs)
                return jax.lax.map(one, (ews, cps))
            return batched

        fn = self.cache.entry("round0", fk, (B, s.Mp), build_round0)
        with span("serve.round0", tracer=self.tracer, batch=B):
            out = jax.block_until_ready(fn(blocks, bmask, self._keys0,
                                           eval_set, ews, cps, attrs))
        with span("serve.round0.fetch", tracer=self.tracer):
            per_req = jax.block_until_ready(
                [tuple(x[b] for x in out) for b in range(len(misses))])
        for j, sv in zip(misses, per_req):
            ck = (fk, items[j][1].fp, s.generation)
            old = self._sol_cache.pop(ck, None)   # a repeat within the group
            if old is not None:
                self._sol_bytes -= old["bytes"]
            nbytes = sum(x.nbytes for x in sv)
            self._sol_cache[ck] = {"versions": s.versions.copy(),
                                   "sols": sv, "bytes": nbytes}
            self._sol_bytes += nbytes
            sols[j] = sv
        while self._sol_cache and (
                (self.sol_cache_capacity is not None
                 and len(self._sol_cache) > self.sol_cache_capacity)
                or (self._sol_bytes_cap is not None
                    and self._sol_bytes > self._sol_bytes_cap)):
            _ck, old = self._sol_cache.popitem(last=False)
            self._sol_bytes -= old["bytes"]
            self.sol_evictions += 1
            if self.tracer is not None:
                self.tracer.metrics.counter("serve_sol_cache_evictions").inc()
        if self.tracer is not None:
            m = self.tracer.metrics
            m.gauge("serve_sol_cache_entries").set(len(self._sol_cache))
            m.gauge("serve_sol_cache_bytes").set(self._sol_bytes)

    def _partial_resolve(self, fk, prep, ent, changed, blocks, bmask,
                         attrs, eval_set) -> None:
        """Re-solve only the machine blocks whose membership version moved
        since this request fingerprint's round-0 solutions were cached,
        then scatter them into the cached device arrays — the delta fast
        path."""
        s = self.session
        C = int(changed.size)
        Cp = min(_bucket(C), s.Mp)
        idx = np.concatenate([changed,
                              np.repeat(changed[-1:], Cp - C)]).astype(int)

        def build_round0():
            body = make_round0_fn(fk)

            def batched(blocks, bmask, keys, eval_set, ews, cps, attrs):
                def one(x):
                    ew, cp = x
                    return body(blocks, bmask, keys, eval_set, ew, cp, attrs)
                return jax.lax.map(one, (ews, cps))
            return batched

        fn = self.cache.entry("round0", fk, (1, Cp), build_round0)
        with span("serve.round0.partial", tracer=self.tracer, machines=C):
            if C < s.Mp:
                blocks, bmask, keys, attrs = (blocks[idx], bmask[idx],
                                              self._keys0[idx], attrs[idx])
            else:                  # every block moved: no gathered copy
                keys = self._keys0
            out = fn(blocks, bmask, keys, eval_set,
                     prep.ew[None], prep.cparams[None], attrs)
            ent["sols"] = jax.block_until_ready(tuple(
                old.at[changed].set(new[0, :C])
                for old, new in zip(ent["sols"], out)))
        ent["versions"] = s.versions.copy()
        self.partial_resolves += 1
        if self.tracer is not None:
            self.tracer.instant("partial-resolve", "serve", track="serve",
                                machines=C)

    # -- ground-set deltas -------------------------------------------------
    def apply_delta(self, insert_rows=None, delete_ids=None,
                    insert_attrs=None):
        with span("serve.delta", tracer=self.tracer, track="serve") as sp:
            rep = self.session.apply_delta(insert_rows=insert_rows,
                                           delete_ids=delete_ids,
                                           insert_attrs=insert_attrs)
            self.deltas += 1
            self.delta_changed += len(rep.changed_machines)
            self.rebuilds += int(rep.rebuilt)
            self._sync_geometry()
            sp.args.update(inserted=rep.inserted, deleted=rep.deleted,
                           changed=len(rep.changed_machines),
                           rebuilt=rep.rebuilt)
        return rep

    def note_queue_depth(self, depth: int) -> None:
        self.queue_depth_max = max(self.queue_depth_max, int(depth))
        if self.tracer is not None:
            self.tracer.metrics.gauge("serve_queue_depth").set(depth)
            self.tracer.metrics.histogram(
                "serve_queue_depth_hist").observe(depth)

    # -- reporting ---------------------------------------------------------
    def serve_stats(self) -> dict:
        h = Histogram()
        for v in self.latencies_s:
            h.observe(v)
        sm = h.summary()
        return {
            "requests": self.requests_served,
            "batches": self.batches,
            "latency_p50_ms": 1e3 * (sm.get("p50") or 0.0),
            "latency_p95_ms": 1e3 * (sm.get("p95") or 0.0),
            "queue_depth_max": int(self.queue_depth_max),
            "cache_keys": len(self.cache.keys),
            "compiles": self.cache.compiles,
            "cache_hits": self.cache.hits,
            "cache_evictions": self.cache.evictions,
            "cache_capacity": self.cache.capacity,
            "steady_retraces": self.cache.steady_retraces(),
            "sol_cache_hits": self.sol_hits,
            "sol_cache_entries": len(self._sol_cache),
            "sol_cache_evictions": self.sol_evictions,
            "sol_cache_bytes": self._sol_bytes,
            "sol_cache_capacity": self.sol_cache_capacity,
            "partial_resolves": self.partial_resolves,
            "deltas": self.deltas,
            "changed_machines": self.delta_changed,
            "rebuilds": self.rebuilds,
        }


# ---------------------------------------------------------------------------
# offline reference: same ladder/keys, fresh unbatched uncached solve
# ---------------------------------------------------------------------------


def offline_solve(session: SessionState, eval_set, req: SelectionRequest, *,
                  algorithm: str = "greedy",
                  eps: float = 0.5) -> SelectionResult:
    """Direct solve of one request against the resident state: the same
    round bodies the service compiles, called once with fresh ``jax.jit``
    wrappers and no batching, caching, or partial re-solve.  This is the
    reference the bit-identity pin compares the served answers to —
    served == offline says the whole serving apparatus (micro-batching
    via ``lax.map``, the compile cache, cached + partially re-solved
    round-0 solutions) is execution policy only.
    """
    svc = SelectionService.__new__(SelectionService)     # prep helpers only
    svc.session = session
    svc.eval_set = np.asarray(eval_set, np.float32)
    svc.algorithm = algorithm
    svc.eps = eps
    prep = SelectionService._prepare(svc, req)
    fk = prep.fuse_key
    _k, _alg, _eps, _sig, _weighted, Mp, mu, d, a, _n_eval = fk

    key = jax.random.PRNGKey(session.seed)
    key1, _kpart, kalg = jax.random.split(key, 3)
    keys0 = jax.random.split(kalg, Mp)
    r0 = jax.jit(make_round0_fn(fk))(
        jnp.asarray(session.blocks), jnp.asarray(session.valid), keys0,
        svc.eval_set, jnp.asarray(prep.ew), jnp.asarray(prep.cparams),
        jnp.asarray(session.attrs))
    brows, bmask, bval, bcalls, bdepth = jax.jit(make_tail_fn(fk))(
        *r0, svc.eval_set, jnp.asarray(prep.ew), jnp.asarray(prep.cparams),
        jnp.int32(req.seed), key1)
    rows_w = np.asarray(brows)
    mask = np.asarray(bmask)
    rows, attrs = rows_w[:, :d], rows_w[:, d:]
    ok, detail = check_feasible(prep.cons_static, attrs, mask)
    return SelectionResult(rows=rows, attrs=attrs, mask=mask,
                           value=float(np.asarray(bval)),
                           oracle_calls=int(np.asarray(bcalls)),
                           feasible=bool(ok), detail=detail,
                           solve_depth=int(np.asarray(bdepth)))
