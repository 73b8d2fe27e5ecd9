"""Selection-service pins: served == offline, delta == rebuild, warm
compile cache never retraces, query reweighting, feasibility, telemetry.

Everything here runs against one small resident session (n=120, μ=12,
Mp=10) so the per-fuse-key compiles are paid once per module.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (ArraySource, ExemplarClustering, TreeConfig,
                        WeightedExemplarClustering, check_feasible,
                        constraint_from_spec)
from repro.core.tree import _round0_partition
from repro.engine import Tracer
from repro.kernels import ref as kref
from repro.serve import (Dispatcher, SelectionRequest, SelectionService,
                         SessionState, ingest, offline_solve,
                         query_relevance_weights, round_ladder)

N, D, MU, K = 112, 5, 12, 4     # L=10 machines, 8 free slots for inserts
N_EVAL = 24


def _data():
    rng = np.random.default_rng(11)
    X = rng.normal(size=(N, D)).astype(np.float32)
    attrs = np.zeros((N, 2), np.float32)
    attrs[:, 0] = rng.uniform(0.2, 1.0, N).astype(np.float32)
    attrs[:, 1] = rng.integers(0, 3, N).astype(np.float32)
    E = X[rng.choice(N, N_EVAL, replace=False)]
    return X, attrs, E


@pytest.fixture(scope="module")
def world():
    X, attrs, E = _data()
    cfg = TreeConfig(k=K, capacity=MU, seed=5)
    st = ingest(ArraySource(X), cfg, attrs=attrs)
    svc = SelectionService(st, E)
    return X, attrs, E, cfg, st, svc


def _fresh_session(X, attrs, cfg):
    return ingest(ArraySource(X), cfg, attrs=attrs)


# ---------------------------------------------------------------------------
# ingestion → resident state
# ---------------------------------------------------------------------------


def test_ingest_matches_round0_partition(world):
    X, attrs, E, cfg, st, _svc = world
    assert st.n_items == N and st.Mp == 10 and st.d == D and st.a == 2
    # the resident (machine, slot) -> item map IS the tree's round-0
    # virtual-location partition for the same seed
    key = jax.random.PRNGKey(cfg.seed)
    _key1, kpart, _kalg = jax.random.split(key, 3)
    part = _round0_partition(kpart, N, st.L, MU, cfg.permutation)
    assert np.array_equal(np.asarray(part.idx),
                          st.item_ids.astype(np.int32))
    # rows and attrs live at their assigned slots, fp32, zero on padding
    m, s = next(zip(*np.nonzero(st.valid)))
    iid = int(st.item_ids[m, s])
    assert np.array_equal(st.blocks[m, s], X[iid])
    assert np.array_equal(st.attrs[m, s], attrs[iid])
    assert not st.blocks[~st.valid].any()


def test_session_save_load(tmp_path, world):
    _X, _attrs, _E, _cfg, st, _svc = world
    st.save(str(tmp_path))
    st2 = SessionState.load(str(tmp_path))
    for f in ("blocks", "attrs", "valid", "item_ids", "versions"):
        assert np.array_equal(getattr(st, f), getattr(st2, f)), f
    assert st2._pos == st._pos


def test_round_ladder_static_and_stall():
    assert round_ladder(10, K, MU) == (10, 4, 2, 1)
    assert round_ladder(1, K, MU) == (1,)
    with pytest.raises(ValueError, match="stalls"):
        round_ladder(4, 11, 12)          # ceil(4*11/12) = 4: no progress


# ---------------------------------------------------------------------------
# pin (a): served selection ≡ direct offline solve on the resident state
# ---------------------------------------------------------------------------

CONS = [None, "knapsack:budget=1.5", "partition:caps=2,2,2:col=1",
        "intersection:knapsack:budget=2.0+partition:caps=2,2,2:col=1"]


@pytest.mark.parametrize("cons", CONS)
def test_served_equals_offline(world, cons):
    X, _attrs, E, _cfg, st, svc = world
    req = SelectionRequest(k=K, constraint=cons)
    got = svc.query(req)
    ref = offline_solve(st, E, req)
    assert got.value == ref.value
    assert np.array_equal(got.rows, ref.rows)
    assert np.array_equal(got.attrs, ref.attrs)
    assert np.array_equal(got.mask, ref.mask)
    assert got.oracle_calls == ref.oracle_calls
    # pin (c): every served coreset verifies feasible independently
    assert got.feasible, got.detail
    ok, detail = check_feasible(constraint_from_spec(cons) if cons else None,
                                got.attrs, got.mask)
    assert ok, detail


@pytest.mark.parametrize("cons", [None, "knapsack:budget=1.5"])
def test_served_equals_offline_with_query(world, cons):
    X, _attrs, E, _cfg, st, svc = world
    req = SelectionRequest(k=K, constraint=cons, query=X[17], seed=3)
    got = svc.query(req)
    ref = offline_solve(st, E, req)
    assert got.value == ref.value
    assert np.array_equal(got.rows, ref.rows)
    assert got.feasible, got.detail


def test_mixed_k_batch_equals_singles(world):
    X, _attrs, _E, _cfg, _st, svc = world
    reqs = [SelectionRequest(k=K), SelectionRequest(k=3),
            SelectionRequest(k=K, constraint="knapsack:budget=1.5"),
            SelectionRequest(k=K, seed=9, query=X[2])]
    batched = svc.serve(reqs)
    singles = [svc.serve([r])[0] for r in reqs]
    for b, s in zip(batched, singles):
        assert b.value == s.value
        assert np.array_equal(b.rows, s.rows)


def test_request_seed_perturbs_only_tail(world):
    _X, _attrs, _E, _cfg, _st, svc = world
    hits0 = svc.sol_hits
    a = svc.query(SelectionRequest(k=K, seed=1))
    b = svc.query(SelectionRequest(k=K, seed=2))
    # both requests share cached round-0 per-machine solutions
    assert svc.sol_hits >= hits0 + 1
    # ...and the tail repartition chain actually moved
    assert a.value != b.value or not np.array_equal(a.rows, b.rows)


# ---------------------------------------------------------------------------
# compile cache: steady state never retraces; novel shapes compile once
# ---------------------------------------------------------------------------


def test_warm_cache_no_retrace_on_new_params(world):
    X, _attrs, _E, _cfg, _st, svc = world
    svc.query(SelectionRequest(k=K, constraint="knapsack:budget=1.5"))
    c0 = svc.cache.compiles
    # new budget value, new query vector, new seed: same fuse keys
    svc.query(SelectionRequest(k=K, constraint="knapsack:budget=0.9"))
    svc.query(SelectionRequest(k=K, constraint="knapsack:budget=2.7",
                               seed=4))
    assert svc.cache.compiles == c0, "parameter-only change retraced"
    svc.query(SelectionRequest(k=K, query=X[33]))
    svc.query(SelectionRequest(k=K, query=X[44]))
    assert svc.cache.compiles == c0, "new query vector retraced"
    assert svc.cache.steady_retraces() == 0


def test_novel_shape_compiles_exactly_once(world):
    _X, _attrs, _E, _cfg, _st, svc = world
    c0 = svc.cache.compiles
    k_novel = 5
    svc.query(SelectionRequest(k=k_novel))
    grew = svc.cache.compiles - c0
    assert grew >= 1                      # round0 + tail entries traced
    svc.query(SelectionRequest(k=k_novel))
    assert svc.cache.compiles == c0 + grew, "repeat of novel shape retraced"
    # every entry traced exactly once, ever
    assert all(c == 1 for c in svc.cache._trace_counts.values())


# ---------------------------------------------------------------------------
# pin (b): delta-then-query ≡ rebuild-then-query
# ---------------------------------------------------------------------------


def _delta_args(kind, X, st):
    if kind == "every-machine":            # one deletion in each block
        return None, None, [int(ids[ok][0])
                            for ids, ok in zip(st.item_ids, st.valid)]
    rng = np.random.default_rng(77)
    ins = (X[rng.choice(N, 6, replace=False)] * np.float32(0.5),
           np.ascontiguousarray(
               np.stack([rng.uniform(0.2, 1.0, 6),
                         rng.integers(0, 3, 6).astype(float)],
                        axis=1).astype(np.float32)))
    dels = [int(i) for i in rng.choice(N, 5, replace=False)]
    if kind == "insert":
        return ins[0], ins[1], None
    if kind == "delete":
        return None, None, dels
    return ins[0], ins[1], dels


@pytest.mark.parametrize("kind", ["insert", "delete", "mixed",
                                  "every-machine"])
@pytest.mark.parametrize("cons", [None, "knapsack:budget=1.5"])
def test_delta_equals_rebuild(world, kind, cons):
    X, attrs, E, cfg, _st, _svc = world
    req = SelectionRequest(k=K, constraint=cons)

    # path 1: resident delta (block-local re-solve), then query
    s1 = _fresh_session(X, attrs, cfg)
    rows, ia, dels = _delta_args(kind, X, s1)
    v1 = SelectionService(s1, E)
    v1.query(req)                          # populate the solution cache
    rep = v1.apply_delta(insert_rows=rows, insert_attrs=ia, delete_ids=dels)
    assert not rep.rebuilt
    if kind == "every-machine":            # re-solve without a gathered copy
        assert len(rep.changed_machines) == s1.Mp
    a = v1.query(req)
    if kind != "insert":
        assert v1.partial_resolves >= 1    # deltas touched cached machines

    # path 2: the same session rebuilt from source + delta log, then query
    s1.rebuild()
    v2 = SelectionService(s1, E)
    b = v2.query(req)

    # path 3: fresh ingest + the same delta on a cold service
    s3 = _fresh_session(X, attrs, cfg)
    s3.apply_delta(insert_rows=rows, insert_attrs=ia, delete_ids=dels)
    c = SelectionService(s3, E).query(req)

    assert np.array_equal(s1.item_ids, s3.item_ids)
    for other in (b, c):
        assert a.value == other.value
        assert np.array_equal(a.rows, other.rows)
        assert np.array_equal(a.mask, other.mask)
        assert a.oracle_calls == other.oracle_calls
    assert a.feasible, a.detail


def test_delta_capacity_overflow_falls_back_to_rebuild(world):
    X, attrs, E, cfg, _st, _svc = world
    s = _fresh_session(X, attrs, cfg)
    free = s.free_slots
    rng = np.random.default_rng(3)
    n_ins = free + 4
    rows = rng.normal(size=(n_ins, D)).astype(np.float32)
    ia = np.zeros((n_ins, 2), np.float32)
    ia[:, 0] = 0.5
    rep = s.apply_delta(insert_rows=rows, insert_attrs=ia)
    assert rep.rebuilt and s.generation == 1
    assert s.n_items == N + n_ins
    assert s.L * MU >= s.n_items
    # the grown session still serves and verifies feasible
    res = SelectionService(s, E).query(
        SelectionRequest(k=K, constraint="knapsack:budget=1.5"))
    assert res.feasible, res.detail


def test_delete_unknown_id_raises(world):
    X, attrs, _E, cfg, _st, _svc = world
    s = _fresh_session(X, attrs, cfg)
    s.apply_delta(delete_ids=[7])
    with pytest.raises(KeyError):
        s.apply_delta(delete_ids=[7])      # already gone


# ---------------------------------------------------------------------------
# query reweighting: uniform == unweighted bit-identically; NumPy reference
# ---------------------------------------------------------------------------


def test_uniform_weights_bit_identical_to_unweighted():
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(32, D)).astype(np.float32))
    E = jnp.asarray(rng.normal(size=(N_EVAL, D)).astype(np.float32))
    ones = jnp.ones((N_EVAL,), jnp.float32)
    cur = jnp.sum(E * E, axis=-1)
    g0 = kref.exemplar_gains(X, E, cur)
    g1 = kref.exemplar_gains(X, E, cur, eval_weights=ones)
    assert np.array_equal(np.asarray(g0), np.asarray(g1))
    # objective level: evaluate() and fused select agree bit-for-bit
    mask = jnp.ones((32,), bool)
    o0 = ExemplarClustering(E)
    o1 = WeightedExemplarClustering(E, eval_weights=ones)
    S = X[:5]
    smask = jnp.ones((5,), bool)
    assert float(o0.evaluate(S, smask)) == float(o1.evaluate(S, smask))
    r0 = o0.fused_select(X, mask, 4)
    r1 = o1.fused_select(X, mask, 4)
    assert np.array_equal(np.asarray(r0[0]), np.asarray(r1[0]))
    assert float(r0[2]) == float(r1[2])


def test_weighted_gains_match_numpy_reference():
    rng = np.random.default_rng(4)
    X = rng.normal(size=(16, D)).astype(np.float32)
    E = rng.normal(size=(10, D)).astype(np.float32)
    w = rng.uniform(0.1, 2.0, 10).astype(np.float32)
    w = w * (10.0 / w.sum())
    cur = np.sum(E * E, axis=-1).astype(np.float32)
    got = np.asarray(kref.exemplar_gains(
        jnp.asarray(X), jnp.asarray(E), jnp.asarray(cur),
        eval_weights=jnp.asarray(w)))
    d2 = (np.sum(X * X, 1)[:, None] - 2.0 * X @ E.T
          + np.sum(E * E, 1)[None, :])
    want = (np.maximum(cur[None, :] - d2, 0.0) * w[None, :]).sum(1) / 10.0
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)


def test_query_relevance_weights_properties(world):
    X, _attrs, E, _cfg, _st, _svc = world
    w = query_relevance_weights(X[9], E)
    assert w.shape == (N_EVAL,) and w.dtype == np.float32
    assert (w >= 0).all()
    np.testing.assert_allclose(w.mean(), 1.0, rtol=1e-5)
    # degenerate query (all eval points equidistant) → exactly uniform
    w0 = query_relevance_weights(np.zeros(D), np.zeros((4, D)))
    assert np.array_equal(w0, np.ones(4, np.float32))


# ---------------------------------------------------------------------------
# dispatcher: threading is execution policy only
# ---------------------------------------------------------------------------


def test_dispatcher_matches_direct_serve(world):
    # max_batch=1 forces singleton compositions, so threaded answers are
    # pinned bit-identical to direct single-request serving (cross-bucket
    # last-bit drift can flip near-tie folds; see dispatcher docstring).
    X, _attrs, E, _cfg, st, svc = world
    reqs = [SelectionRequest(k=K, seed=s) for s in range(5)]
    reqs.append(SelectionRequest(k=K, constraint="knapsack:budget=1.5"))
    dp = Dispatcher(svc, max_batch=1)
    try:
        threaded = dp.map(reqs)
    finally:
        dp.close()
    direct = [svc.serve([r])[0] for r in reqs]
    for t, d_ in zip(threaded, direct):
        assert t.value == d_.value
        assert np.array_equal(t.rows, d_.rows)
    assert svc.queue_depth_max >= 1


def test_batched_serving_deterministic_and_accurate(world):
    # same batch composition twice -> bit-identical; batched answers stay
    # feasible and value-equivalent (rtol ~1e-6) to one-at-a-time answers
    # even when the coreset differs at a near-tie.
    _X, _attrs, _E, _cfg, st, svc = world
    reqs = [SelectionRequest(k=K, seed=s) for s in range(5)]
    b1 = svc.serve(reqs)
    b2 = svc.serve(reqs)
    singles = [svc.serve([r])[0] for r in reqs]
    for r1, r2, s in zip(b1, b2, singles):
        assert r1.value == r2.value
        assert np.array_equal(r1.rows, r2.rows)
        assert r1.feasible and s.feasible
        assert np.isclose(r1.value, s.value, rtol=1e-5, atol=0.0)
    # an opportunistic burst through a wide dispatcher must also stay
    # feasible and value-accurate regardless of how the queue drained
    dp = Dispatcher(svc, max_batch=4)
    try:
        burst = dp.map(reqs)
    finally:
        dp.close()
    for r, s in zip(burst, singles):
        assert r.feasible
        assert np.isclose(r.value, s.value, rtol=1e-5, atol=0.0)


def test_dispatcher_surfaces_errors(world):
    _X, _attrs, _E, _cfg, _st, svc = world
    dp = Dispatcher(svc, max_batch=4)
    try:
        fut = dp.submit(SelectionRequest(k=MU + 3))   # invalid: k ≥ mu
        with pytest.raises(ValueError, match="must satisfy"):
            fut.result(timeout=60)
    finally:
        dp.close()


# ---------------------------------------------------------------------------
# telemetry: serve track + latency histograms; off = zero cost
# ---------------------------------------------------------------------------


def test_serve_telemetry_spans_and_metrics(world, tmp_path):
    X, attrs, E, cfg, _st, _svc = world
    tracer = Tracer()
    s = _fresh_session(X, attrs, cfg)
    svc = SelectionService(s, E, tracer=tracer)
    svc.serve([SelectionRequest(k=K), SelectionRequest(k=K, seed=1)])
    svc.apply_delta(delete_ids=[0])
    svc.query(SelectionRequest(k=K))
    assert any(ev.cat == "serve" for ev in tracer.events)
    snap = tracer.metrics.snapshot()
    assert any(k.startswith("serve_request_latency_s")
               for k in snap["histograms"])
    assert any(k.startswith("serve_requests") for k in snap["counters"])
    # chrome export carries the serve spans
    import json
    out = str(tmp_path / "trace.json")
    tracer.export_chrome_trace(out)
    with open(out) as f:
        trace = json.load(f)
    evs = trace["traceEvents"] if isinstance(trace, dict) else trace
    assert any(ev.get("cat") == "serve" for ev in evs
               if isinstance(ev, dict))
    # stats surface the exact keys the manifest report formats
    stats = svc.serve_stats()
    for key in ("requests", "batches", "latency_p50_ms", "latency_p95_ms",
                "queue_depth_max", "cache_keys", "compiles", "cache_hits",
                "steady_retraces", "deltas", "changed_machines", "rebuilds"):
        assert key in stats, key


def test_telemetry_off_is_default_and_harmless(world):
    _X, _attrs, _E, _cfg, _st, svc = world
    assert svc.tracer is None
    res = svc.query(SelectionRequest(k=K))
    assert res.feasible or res.detail == "unconstrained"


# ---------------------------------------------------------------------------
# bounded caches: LRU eviction on the round-0 solution + compile caches
# ---------------------------------------------------------------------------


def test_sol_cache_lru_bounded_and_correct(world):
    X, attrs, E, cfg, _st, _svc = world
    s = _fresh_session(X, attrs, cfg)
    svc = SelectionService(s, E, sol_cache_capacity=2)
    r3 = svc.query(SelectionRequest(k=3))
    svc.query(SelectionRequest(k=4))
    svc.query(SelectionRequest(k=5))          # capacity 2 → k=3 entry evicted
    stats = svc.serve_stats()
    assert stats["sol_cache_capacity"] == 2
    assert stats["sol_cache_entries"] <= 2
    assert stats["sol_cache_evictions"] >= 1
    # the evicted key re-solves from the session and returns the same bits
    r3b = svc.query(SelectionRequest(k=3))
    np.testing.assert_array_equal(r3.rows, r3b.rows)
    assert r3.value == r3b.value
    # a hit refreshes recency: touch k=3, insert k=6 → k=5 goes, k=3 stays
    svc.query(SelectionRequest(k=3))
    hits = svc.serve_stats()["sol_cache_hits"]
    svc.query(SelectionRequest(k=6))
    svc.query(SelectionRequest(k=3))
    assert svc.serve_stats()["sol_cache_hits"] == hits + 1


def test_compile_cache_lru_bounded_and_correct(world):
    X, attrs, E, cfg, _st, _svc = world
    s = _fresh_session(X, attrs, cfg)
    svc = SelectionService(s, E, compile_cache_capacity=1)
    r3 = svc.query(SelectionRequest(k=3))
    svc.query(SelectionRequest(k=4))          # capacity 1 → k=3 fn evicted
    stats = svc.serve_stats()
    assert stats["cache_capacity"] == 1
    assert stats["cache_keys"] <= 1
    assert stats["cache_evictions"] >= 1
    # rebuilding an evicted entry is a fresh compile, not a steady retrace
    r3b = svc.query(SelectionRequest(k=3))
    np.testing.assert_array_equal(r3.rows, r3b.rows)
    assert svc.serve_stats()["steady_retraces"] == 0


def test_cache_eviction_metrics_registered(world):
    X, attrs, E, cfg, _st, _svc = world
    tracer = Tracer()
    s = _fresh_session(X, attrs, cfg)
    svc = SelectionService(s, E, tracer=tracer,
                           compile_cache_capacity=1, sol_cache_capacity=1)
    for k in (3, 4, 5):
        svc.query(SelectionRequest(k=k))
    snap = tracer.metrics.snapshot()
    evs = {k: v for k, v in snap["counters"].items()
           if "cache_evictions" in k}
    assert any(k.startswith("serve_compile_cache_evictions") and v >= 1
               for k, v in evs.items()), snap["counters"]
    assert any(k.startswith("serve_sol_cache_evictions") and v >= 1
               for k, v in evs.items()), snap["counters"]
    assert any(k.startswith("serve_compile_cache_entries")
               for k in snap["gauges"])
    assert any(k.startswith("serve_sol_cache_entries")
               for k in snap["gauges"])


def test_unbounded_caches_by_default(world):
    _X, _attrs, _E, _cfg, _st, svc = world
    assert svc.cache.capacity is None and svc.sol_cache_capacity is None
    assert svc.serve_stats()["cache_evictions"] == 0
    assert svc.serve_stats()["sol_cache_evictions"] == 0


@pytest.mark.parametrize("weighted", [False, True], ids=["plain", "weighted"])
def test_tail_round_in_turn_equals_vmapped_round(weighted):
    # the tail solves its rounds one machine at a time; each machine's
    # answer must be the one the vmapped round gives it
    from repro.core.distributed import run_round
    from repro.serve.service import _run_round_in_turn
    X, _attrs, E = _data()
    blocks = jnp.asarray(X[:3 * MU].reshape(3, MU, D))
    bmask = jnp.asarray(np.arange(3 * MU).reshape(3, MU) % 5 != 4)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    ew = query_relevance_weights(X[7], E)
    obj = (WeightedExemplarClustering(jnp.asarray(E),
                                      eval_weights=jnp.asarray(ew))
           if weighted else ExemplarClustering(jnp.asarray(E)))
    want = run_round(obj, blocks, bmask, keys, k=K)
    got = _run_round_in_turn(obj, blocks, bmask, keys, k=K)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# round-0 solutions stay on the device
# ---------------------------------------------------------------------------


def _cached_arrays(svc):
    return [x for ent in svc._sol_cache.values() for x in ent["sols"]]


def test_sol_cache_holds_device_arrays(world):
    X, attrs, E, cfg, _st, _svc = world
    s = _fresh_session(X, attrs, cfg)
    svc = SelectionService(s, E)
    req = SelectionRequest(k=K, constraint="knapsack:budget=1.5")
    svc.query(req)                                   # miss
    assert svc.serve_stats()["sol_cache_entries"] == 1
    assert all(isinstance(x, jax.Array) for x in _cached_arrays(svc))
    svc.query(dataclasses.replace(req, seed=7))      # hit
    assert svc.sol_hits == 1
    assert all(isinstance(x, jax.Array) for x in _cached_arrays(svc))
    svc.apply_delta(delete_ids=[int(s.item_ids[0][s.valid[0]][0])])
    svc.query(req)                                   # partial re-solve
    assert svc.partial_resolves == 1
    assert all(isinstance(x, jax.Array) for x in _cached_arrays(svc))


@pytest.mark.parametrize("cons", [None, "knapsack:budget=1.5"])
def test_bucket1_hit_and_miss_equal_offline(world, cons):
    X, attrs, E, cfg, _st, _svc = world
    s = _fresh_session(X, attrs, cfg)
    svc = SelectionService(s, E)
    req = SelectionRequest(k=K, constraint=cons, query=X[21], seed=5)
    ref = offline_solve(s, E, req)
    miss = svc.query(req)
    hit = svc.query(req)
    assert svc.sol_hits == 1
    for got in (miss, hit):
        assert got.value == ref.value
        assert np.array_equal(got.rows, ref.rows)
        assert np.array_equal(got.mask, ref.mask)
        assert got.oracle_calls == ref.oracle_calls


@pytest.mark.parametrize("n", [2, 3, 4])
def test_bucketed_hit_equals_filling_miss(world, n):
    # across buckets only the composition pins bits: the same group served
    # from the cache must give the bits of the group that filled it
    X, attrs, E, cfg, _st, _svc = world
    s = _fresh_session(X, attrs, cfg)
    svc = SelectionService(s, E)
    reqs = [SelectionRequest(k=K, query=X[30 + i], seed=i) for i in range(n)]
    misses = svc.serve(reqs)
    assert svc.sol_hits == 0
    hits = svc.serve(reqs)
    assert svc.sol_hits == n
    for a, b in zip(misses, hits):
        assert a.value == b.value
        assert np.array_equal(a.rows, b.rows)
        assert np.array_equal(a.mask, b.mask)
        assert a.oracle_calls == b.oracle_calls


def test_sol_cache_byte_bound_evicts_lru(world, monkeypatch):
    from repro.serve import service as service_mod
    X, attrs, E, cfg, _st, _svc = world
    s = _fresh_session(X, attrs, cfg)
    reqs = [SelectionRequest(k=K, constraint=f"knapsack:budget={b}")
            for b in (1.1, 1.2, 1.3)]
    probe = SelectionService(s, E)
    assert probe.serve_stats()["sol_cache_bytes"] == 0
    probe.query(reqs[0])
    entry = probe.serve_stats()["sol_cache_bytes"]
    assert entry == sum(x.nbytes for x in _cached_arrays(probe))
    assert entry >= s.Mp * K * (D + 2) * 4
    # half of the reported limit holds two entries, not three
    monkeypatch.setattr(service_mod, "_device_bytes_limit",
                        lambda: 5 * entry)
    tracer = Tracer()
    svc = SelectionService(s, E, tracer=tracer)
    for r in reqs:
        svc.query(r)
    stats = svc.serve_stats()
    assert stats["sol_cache_capacity"] is None
    assert stats["sol_cache_entries"] == 2
    assert stats["sol_cache_evictions"] == 1
    assert stats["sol_cache_bytes"] == 2 * entry
    gauges = tracer.metrics.snapshot()["gauges"]
    assert [v for k, v in gauges.items()
            if k.startswith("serve_sol_cache_bytes")] == [2 * entry]
    # the least recently used entry went: the first request misses again,
    # the last one still hits
    hits = svc.sol_hits
    svc.query(reqs[2])
    assert svc.sol_hits == hits + 1
    svc.query(reqs[0])
    assert svc.sol_hits == hits + 1
    assert svc.serve_stats()["sol_cache_evictions"] == 2
