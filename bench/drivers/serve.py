"""Open-loop selection requests through ``Dispatcher`` -> ``SelectionService``.

Set-up makes the ground set, exemplars and attribute columns (a knapsack
weight and a 3-group id) from ``--seed``, ingests them into one resident
session, and answers requests of every fuse key at every batch bucket the
dispatcher can form (1, 2, 4), so the window compiles nothing; a mix
that repeats a pool of request specs has the pool solved once here, in
groups of at most ``max_batch``, so its round-0 solutions are cached.  The
window sends each request
at its due time whether or not earlier ones have finished, and times it
from when it was due to when its answer is back; a failed request counts
as missing every limit.  After the window (and after the peak memory is
read and the service dropped) a sample of the answered requests, drawn
from the seed, is compared with the plain reference
(``bench/lib/serve_ref.py``).
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from bench.lib import checks, data as data_lib, device, serve_ref, stats
from bench.lib import trace as trace_lib, traffic, tree_ref

BUCKETS = (1, 2, 4)
WAIT_PAST_CLOSE_S = 60.0


def _request(spec: dict, ground: np.ndarray):
    from repro.serve import SelectionRequest
    kw = {"k": spec["k"], "seed": spec["seed"]}
    if "query_row" in spec:
        kw["query"] = ground[spec["query_row"]]
    if "budget" in spec:
        kw["constraint"] = f"knapsack:budget={spec['budget']!r}:col=0"
    return SelectionRequest(**kw)


def setup(ctx) -> dict:
    """Everything up to the window: data, session, service, warm programs."""
    from repro.core import ArraySource, QuantizedSource, TreeConfig
    from repro.serve import SelectionService, ingest

    c = ctx.config
    n, d = c["n"], c["d"]
    ground = data_lib.tiny(n, d, ctx.seed)
    E = data_lib.eval_set(ground, c["n_eval"], ctx.seed)
    rng = np.random.default_rng((ctx.seed, 0xA77))
    attrs = np.stack([rng.uniform(0.2, 1.0, n),
                      rng.integers(0, c["groups"], n)],
                     axis=1).astype(np.float32)
    session_seed = int(rng.integers(1, traffic.SEED_HI))
    src = ArraySource(ground)
    if ctx.control == "program-bf16-wire":          # the program's own path
        src = QuantizedSource(src, store_dtype="bf16")
    st = ingest(src, TreeConfig(k=c["ingest_k"], capacity=c["mu"],
                                seed=session_seed), attrs=attrs)
    svc = SelectionService(st, E,
                           sol_cache_capacity=c["sol_cache_capacity"])
    warm = np.random.default_rng((ctx.seed, 0x3A3))
    spec = ctx.traffic
    for kind in spec["mix"]:
        for b in BUCKETS:                 # every bucket of every fuse key
            reqs = []
            for _ in range(b):
                s = traffic._spec(kind, warm, n)
                s["seed"] = int(warm.integers(0, traffic.SEED_HI))
                reqs.append(_request(s, ground))
            svc.serve(reqs)
    pool = None
    if "pool" in spec:
        pool = traffic.pool_specs(spec, ctx.seed, n)
        step = spec["max_batch"]
        for i in range(0, len(pool), step):
            svc.serve([_request(dict(p, seed=int(warm.integers(
                0, traffic.SEED_HI))), ground) for p in pool[i:i + step]])
    return {"ground": ground, "E": E, "attrs": attrs, "session": st,
            "service": svc, "session_seed": session_seed, "pool": pool}


def window(ctx, state: dict, seconds: float, rate: float | None = None,
           trace: bool = False, seed: int | None = None) -> dict:
    """Send the mix open-loop for ``seconds``; every request's outcome."""
    import jax

    from repro.serve import Dispatcher

    svc, ground = state["service"], state["ground"]
    due = traffic.open_requests(ctx.traffic, ctx.seed if seed is None
                                else seed, len(ground), seconds, rate=rate,
                                pool=state["pool"])
    reqs = [_request(s, ground) for s in due]
    done_at = [None] * len(due)
    results = [None] * len(due)
    lock = threading.Lock()
    counters0 = (svc.sol_hits, svc.batches, svc.requests_served)
    dp = Dispatcher(svc, max_batch=ctx.traffic["max_batch"])

    def finished(i):
        def cb(fut):
            t = time.perf_counter()
            with lock:
                done_at[i] = t
                results[i] = fut.result() if fut.exception() is None else None
        return cb

    late = []
    try:
        with trace_lib.profiled(trace, ctx.keep_trace) as th:
            with jax.profiler.TraceAnnotation("bench.window"):
                t0 = time.perf_counter()
                futs = []
                for i, (s, req) in enumerate(zip(due, reqs)):
                    wait = t0 + s["due"] - time.perf_counter()
                    if wait > 0:
                        time.sleep(wait)
                    late.append(time.perf_counter() - (t0 + s["due"]))
                    with jax.profiler.TraceAnnotation("bench.submit"):
                        f = dp.submit(req)
                    f.add_done_callback(finished(i))
                    futs.append(f)
                rest = t0 + seconds - time.perf_counter()
                if rest > 0:
                    time.sleep(rest)
                t_close = time.perf_counter()
            deadline = t_close + WAIT_PAST_CLOSE_S
            for f in futs:
                try:
                    f.result(timeout=max(0.0, deadline - time.perf_counter()))
                except Exception:                    # counted as failed
                    pass
    finally:
        dp.close()
    counters1 = (svc.sol_hits, svc.batches, svc.requests_served)
    lat, ok_by_close, failed = [], 0, 0
    for i, s in enumerate(due):
        r = results[i]
        good = r is not None and bool(r.feasible)
        if not good:
            failed += 1
            lat.append(float("inf"))
            continue
        lat.append(done_at[i] - (t0 + s["due"]))
        ok_by_close += done_at[i] <= t_close
    served = counters1[2] - counters0[2]
    return {"due": due, "results": results, "latency": lat, "failed": failed,
            "t0": t0, "t_close": t_close, "late": late, "trace": th,
            "sol_hits": counters1[0] - counters0[0],
            "batches": counters1[1] - counters0[1], "served": served,
            "completed_by_close": ok_by_close}


def summary(w: dict, seconds: float) -> dict:
    lat = w["latency"]
    return {"requests": len(lat),
            "serve_p50_s": stats.percentile(lat, 50) if lat else None,
            "serve_p95_s": stats.percentile(lat, 95) if lat else None,
            "serve_req_per_s": w["completed_by_close"] / seconds,
            "offered_per_s": len(lat) / seconds,
            "backlog_at_close": len(lat) - w["completed_by_close"],
            "late_max_s": max(w["late"], default=0.0),
            "failed": w["failed"]}


def run(ctx) -> dict:
    import jax.numpy as jnp

    c = ctx.config
    state = setup(ctx)
    setup_s = time.perf_counter() - ctx.t_process0
    ctx.log(f"setup: n={c['n']} d={c['d']} mu={c['mu']} "
            f"machines={state['session'].Mp} setup_s={setup_s:.3f} "
            f"{ctx.compiles.since((0, 0, 0.0))}")
    snap = ctx.compiles.snap()
    w = window(ctx, state, ctx.seconds, trace=ctx.trace)
    in_window = ctx.compiles.since(snap)
    sm = summary(w, ctx.seconds)
    ctx.log(f"window: {sm} compiles_in_window={in_window['compiles']} "
            f"cache_loads_in_window={in_window['cache_loads']}")
    if not w["latency"]:
        raise RuntimeError("no request was due in the window")
    peak = device.memory_peak(ctx.devs)

    record = {"serve": {"requests": len(w["due"]), "sol_hits": w["sol_hits"],
                        "batches": w["batches"], "served": w["served"],
                        "p95_s": sm["serve_p95_s"]},
              "trace": None, "jobs": None, "config": c}
    if ctx.trace:
        record["trace"] = trace_lib.Trace.load(w["trace"]["path"])
        trace_lib.cleanup(w["trace"])

    # --- correctness: a sample of the answers against the reference ------
    ground, E, attrs = state["ground"], state["E"], state["attrs"]
    session_seed = state["session_seed"]
    del state, w["trace"]
    gc.collect()
    t_ref = time.perf_counter()
    data_dev = tree_ref.upload(ground, ctx.devs[0])
    wcol = jnp.asarray(attrs[:, 0])
    slots0 = serve_ref.session_slots(len(ground), c["mu"], session_seed)
    answered = [i for i, r in enumerate(w["results"]) if r is not None]
    pick = np.random.default_rng((ctx.seed, 0xC4EC))
    sample = sorted(pick.choice(answered, min(c["check_sample"],
                                              len(answered)), replace=False))
    readings = []
    for i in sample:
        s, r = w["due"][i], w["results"][i]
        ew = (serve_ref.query_weights(ground[s["query_row"]], E)
              if "query_row" in s else None)
        ref = serve_ref.solve(data_dev, wcol, E, slots0, k=s["k"],
                              mu=c["mu"], session_seed=session_seed,
                              request_seed=s["seed"], ew=ew,
                              budget=s.get("budget"))
        job = {"mask": r.mask, "rows": r.rows, "value": r.value}
        rd = checks.answer_readings(ground, data_dev, E, s["k"], job, ref,
                                    weights=ew)
        if "budget" in s:
            m = np.asarray(r.mask, bool)
            used = float(np.sum(np.asarray(r.attrs)[m, 0], dtype=np.float64))
            rd["over_budget"] = max(0.0, used - s["budget"] - 1e-6)
        else:
            rd["over_budget"] = 0.0
        readings.append(rd)
    ctx.log(f"reference: requests={len(sample)} "
            f"s={time.perf_counter() - t_ref:.3f}")
    del data_dev
    return {
        "attempted": len(w["due"]), "failed": w["failed"],
        "end_to_end": {"serve_p50_s": sm["serve_p50_s"],
                       "serve_req_per_s": sm["serve_req_per_s"],
                       "setup_s": setup_s},
        "readings": checks.worst(readings), "memory_peak_bytes": peak,
        "record": record,
    }
