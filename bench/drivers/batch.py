"""Batch TREE jobs back to back through ``tree_maximize``.

Set-up makes the ground set and the exemplars from ``--seed`` on the host
(the program streams round 0 from host memory), then runs one whole job so
that every program the window runs is compiled or loaded from the cache.
The window runs jobs one at a time, each with a fresh partition seed, and
starts no job once ``--seconds`` have passed.  A job runs from its first
wave gather to a coreset checked feasible and re-scored in fp64.

After the window (and after the peak memory is read and the program's
state dropped) every job of the window is compared with the plain
reference (``bench/lib/tree_ref.py``) on its seed.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench.lib import checks, data as data_lib, device, rescore, stats
from bench.lib import trace as trace_lib, traffic, tree_ref


def _config(c: dict, seed: int):
    from repro.core import TreeConfig
    return TreeConfig(k=c["k"], capacity=c["mu"], algorithm=c["algorithm"],
                      seed=seed, engine=c["engine"],
                      capacity_bytes=(c["wave_machines"] * c["mu"] * c["d"]
                                      * 4))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import (ArraySource, ExemplarClustering, QuantizedSource,
                            make_submod_mesh, tree_maximize)

    c = ctx.config
    n, d, k, mu = c["n"], c["d"], c["k"], c["mu"]
    ground = data_lib.tiny(n, d, ctx.seed)
    E = data_lib.eval_set(ground, c["n_eval"], ctx.seed)
    mesh = make_submod_mesh(ctx.devs)
    obj = ExemplarClustering(jnp.asarray(E))
    seeds = traffic.job_seeds(ctx.traffic, ctx.seed)

    def source():
        if ctx.control == "program-bf16-wire":   # the program's bf16 wire
            return QuantizedSource(ArraySource(ground), store_dtype="bf16")
        return ArraySource(ground)

    def job(seed: int) -> dict:
        with jax.profiler.TraceAnnotation("bench.job"):
            t0 = time.perf_counter()
            try:
                res = tree_maximize(obj, source(), _config(c, seed),
                                    mesh=mesh)
            except Exception as e:                     # counted, not hidden
                ctx.log(f"job seed={seed} failed: {e!r}")
                return {"seed": seed, "t0": t0, "t1": time.perf_counter(),
                        "ok": False}
            t_solved = time.perf_counter()
            mask = np.asarray(res.sel_mask, bool)
            rows = np.asarray(res.sel_rows, np.float32)
            ok = int(mask.sum()) <= k and bool(np.all(np.isfinite(rows)))
            v64 = rescore.exemplar_value(E, rows, mask)
            t1 = time.perf_counter()
        tr = res.engine_stats.traces
        return {"seed": seed, "t0": t0, "t1": t1, "ok": ok,
                "t1_solve": t_solved,
                "value": float(res.value), "value_fp64": v64,
                "mask": mask, "rows": rows,
                "machines": res.ingest.total_machines,
                "waves": [{"gather_s": w.gather_s, "solve_s": w.solve_s,
                           "t_start": w.t_start, "t_end": w.t_end,
                           "machines": w.machines} for w in tr],
                "round_walls": list(res.round_walls)}

    warm = job(next(seeds))
    if not warm["ok"]:
        raise RuntimeError("the set-up job failed")
    setup_s = time.perf_counter() - ctx.t_process0
    ctx.log(f"setup: n={n} d={d} mu={mu} k={k} waves={len(warm['waves'])} "
            f"warm_job_s={warm['t1'] - warm['t0']:.3f} "
            f"setup_s={setup_s:.3f} {ctx.compiles.since((0, 0, 0.0))}")

    snap = ctx.compiles.snap()
    jobs = []
    with trace_lib.profiled(ctx.trace, ctx.keep_trace) as th:
        with jax.profiler.TraceAnnotation("bench.window"):
            tw0 = time.perf_counter()
            while time.perf_counter() - tw0 < ctx.seconds:
                jobs.append(job(next(seeds)))
            tw1 = time.perf_counter()
    in_window = ctx.compiles.since(snap)
    ctx.log(f"window: jobs={len(jobs)} window_s={tw1 - tw0:.3f} "
            f"compiles_in_window={in_window['compiles']} "
            f"cache_loads_in_window={in_window['cache_loads']}")
    peak = device.memory_peak(ctx.devs)
    done = [j for j in jobs if j["ok"]]
    failed = len(jobs) - len(done)
    rate = stats.whole_jobs_rate([n] * len(done), tw0,
                                 [j["t1"] for j in jobs])

    record = {"jobs": done, "window_pc": (tw0, tw1), "config": c,
              "trace": None, "compiles_in_window": in_window["compiles"]}
    if ctx.trace:
        record["trace"] = trace_lib.Trace.load(th["path"])
        trace_lib.cleanup(th)
        record["spans"] = job_spans(done, record["trace"].window()[0] - tw0)

    # --- correctness: the window's jobs against the reference ------------
    del obj, mesh
    gc.collect()
    t_ref = time.perf_counter()
    data_dev = tree_ref.upload(ground, ctx.devs[0])
    Ej = jnp.asarray(E)
    readings = []
    for j in done:
        ref = tree_ref.tree(data_dev, Ej, j["seed"], k=k, mu=mu)
        readings.append(checks.answer_readings(ground, data_dev, E, k, j,
                                               ref))
    ctx.log(f"reference: jobs={len(done)} s={time.perf_counter() - t_ref:.3f}")
    del data_dev
    return {
        "attempted": len(jobs), "failed": failed,
        "end_to_end": {"batch_rows_per_s": rate, "setup_s": setup_s},
        "readings": checks.worst(readings), "memory_peak_bytes": peak,
        "record": record,
    }


def job_spans(jobs: list, offset: float) -> list:
    """Each job's phases as ``(name, t0, t1)`` on the trace clock, from the
    program's wave records: a wave's gather and solve, the rounds after
    round 0, and the benchmark's own check and re-score."""
    out = []
    for j in jobs:
        for w in j["waves"]:
            out.append(("wave.gather", w["t_start"] + offset,
                        w["t_start"] + w["gather_s"] + offset))
            out.append(("wave.solve", w["t_end"] - w["solve_s"] + offset,
                        w["t_end"] + offset))
        r0_end = max(w["t_end"] for w in j["waves"])
        out.append(("rounds.tail", r0_end + offset,
                    r0_end + sum(j["round_walls"][1:]) + offset))
        out.append(("job.check", j["t1_solve"] + offset, j["t1"] + offset))
    return out

