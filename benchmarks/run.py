# One function per paper table/figure. Prints ``name,...`` CSV rows.
"""Benchmark driver:  PYTHONPATH=src python -m benchmarks.run [--full]

  table1   — capacity / rounds / oracle-call accounting   (paper Table 1)
  table3   — relative error vs centralized, fixed μ       (paper Table 3)
  fig2     — approximation ratio vs capacity sweep        (paper Fig 2 a-d)
  fig2ef   — large-scale, stochastic subprocedure         (paper Fig 2 e-f)
  ft       — failure/straggler degradation                (beyond paper)
  kernels  — kernel micro-benchmarks + traffic models
  tree     — streaming-ingestion scaling sweep            (PR 2)
  constrained — hereditary-constraint streaming sweep     (PR 3)
  engine   — async engine overlap + multi-host ingestion  (PR 4)
  adaptive — wave autoscaler + async checkpoint writer    (PR 5)
  faults   — fault supervision: retries/eviction/drops    (PR 6)
  bytes_lean — quantized wave streaming, dtype ladder     (PR 7)
  telemetry — tracer overhead: off vs instrumented run    (PR 8)
  serve    — selection-service latency + delta vs rebuild (PR 9)
  adaptivity — threshold-batch solve depth vs greedy      (PR 10)

Suites that return a dict contribute to the cross-PR perf trajectory
record: ``tree`` writes ``BENCH_PR2.json``, ``constrained`` writes
``BENCH_PR3.json``, ``engine`` writes ``BENCH_PR4.json``, ``adaptive``
writes ``BENCH_PR5.json``, ``faults`` writes ``BENCH_PR6.json``,
``bytes_lean`` writes ``BENCH_PR7.json``, ``telemetry`` writes
``BENCH_PR8.json``, ``serve`` writes ``BENCH_PR9.json``, ``adaptivity``
writes ``BENCH_PR10.json``; everything else goes to ``BENCH_PR1.json``
(repo root).  ``--only bytes_lean`` is the PR 7 refresh.
"""
import argparse
import json
import os
import sys
import time

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
BENCH_JSON = os.path.join(_ROOT, "BENCH_PR1.json")
BENCH_PR2_JSON = os.path.join(_ROOT, "BENCH_PR2.json")
BENCH_PR3_JSON = os.path.join(_ROOT, "BENCH_PR3.json")
BENCH_PR4_JSON = os.path.join(_ROOT, "BENCH_PR4.json")
BENCH_PR5_JSON = os.path.join(_ROOT, "BENCH_PR5.json")
BENCH_PR6_JSON = os.path.join(_ROOT, "BENCH_PR6.json")
BENCH_PR7_JSON = os.path.join(_ROOT, "BENCH_PR7.json")
BENCH_PR8_JSON = os.path.join(_ROOT, "BENCH_PR8.json")
BENCH_PR9_JSON = os.path.join(_ROOT, "BENCH_PR9.json")
BENCH_PR10_JSON = os.path.join(_ROOT, "BENCH_PR10.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow on CPU)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    quick = not args.full
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (adaptive_depth, adaptive_engine, bytes_lean,
                            constrained_tree, engine_overlap, fault_engine,
                            fault_tolerance_bench,
                            fig2_capacity, fig2_large_scale, kernel_bench,
                            serve_latency, table1_complexity,
                            table3_relative_error, telemetry_overhead,
                            tree_scaling)
    suites = {
        "table1": table1_complexity.run,
        "table3": table3_relative_error.run,
        "fig2": fig2_capacity.run,
        "fig2ef": fig2_large_scale.run,
        "ft": fault_tolerance_bench.run,
        "kernels": kernel_bench.run,
        "tree": tree_scaling.run,
        "constrained": constrained_tree.run,
        "engine": engine_overlap.run,
        "adaptive": adaptive_engine.run,
        "faults": fault_engine.run,
        "bytes_lean": bytes_lean.run,
        "telemetry": telemetry_overhead.run,
        "serve": serve_latency.run,
        "adaptivity": adaptive_depth.run,
    }
    # suite → (trajectory file, PR tag); default is the PR-1 record
    targets = {"tree": (BENCH_PR2_JSON, 2),
               "constrained": (BENCH_PR3_JSON, 3),
               "engine": (BENCH_PR4_JSON, 4),
               "adaptive": (BENCH_PR5_JSON, 5),
               "faults": (BENCH_PR6_JSON, 6),
               "bytes_lean": (BENCH_PR7_JSON, 7),
               "telemetry": (BENCH_PR8_JSON, 8),
               "serve": (BENCH_PR9_JSON, 9),
               "adaptivity": (BENCH_PR10_JSON, 10)}
    measured: dict[str, dict] = {}
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        t0 = time.perf_counter()
        print(f"# --- {name} ---", flush=True)
        out = fn(quick=quick)
        if isinstance(out, dict):
            measured[name] = out
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s", flush=True)

    by_file: dict[str, tuple[int, dict]] = {}
    for name, out in measured.items():
        path, pr = targets.get(name, (BENCH_JSON, 1))
        by_file.setdefault(path, (pr, {}))[1][name] = out

    for path, (pr, suites_out) in by_file.items():
        # never let a quick run clobber a recorded full-size trajectory point
        if quick and os.path.exists(path):
            try:
                with open(path) as f:
                    if json.load(f).get("quick") is False:
                        print(f"# kept full-size {os.path.normpath(path)}"
                              " (quick run does not overwrite)", flush=True)
                        continue
            except (OSError, ValueError):
                pass
        import jax
        record = {"pr": pr, "quick": quick,
                  "backend": jax.default_backend(), "suites": suites_out}
        with open(path, "w") as f:
            json.dump(record, f, indent=2)
        print(f"# wrote {os.path.normpath(path)}", flush=True)


if __name__ == '__main__':
    main()
