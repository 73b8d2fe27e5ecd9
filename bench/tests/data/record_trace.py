"""Record the small TPU trace that ``test_bench_trace.py`` reduces.

    python3 bench/tests/data/record_trace.py OUT_DIR

Runs a few jitted matmuls on one chip inside the host spans the harness
writes (``bench.window``, ``bench.job``) with a host-side sleep between
them, so the trace holds device ops, an idle gap under ``bench.job`` and
one under ``bench.window``.  Copies the ``.xplane.pb`` to OUT_DIR and
prints what the reduction reads from it.
"""
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                *[os.pardir] * 3)))


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    from bench.lib import trace

    f = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jnp.ones((2048, 2048), jnp.float32) / 2048
    f(x).block_until_ready()                          # compile outside
    with trace.profiled(True) as th:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.job"):
                    y = x
                    for _ in range(5):
                        y = f(y)
                    y.block_until_ready()
                    time.sleep(0.05)                  # idle inside a job
                time.sleep(0.02)                      # idle between jobs
    os.makedirs(out, exist_ok=True)
    dst = os.path.join(out, "tpu_small.xplane.pb")
    shutil.copy(th["path"], dst)
    trace.cleanup(th)
    tr = trace.Trace.load(dst)
    t0, t1 = tr.window()
    print("planes ops:", {d: len(v) for d, v in tr.ops.items()},
          "modules:", {d: len(v) for d, v in tr.modules.items()})
    print("window_s", t1 - t0, "busy_s", tr.busy_s(t0, t1))
    print("top_ops", tr.top_ops(t0, t1, 5))
    print("modules", tr.module_s(t0, t1))
    print("idle_gaps", tr.idle_gaps(t0, t1))
    print("bytes", os.path.getsize(dst))


if __name__ == "__main__":
    main(sys.argv[1])
