"""Bring-up smoke test: the selection system's main path on a TPU.

    python chip_smoke.py             # one chip: kernels, batch job, serving
    python chip_smoke.py --chips 4 [--wave-machines 800]
                                     # four chips: the batch job on a
                                     # 4-device mesh against one device

One process drives the chip (a child would find it taken).  Phases run in
order and print their lines as they finish; any failed check raises, so the
script exits non-zero without a result.  The last line of standard output
is the contract line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
With no TPU the script exits 2 before any phase runs.

Phases:
  kernels  compiled Pallas ``greedy_select`` / ``threshold_select`` /
           ``exemplar_gains`` on a per-machine block (unconstrained,
           knapsack+partition, int8 operands) against ``impl="ref"``.
  batch    ``tree_maximize`` at the paper's large-scale Tiny Images shape
           (Fig. 2e-f, §4.1): d = 3,072, n = 10⁶, μ = 1,000, k = 50,
           |E| = 512, round 0 streamed from host memory in 2.46 GB waves;
           checked against a NumPy fp64 re-score and centralized greedy.
  serve    a resident ``SelectionService`` session of 327,680 × 3,072
           fp32 rows (4.0 GB) answering the serve smoke's request mix of
           16 requests cold and warm, a dispatcher burst and a ~1 % delta.

A watchdog dumps every thread's stack and exits 1 after ``WATCHDOG_S``
seconds, so a hung device program is located and never holds the chip.
"""
from __future__ import annotations

import argparse
import faulthandler
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

D = 3_072            # Tiny Images row width (paper §4.1)
MU = 1_000           # machine capacity: 0.1% of n = 10⁶
K = 50
N_EVAL = 512         # |E|
WAVE_MACHINES = 200  # machines per round-0 wave: 200·μ·d·4 B = 2.46 GB
SERVE_ROWS = 327_680 # 4.0 GB of fp32 rows resident for serving
SERVE_REQUESTS = 16  # the serve smoke's mix: 4 fuse keys × 4 requests
WATCHDOG_S = 1_140


class Compiles:
    """Counts XLA compiles, their seconds and persistent-cache hits."""

    def __init__(self):
        from jax import monitoring
        self.n = self.hits = 0
        self.secs = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snap(self):
        return self.n, self.hits, self.secs

    def since(self, snap) -> str:
        n, hits, secs = snap
        return (f"compiles={self.n - n} cache_hits={self.hits - hits} "
                f"compile_s={self.secs - secs:.3f}")


def _paths() -> str:
    from repro.kernels import ops
    out = " ".join(f"{k}:{p}={c}" for (k, p), c in sorted(ops.PATHS.items()))
    ops.PATHS.clear()
    return out or "none"


def _mem(dev, key: str = "peak_bytes_in_use") -> int:
    return int(dev.memory_stats()[key])


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def preflight(chips: int):
    import importlib.metadata as md

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"preflight: FAIL backend={backend}, a TPU is required",
              file=sys.stderr)
        sys.exit(2)
    devs = jax.devices()
    if len(devs) < chips:
        print(f"preflight: FAIL {len(devs)} devices, --chips {chips}",
              file=sys.stderr)
        sys.exit(2)
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(f"preflight: platform={devs[0].platform} "
          f"device_kind={devs[0].device_kind} count={len(devs)} "
          f"jax={jax.__version__} jaxlib={md.version('jaxlib')} "
          f"libtpu={md.version('libtpu')} compile_cache={cache}",
          flush=True)
    return devs


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def kernel_phase(rng, comp: Compiles) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ArraySource, QuantizedSource, check_feasible
    from repro.core.constraints import from_spec
    from repro.data import datasets
    from repro.kernels import ops

    snap = comp.snap()
    n, m, d, k = MU, N_EVAL, 64, K     # one machine's block of a d=64 tree
    assert ops._fits_vmem(n, m, d, 256, cols=3), "block must route to Pallas"

    rows = datasets.tiny(n=n + m, d=d, seed=int(rng.integers(1 << 30)))
    X, E = rows[:n], rows[n:]
    cm0 = np.sum(E * E, axis=1)
    mask = np.ones((n,), bool)
    attrs = np.stack([rng.uniform(0.2, 1.0, n),
                      rng.integers(0, 3, n)], axis=1).astype(np.float32)
    budget, caps = 6.0, (6, 6, 6)
    cons = from_spec("intersection:knapsack:budget=6.0:col=0"
                     "+partition:caps=6,6,6:col=1")
    qsrc = QuantizedSource(ArraySource(X), store_dtype="int8")
    idx = np.arange(n)
    Xq, qm = qsrc.gather(idx), qsrc.gather_qmeta(idx)
    ops_in = dict(w=attrs[:, 0], g=attrs[:, 1].astype(np.int32),
                  xs=qm[:, 0], xz=qm[:, 1])
    g0 = np.asarray(ops.exemplar_gains(X, E, cm0, impl="ref"))
    tau = float(0.3 * g0.max())

    def feasible(sel_mask, constrained):
        sel_mask = np.asarray(sel_mask, bool)
        ok = sel_mask.sum() <= k
        if constrained:
            ok &= check_feasible(cons, attrs, sel_mask)[0]
        return bool(ok)

    def run(name, fn, args, result, constrained=False):
        auto = jax.jit(lambda *a: fn(*a, impl="auto")).lower(*args).compile()
        assert "tpu_custom_call" in auto.as_text(), f"{name}: no Pallas"
        got = result(auto(*args))
        want = result(jax.jit(lambda *a: fn(*a, impl="ref"))(*args))
        if isinstance(got, tuple):                 # (value, selected mask)
            rel = _rel(got[0], want[0])
            fz = (feasible(got[1], constrained),
                  feasible(want[1], constrained))
            print(f"kernels: {name} pallas={got[0]:.7f} ref={want[0]:.7f} "
                  f"rel={rel:.3e} feasible={fz[0]}/{fz[1]} "
                  f"selected={int(np.sum(got[1]))}/{int(np.sum(want[1]))}",
                  flush=True)
            assert all(fz), (name, fz)
        else:                                      # gains vector
            rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
            print(f"kernels: {name} max|pallas-ref|/max|ref|={rel:.3e}",
                  flush=True)
        assert rel <= 1e-5, (name, rel)

    def sel_value(out):
        sel, cm = (np.asarray(o) for o in out)
        chosen = np.zeros((n,), bool)
        chosen[sel[sel >= 0]] = True
        return float(np.mean(cm0) - np.mean(cm)), chosen

    def acc_value(out):
        acc, cm = (np.asarray(o) for o in out)
        return float(np.mean(cm0) - np.mean(cm)), acc

    greedy = {
        "plain": (lambda X, impl: ops.greedy_select(
            X, E, cm0, mask, k, impl=impl), (X,)),
        "knapsack+partition": (lambda X, w, g, impl: ops.greedy_select(
            X, E, cm0, mask, k, impl=impl, weights=w, budget=budget,
            group_ids=g, caps=caps), (X, ops_in["w"], ops_in["g"])),
        "int8": (lambda X, xs, xz, impl: ops.greedy_select(
            X, E, cm0, mask, k, impl=impl, x_scale=xs, x_zp=xz),
            (Xq, ops_in["xs"], ops_in["xz"])),
    }
    for name, (fn, args) in greedy.items():
        run(f"greedy_select[{name}]", fn, args, sel_value,
            constrained=name == "knapsack+partition")
    threshold = {
        "plain": (lambda X, impl: ops.threshold_select(
            X, E, cm0, mask, tau, k, impl=impl), (X,)),
        "knapsack+partition": (lambda X, w, g, impl: ops.threshold_select(
            X, E, cm0, mask, tau, k, impl=impl, weights=w, budget=budget,
            group_ids=g, caps=caps), (X, ops_in["w"], ops_in["g"])),
        "int8": (lambda X, xs, xz, impl: ops.threshold_select(
            X, E, cm0, mask, tau, k, impl=impl, x_scale=xs, x_zp=xz),
            (Xq, ops_in["xs"], ops_in["xz"])),
    }
    for name, (fn, args) in threshold.items():
        run(f"threshold_select[{name}]", fn, args, acc_value,
            constrained=name == "knapsack+partition")
    ew = rng.uniform(0.0, 2.0, m).astype(np.float32)
    gains = {
        "plain": (lambda X, impl: ops.exemplar_gains(X, E, cm0, impl=impl),
                  (X,)),
        "int8": (lambda X, xs, xz, impl: ops.exemplar_gains(
            X, E, cm0, impl=impl, x_scale=xs, x_zp=xz),
            (Xq, ops_in["xs"], ops_in["xz"])),
        "weighted": (lambda X, w, impl: ops.exemplar_gains(
            X, E, cm0, impl=impl, eval_weights=w), (X, ew)),
        "d=3072": (lambda X, impl: ops.exemplar_gains(
            X, jnp.asarray(np.pad(E, ((0, 0), (0, D - d)))), cm0, impl=impl),
            (np.pad(X, ((0, 0), (0, D - d))),)),
    }
    for name, (fn, args) in gains.items():
        run(f"exemplar_gains[{name}]", fn, args, np.asarray)
    print(f"kernels: paths {_paths()} {comp.since(snap)}", flush=True)


# ---------------------------------------------------------------------------
# batch job
# ---------------------------------------------------------------------------


def batch_data(n: int, seed: int):
    import numpy as np

    from repro.data import datasets
    t0 = time.perf_counter()
    data = datasets.tiny(n=n, d=D, seed=seed)
    E = data[np.random.default_rng(seed).choice(n, N_EVAL, replace=False)]
    print(f"batch: data n={n} d={D} bytes={data.nbytes} "
          f"made_s={time.perf_counter() - t0:.3f}", flush=True)
    return data, E


def tree_run(data, E, mesh, seed: int, comp: Compiles, label: str,
             wave_machines: int = WAVE_MACHINES):
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (ArraySource, ExemplarClustering, TreeConfig,
                            check_feasible, tree_maximize)
    from repro.launch.submod import _np_exemplar_value

    snap = comp.snap()
    obj = ExemplarClustering(jnp.asarray(E))
    cfg = TreeConfig(k=K, capacity=MU, algorithm="greedy", seed=seed,
                     engine="pipelined",
                     capacity_bytes=wave_machines * MU * D * 4)
    t0 = time.perf_counter()
    # tree_maximize hands back host arrays: the wall below ends after the
    # device finished (every round also pulls its best value to the host)
    res = tree_maximize(obj, ArraySource(data), cfg, mesh=mesh)
    wall = time.perf_counter() - t0
    ok, detail = check_feasible(None, None, res.sel_mask)
    ok = ok and int(np.sum(res.sel_mask)) <= K
    npv = _np_exemplar_value(E, res.sel_rows, res.sel_mask)
    rel = _rel(res.value, npv)
    print(f"batch[{label}]: devices={mesh.devices.size} rounds={res.rounds} "
          f"machines/round={res.machines_per_round} "
          f"waves={res.ingest.waves} W={res.ingest.wave_machines} "
          f"peak_wave_bytes={res.ingest.peak_wave_bytes} "
          f"round0_wall_s={res.round_walls[0]:.3f} total_wall_s={wall:.3f}",
          flush=True)
    print(f"batch[{label}]: value={res.value:.7f} numpy_fp64={npv:.7f} "
          f"rel={rel:.3e} feasible={ok} ({detail}) "
          f"selected={int(np.sum(res.sel_mask))} {comp.since(snap)} "
          f"paths {_paths()}", flush=True)
    assert ok, detail
    assert rel <= 1e-4, (res.value, npv)
    return res


def batch_phase(args, devs, comp: Compiles) -> None:
    import jax.numpy as jnp

    from repro.core import (ArraySource, ExemplarClustering,
                            centralized_greedy, make_submod_mesh)

    n = args.n
    if n != 1_000_000:
        print(f"batch: n cut from 1000000 to {n}", flush=True)
    data, E = batch_data(n, args.seed)
    res = tree_run(data, E, make_submod_mesh(devs[:1]), args.seed, comp,
                   "1-chip")
    print(f"batch: peak_bytes_in_use={_mem(devs[0])}", flush=True)
    snap = comp.snap()
    t0 = time.perf_counter()
    cg = centralized_greedy(ExemplarClustering(jnp.asarray(E)),
                            ArraySource(data), K, chunk_rows=65_536)
    cval = float(cg.value)
    ratio = res.value / cval
    print(f"batch: centralized greedy [streamed] f={cval:.7f} "
          f"TREE/centralized={ratio:.4f} wall_s={time.perf_counter() - t0:.3f}"
          f" {comp.since(snap)} paths {_paths()}", flush=True)
    assert ratio >= 0.95, ratio


def mesh_phase(args, devs, comp: Compiles) -> None:
    """Four devices against one, same config, same process: the four
    split each wave of ``--wave-machines``; one device takes waves of one
    device's share (the answer does not depend on the wave width)."""
    from repro.core import make_submod_mesh

    n = args.n
    if n != 1_000_000:
        print(f"batch: n cut from 1000000 to {n}", flush=True)
    data, E = batch_data(n, args.seed)
    W = args.wave_machines
    r4 = tree_run(data, E, make_submod_mesh(devs[:4]), args.seed, comp,
                  "4-chip", W)
    peaks = [_mem(dv) for dv in devs[:4]]
    print(f"batch[4-chip]: peak_bytes_in_use per device={peaks}", flush=True)
    r1 = tree_run(data, E, make_submod_mesh(devs[:1]), args.seed, comp,
                  "1-chip", W // 4)
    rel = _rel(r4.value, r1.value)
    print(f"batch: 4-chip vs 1-chip value rel={rel:.3e}", flush=True)
    assert rel <= 1e-5, (r4.value, r1.value)
    share = W // 4 * MU * D * 4                # one device's part of a wave
    assert min(peaks) >= share, ("machine blocks did not spread", peaks)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def serve_phase(args, devs, comp: Compiles) -> None:
    import numpy as np

    from repro.core import TreeConfig
    from repro.data import datasets
    from repro.launch.submod import serve_exercise

    snap = comp.snap()
    rng = np.random.default_rng(args.seed + 1)
    data = datasets.tiny(n=SERVE_ROWS, d=D, seed=args.seed + 1)
    E = data[rng.choice(SERVE_ROWS, N_EVAL, replace=False)]
    cfg = TreeConfig(k=K, capacity=MU, algorithm="greedy", seed=args.seed)
    ex = serve_exercise(data, E, cfg, n_requests=SERVE_REQUESTS, rng=rng,
                        log=lambda msg: print(f"serve: {msg} "
                                              f"{comp.since(snap)}",
                                              flush=True))
    svc, st = ex.service, ex.session
    stats = svc.serve_stats()
    in_use = _mem(devs[0], "bytes_in_use")
    print(f"serve: rows={SERVE_ROWS} d={D} machines={st.Mp} mu={st.mu} "
          f"resident_bytes={st.blocks.nbytes} device_bytes_in_use={in_use} "
          f"peak_bytes_in_use={_mem(devs[0])}", flush=True)
    print(f"serve: ingest_s={ex.ingest_s:.3f} cold_s={ex.cold_s:.3f} "
          f"warm_s={ex.warm_s:.3f} burst_s={ex.burst_s:.3f} "
          f"delta_s={ex.delta_s:.3f} requests={stats['requests']} "
          f"batches={stats['batches']} "
          f"steady_retraces={svc.cache.steady_retraces()} "
          f"feasible={all(r.feasible for r in ex.cold)}", flush=True)
    print(f"serve: delta inserted={ex.delta.inserted} "
          f"deleted={ex.delta.deleted} "
          f"changed_machines={len(ex.delta.changed_machines)}/{st.Mp} "
          f"recheck={ex.recheck['status']} "
          f"rel_gap={ex.recheck['rel_gap']:.3e} {comp.since(snap)} "
          f"paths {_paths()}", flush=True)
    assert ex.recheck["status"] == "PASS", ex.recheck


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the batch job, on a 4-device mesh "
                         "and on one device")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--wave-machines", type=int, default=WAVE_MACHINES,
                    help="with --chips 4: machines per round-0 wave; the "
                         "four chips split each wave (800: 200 machines, "
                         "2.46 GB, a chip) and the one-chip run takes "
                         "waves of one chip's share")
    ap.add_argument("--n", type=int, default=1_000_000,
                    help="batch ground-set rows (cut only if host memory "
                         "or time force it)")
    args = ap.parse_args()

    devs = preflight(args.chips)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    import jax
    import numpy as np

    comp = Compiles()
    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(args, devs, comp)
    else:
        kernel_phase(np.random.default_rng(args.seed), comp)
        batch_phase(args, devs, comp)
        gc.collect()
        serve_phase(args, devs, comp)
    print(f"done: wall_s={time.perf_counter() - t0:.3f} "
          f"compiles={comp.n} cache_hits={comp.hits} "
          f"compile_s={comp.secs:.3f}", flush=True)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
