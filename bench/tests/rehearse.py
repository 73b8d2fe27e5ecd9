"""Drive ``bench/run.py`` in this process on the CPU at a small size.

The chip check is replaced by the CPU devices, the CPU gets the v5e's
peaks and a zero memory reading (a CPU reports none), and the persistent
compile cache lives in the test's temporary directory; everything else —
manifest, config, traffic, driver, reference, comparison — runs as on the
chip.  The cell's config is copied with ``overrides`` (its limits kept).
"""
from __future__ import annotations

import contextlib
import io
import json
import os

from bench.lib import device, manifest, peaks

SMALL = {"n": 12_000, "wave_machines": 4}       # d, k, mu, |E| as published


def run_cell(tmp_path, workload: str, seed: int, *args: str,
             overrides: dict | None = None, seconds: float = 0.1,
             trace: int = 0):
    """``(exit code, result line or None, standard error)``."""
    import jax
    bm = manifest.load()
    cell = manifest.cell(bm, workload)
    config = {**cell["config"], **SMALL, **(overrides or {})}
    cfg_path = os.path.join(tmp_path, f"{config['name']}.json")
    with open(cfg_path, "w") as f:
        json.dump(config, f)
    for c in bm["configs"]:
        if c["name"] == config["name"]:
            c["file"] = cfg_path
    bm_path = os.path.join(tmp_path, "BENCHMARK.json")
    with open(bm_path, "w") as f:
        json.dump(bm, f)

    from jax.experimental.compilation_cache import compilation_cache as cc

    from bench import run
    saved = dict(peaks.PEAKS), device.memory_peak, run.enable_compile_cache
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs")
    saved_cfg = {k: getattr(jax.config, k) for k in keys}
    peaks.PEAKS["cpu"] = peaks.PEAKS["TPU v5 lite"]
    device.memory_peak = lambda devs: 0
    # a cache of this run's own, as the chip run keeps one in its checkout;
    # the process's settings come back afterwards
    cache = os.path.join(tmp_path, "jax_cache")
    run.enable_compile_cache = lambda: cache
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    out, err = io.StringIO(), io.StringIO()
    try:
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = run.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace),
                           "--benchmark", bm_path, *args],
                          chips_check=lambda n: jax.devices()[:n])
    finally:
        peaks.PEAKS.clear()
        peaks.PEAKS.update(saved[0])
        device.memory_peak = saved[1]
        run.enable_compile_cache = saved[2]
        for k, v in saved_cfg.items():
            jax.config.update(k, v)
        cc.reset_cache()
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
