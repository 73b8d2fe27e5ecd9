"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell, its config, traffic and metrics are found by name from
``BENCHMARK.json`` (see ``bench/__init__.py``).  With no TPU, or fewer
chips than the cell asks for, it exits 3 and prints no result.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit,
also printed as the last lines of standard error.

``--control program-bf16-wire`` is for the correctness proof only: the
program with its own bf16 wire format for the fp32 ground set switched on.
"""
import time

T_PROCESS0 = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

NO_CHIP_EXIT = 3
WATCHDOG_S = 1_150     # a hung device program dumps stacks and exits 1


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _num(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def per_layer(cell: dict, record: dict) -> dict:
    from bench.lib import manifest
    out = {}
    for m in cell["per_layer"]:
        value = manifest.metric_reader(m["name"]).read(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(record: dict) -> tuple[dict, dict]:
    """``device`` busy/window seconds and the top ops / idle gaps."""
    tr = record["trace"]
    t0, t1 = tr.window()
    busy = tr.busy_s(t0, t1)
    return ({"busy_s": busy, "window_s": t1 - t0},
            {"device_ops": tr.top_ops(t0, t1),
             "idle_gaps": tr.idle_gaps(t0, t1, spans=record.get("spans"))})


def main(argv=None, chips_check=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None,
                    choices=("program-bf16-wire",),
                    help="correctness proof only: switch on the program's "
                         "own bf16 wire format for the ground set")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the trace's xplane file into this directory")
    ap.add_argument("--benchmark", default=None,
                    help="manifest path (default: BENCHMARK.json at the root)")
    args = ap.parse_args(argv)

    from bench.lib import checks, device, manifest, peaks
    bm = manifest.load(args.benchmark)
    cell = manifest.cell(bm, args.workload)
    chips = cell["workload"]["chips"]
    try:
        devs = (chips_check or device.require_chips)(chips)
    except device.NoChip as e:
        log(f"no chip: {e}")
        return NO_CHIP_EXIT
    dev = device.record(devs)
    peaks.peaks(devs[0].device_kind)         # an unknown chip is an error
    cache = enable_compile_cache()
    log(f"workload={args.workload} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} device={dev} compile_cache={cache}")

    from bench.lib.compiles import Compiles
    ctx = types.SimpleNamespace(
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        keep_trace=args.keep_trace, control=args.control,
        config=cell["config"], traffic=cell["traffic"], devs=devs,
        compiles=Compiles(), t_process0=T_PROCESS0, log=log)
    out = manifest.driver(cell["config"]["driver"]).run(ctx)

    correct, compared = checks.judge(out["readings"],
                                     cell["config"]["limits"], out["failed"])
    dev["memory_peak_bytes"] = int(out["memory_peak_bytes"])
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.trace:
        record = out["record"]
        record["device_kind"] = devs[0].device_kind
        result["metrics"] = per_layer(cell, record)
        busy, brk = breakdown(record)
        dev.update(busy)
        result["device"] = dev
        result["breakdown"] = brk
    else:
        result["metrics"] = {
            m["name"]: {"value": out["end_to_end"][m["name"]],
                        "unit": m["unit"]} for m in cell["end_to_end"]}
        result["device"] = dev
    result["checks"] = {k: {"value": _num(v["value"]), "limit": v["limit"]}
                        for k, v in compared.items()}
    checks.report(compared, out["failed"], out["readings"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    sys.exit(main())
