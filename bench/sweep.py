"""Find the highest rate a serving cell sustains: one set-up, many windows.

    python3 bench/sweep.py --workload serve-fresh --seed N --seconds 20 \
        --rates 1,2,3,4

Runs the cell's set-up once, then one open-loop window per rate (the
traffic file's mix at that rate instead of its fixed one) and prints per
rate the offered and completed rates, p50/p95, the backlog left at the
window's close and how late the generator ran.  The knee is the highest
rate at which completed keeps up with offered and no backlog is left; the
cell's traffic file then fixes its rate from it.  Used once, to set the
rates; the benchmark's runs never search.
"""
import time

T_PROCESS0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=INT",
                    help="override a whole-number config key for this sweep "
                         "(the rows of a session being sized)")
    args = ap.parse_args()

    from bench import run
    from bench.lib import device, manifest
    from bench.lib.compiles import Compiles
    cell = manifest.cell(manifest.load(), args.workload)
    for kv in args.set:
        key, value = kv.split("=", 1)
        cell["config"][key] = int(value)
    try:
        devs = device.require_chips(cell["workload"]["chips"])
    except device.NoChip as e:
        run.log(f"no chip: {e}")
        return run.NO_CHIP_EXIT
    run.enable_compile_cache()
    drv = manifest.driver(cell["config"]["driver"])
    ctx = types.SimpleNamespace(
        seed=args.seed, seconds=args.seconds, trace=False, keep_trace=None,
        control=None, config=cell["config"], traffic=cell["traffic"],
        devs=devs, compiles=Compiles(), t_process0=T_PROCESS0, log=run.log)
    state = drv.setup(ctx)
    run.log(f"sweep setup_s={time.perf_counter() - T_PROCESS0:.3f}")
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        snap = ctx.compiles.snap()
        # a fresh request stream per rate: no window reuses another's
        w = drv.window(ctx, state, args.seconds, rate=rate,
                       seed=args.seed + 7919 * (i + 1))
        sm = drv.summary(w, args.seconds)
        sm.update(rate=rate, batches=w["batches"], served=w["served"],
                  **ctx.compiles.since(snap))
        print(json.dumps(sm), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
