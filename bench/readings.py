"""Readings for the correctness limits: many seeds of one cell in one process.

    python3 bench/readings.py --workload <cell> --seconds <s> \
        --seeds 4100001-4100012 [--control-seeds 4100021-4100023] \
        [--trace-seeds 4100001] --out <dir>

Runs ``bench/run.py``'s ``main`` once per seed (and once per control seed
with ``--control program-bf16-wire``) in this process, so the chip is
reached and the programs loaded once.  Each run's standard output and
error go to ``<dir>/<cell>.<kind>.<seed>.out|.err``; one summary line per
run (its ``correct`` and every number compared) goes to standard output.
Only for setting the limits in ``bench/configs``: the benchmark's own runs
never call it.
"""
import contextlib
import faulthandler
import gc
import json
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                os.pardir)))


def seeds(spec: str) -> list:
    out = []
    for part in filter(None, spec.split(",")):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)

    from bench import run
    traced = set(seeds(args.trace_seeds))
    plan = [("sound", s, []) for s in seeds(args.seeds)]
    plan += [("control", s, ["--control", "program-bf16-wire"])
             for s in seeds(args.control_seeds)]
    bad = 0
    for kind, seed, extra in plan:
        trace = int(seed in traced and kind == "sound")
        stem = os.path.join(args.out, f"{args.workload}.{kind}.{seed}")
        with open(stem + ".out", "w") as fo, open(stem + ".err", "w") as fe:
            with contextlib.redirect_stdout(fo), \
                    contextlib.redirect_stderr(fe):
                try:
                    rc = run.main(["--workload", args.workload,
                                   "--seed", str(seed),
                                   "--seconds", str(args.seconds),
                                   "--trace", str(trace), *extra])
                except Exception as e:                # a crash is a reading
                    print(f"bench: crashed: {e!r}", file=sys.stderr)
                    rc = 1
        gc.collect()
        with open(stem + ".out") as f:
            lines = f.read().strip().splitlines()
        res = json.loads(lines[-1]) if rc == 0 and lines else None
        checks = {k: v["value"] for k, v in (res or {}).get("checks",
                                                             {}).items()}
        with open(stem + ".err") as f:
            for line in f:
                if line.startswith("reading "):       # reported, not compared
                    name, value = line.split()[1:3]
                    checks[name] = float(value)
        bad += res is None
        with open("/proc/self/status") as f:     # host memory left behind
            rss = [ln.split()[1] for ln in f if ln.startswith("VmRSS")]
        print(json.dumps({"kind": kind, "seed": seed, "trace": trace,
                          "rc": rc, "correct": res and res["correct"],
                          "attempted": res and res["attempted"],
                          "checks": checks, "rss_kib": int(rss[0]),
                          "metrics": res and {k: v["value"] for k, v in
                                              res["metrics"].items()}}),
              flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    faulthandler.dump_traceback_later(3_000, exit=True)
    sys.exit(main())
