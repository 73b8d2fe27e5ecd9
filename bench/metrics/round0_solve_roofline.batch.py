"""Share of the roofline reached by the round-0 greedy solves.

Work: ``bench.lib.work.greedy_round0`` from the shapes of every wave of the
window's jobs (machines, mu, |E|, d, k), whatever implements the solve.
Time: device seconds of the round-0 solve programs, picked out by name
(``ROUND0_PROGRAMS``) and summed inside the waves' solve spans, averaged
over devices; a trace without them reads nothing.
fp32 contractions are charged against the published bf16 peak (no fp32
peak is published), so the share stays under 100%.
"""
from bench.lib import peaks, trace as trace_lib, work

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "batch_rows_per_s"
# the programs that solve a round-0 wave (``run_round``'s jitted body)
ROUND0_PROGRAMS = ("jit__round_local",)


def solve_spans(rec):
    """The waves' solve spans on the trace clock."""
    tr = rec["trace"]
    off = tr.window()[0] - rec["window_pc"][0]
    return [(w["t_end"] - w["solve_s"] + off, w["t_end"] + off)
            for j in rec["jobs"] for w in j["waves"]]


def read(rec):
    tr = rec.get("trace")
    if tr is None or not rec.get("jobs") or not tr.modules:
        return None
    device_s = sum(t for s, e in solve_spans(rec)
                   for name, t in tr.module_s(s, e).items()
                   if trace_lib.program_name(name) in ROUND0_PROGRAMS)
    if device_s <= 0:
        return None
    c = rec["config"]
    machines = sum(w["machines"] for j in rec["jobs"] for w in j["waves"])
    flops, nbytes = work.greedy_round0(machines, c["mu"], c["n_eval"],
                                       c["d"], c["k"])
    pk = peaks.peaks(rec["device_kind"])
    share, _ = work.roofline_share(flops / len(tr.modules),
                                   nbytes / len(tr.modules), device_s,
                                   pk["bf16_flop_s"], pk["hbm_bytes_s"])
    return share
