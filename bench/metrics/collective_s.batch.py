"""Device seconds of collective operations (all-gather, all-reduce,
collective-permute, ...) per job: ``Trace.collective_s`` inside each
``bench.job`` of the window, averaged over the trace's devices and then
over those jobs.  A trace with no device operations reads nothing."""
LAYER = "mesh exchange"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "batch_rows_per_s"


def read(rec):
    tr = rec.get("trace")
    win = tr.spans("bench.window") if tr is not None else []
    if not win or not tr.ops:
        return None
    t0, t1 = win[0][0], win[-1][1]
    jobs = [(s, e) for s, e in tr.spans("bench.job") if t0 <= s <= t1]
    if not jobs:
        return None
    return sum(tr.collective_s(s, e) for s, e in jobs) / len(jobs)
