"""Seconds per job spent building, loading and enqueuing round programs:
the sum of the program's ``wave.dispatch`` and ``round.dispatch`` spans
(``run_round`` builds a fresh ``jax.jit`` per call) inside each
``bench.job`` of the window, averaged over those jobs.  A trace without
the spans reads nothing."""
import bisect

LAYER = "host-to-device staging and round solve"
UNIT = "s"
SOURCE = "program_span"
MOVES = "batch_rows_per_s"
SPANS = ("wave.dispatch", "round.dispatch")


def read(rec):
    tr = rec.get("trace")
    win = tr.spans("bench.window") if tr is not None else []
    if not win:
        return None
    t0, t1 = win[0][0], win[-1][1]
    jobs = [(s, e) for s, e in tr.spans("bench.job") if t0 <= s <= t1]
    spans = sorted(sp for name in SPANS for sp in tr.spans(name))
    if not jobs or not spans:
        return None
    starts = [s for s, _ in spans]
    per_job = []
    for j0, j1 in jobs:
        lo = bisect.bisect_left(starts, j0)
        hi = bisect.bisect_right(starts, j1)
        per_job.append(sum(e - s for s, e in spans[lo:hi] if e <= j1))
    return sum(per_job) / len(per_job)
