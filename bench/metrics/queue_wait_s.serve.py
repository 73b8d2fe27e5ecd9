"""Median seconds a request waits in the dispatcher's queue: for each
request whose ``serve.submit`` span starts in the window, the start of the
``serve.drain`` span holding its ``serve.reply`` less the end of its
submit.  One FIFO queue and one worker, so the i-th submit pairs with the
i-th reply.  A trace without the spans reads nothing."""
import bisect
import statistics

LAYER = "serving (dispatcher)"
UNIT = "s"
SOURCE = "program_span"
MOVES = "serve_p50_s"


def read(rec):
    tr = rec.get("trace")
    win = tr.spans("bench.window") if tr is not None else []
    if not win:
        return None
    t0, t1 = win[0][0], win[-1][1]
    submits, replies = tr.spans("serve.submit"), tr.spans("serve.reply")
    drains = tr.spans("serve.drain")
    starts = [s for s, _ in drains]
    waits = []
    for (s0, s1), (r0, r1) in zip(submits, replies):
        i = bisect.bisect_right(starts, r0) - 1
        if t0 <= s0 <= t1 and i >= 0 and drains[i][1] >= r1:
            waits.append(drains[i][0] - s1)
    return statistics.median(waits) if waits else None
