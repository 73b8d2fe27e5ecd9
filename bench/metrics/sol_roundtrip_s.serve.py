"""Seconds per fused group that round-0 solutions spend crossing the
host: the program's ``serve.round0.fetch`` (pull to NumPy),
``serve.tail.stack`` (host stacking) and ``serve.tail.upload`` spans
inside each ``serve.group`` that starts in the window, averaged over
those groups.  A trace without the spans reads nothing."""
import bisect

LAYER = "serving"
UNIT = "s"
SOURCE = "program_span"
MOVES = "serve_p50_s"
SPANS = ("serve.round0.fetch", "serve.tail.stack", "serve.tail.upload")


def read(rec):
    tr = rec.get("trace")
    win = tr.spans("bench.window") if tr is not None else []
    if not win:
        return None
    t0, t1 = win[0][0], win[-1][1]
    groups = [(s, e) for s, e in tr.spans("serve.group") if t0 <= s <= t1]
    spans = [sp for name in SPANS for sp in tr.spans(name)]
    if not groups or not spans:
        return None
    starts = [s for s, _ in groups]
    total = 0.0
    for s, e in spans:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= groups[i][1]:
            total += e - s
    return total / len(groups)
