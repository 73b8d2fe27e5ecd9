"""Share of the time the service works on requests in which device 0 is
idle: device-idle seconds inside the program's ``serve.drain`` spans
(from a request taken off the queue to the last answer of its slice
handed back) over the seconds inside them, both clipped to the window.
A trace without the span, or without device operations, reads nothing."""
LAYER = "serving"
UNIT = "%"
SOURCE = "program_span"
MOVES = "serve_p50_s"


def read(rec):
    tr = rec.get("trace")
    win = tr.spans("bench.window") if tr is not None else []
    if not win or not tr.ops:
        return None
    t0, t1 = win[0][0], win[-1][1]
    drains = [(max(s, t0), min(e, t1)) for s, e in tr.spans("serve.drain")
              if e > t0 and s < t1]
    inside = sum(e - s for s, e in drains)
    if inside <= 0:
        return None
    busy = tr.busy(min(tr.ops), t0, t1)      # merged, sorted intervals
    overlap, j = 0.0, 0
    for s, e in drains:                      # one worker: no overlaps
        while j < len(busy) and busy[j][1] <= s:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < e:
            overlap += min(e, busy[k][1]) - max(s, busy[k][0])
            k += 1
    return 100.0 * (inside - overlap) / inside
