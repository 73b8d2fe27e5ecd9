"""Mean seconds of one fused group's tail: the program's ``serve.tail``
span (fold and rounds t >= 1, ended by a block on its outputs), over the
groups whose tail starts in the window.  A trace without the span reads
nothing."""
LAYER = "serving"
UNIT = "s"
SOURCE = "program_span"
MOVES = "serve_p50_s"


def read(rec):
    tr = rec.get("trace")
    win = tr.spans("bench.window") if tr is not None else []
    if not win:
        return None
    t0, t1 = win[0][0], win[-1][1]
    tails = [e - s for s, e in tr.spans("serve.tail") if t0 <= s <= t1]
    return sum(tails) / len(tails) if tails else None
