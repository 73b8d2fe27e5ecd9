"""Mean seconds of one round-0 launch for cache misses: the program's
``serve.round0`` span (the fused round-0 program over every resident
machine, ended by a block on its outputs), over the launches that start
in the window.  A trace without the span reads nothing."""
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
    runs = [e - s for s, e in tr.spans("serve.round0") if t0 <= s <= t1]
    return sum(runs) / len(runs) if runs else None
