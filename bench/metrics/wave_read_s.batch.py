"""Mean seconds of one round-0 wave's source read: the program's
``wave.read`` span (``source.gather`` or the planner's per-host gathers,
inside ``wave.gather``), over the waves whose read starts in the window.
A trace without the span reads nothing."""
LAYER = "round-0 wave engine"
UNIT = "s"
SOURCE = "program_span"
MOVES = "batch_rows_per_s"


def read(rec):
    tr = rec.get("trace")
    win = tr.spans("bench.window") if tr is not None else []
    if not win:
        return None
    t0, t1 = win[0][0], win[-1][1]
    reads = [e - s for s, e in tr.spans("wave.read") if t0 <= s <= t1]
    return sum(reads) / len(reads) if reads else None
