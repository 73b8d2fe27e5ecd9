"""Mean seconds of one chip's share of a round-0 wave upload: the
program's ``wave.put`` span (inside ``wave.stage``, one per device a
wave), over the spans that start in the window.  A trace without the
span reads nothing."""
LAYER = "host-to-device staging and round solve"
UNIT = "s"
SOURCE = "program_span"
MOVES = "batch_rows_per_s"


def read(rec):
    tr = rec.get("trace")
    win = tr.spans("bench.window") if tr is not None else []
    if not win:
        return None
    t0, t1 = win[0][0], win[-1][1]
    puts = [e - s for s, e in tr.spans("wave.put") if t0 <= s <= t1]
    return sum(puts) / len(puts) if puts else None
