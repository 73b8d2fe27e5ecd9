"""Mean seconds of one round-0 wave's host-to-device staging: the
program's ``wave.stage`` span (``stage_wave_inputs`` until the staged
arrays are on the device), over the waves whose staging starts in the
window.  A trace without the span reads nothing."""
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
    stages = [e - s for s, e in tr.spans("wave.stage") if t0 <= s <= t1]
    return sum(stages) / len(stages) if stages else None
