"""Mean seconds of one round-0 wave's device side (``WaveTrace.solve_s``):
host-to-device staging, dispatch and fold, ended by a block on the result."""
LAYER = "host-to-device staging and round solve"
UNIT = "s"
SOURCE = "program_span"
MOVES = "batch_rows_per_s"


def read(rec):
    waves = [w["solve_s"] for j in rec.get("jobs", []) for w in j["waves"]]
    return sum(waves) / len(waves) if waves else None
