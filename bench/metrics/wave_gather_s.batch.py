"""Mean host seconds to gather one round-0 wave (``WaveTrace.gather_s``):
source reads and block assembly, over every wave of the window's jobs."""
LAYER = "round-0 wave engine"
UNIT = "s"
SOURCE = "program_span"
MOVES = "batch_rows_per_s"


def read(rec):
    waves = [w["gather_s"] for j in rec.get("jobs", []) for w in j["waves"]]
    return sum(waves) / len(waves) if waves else None
