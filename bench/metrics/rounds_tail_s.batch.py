"""Seconds per job spent after round 0: the rounds t >= 1 and their folds
(sum of ``TreeResult.round_walls[1:]``), averaged over the window's jobs."""
LAYER = "rounds t >= 1 and fold"
UNIT = "s"
SOURCE = "program_span"
MOVES = "batch_rows_per_s"


def read(rec):
    jobs = rec.get("jobs", [])
    if not jobs:
        return None
    return sum(sum(j["round_walls"][1:]) for j in jobs) / len(jobs)
