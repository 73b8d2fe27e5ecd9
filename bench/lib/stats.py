"""Rates, percentiles and spreads, computed one way for every cell."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of every sample; a failed
    sample enters as +inf, so it counts as missing any limit."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def whole_jobs_rate(units_per_job, t_window0: float, job_ends) -> float:
    """Units of every whole job over the time from the window's start to the
    end of the last job (jobs start only while the window is open)."""
    if not job_ends:
        raise ValueError("no job finished")
    return sum(units_per_job) / (max(job_ends) - t_window0)
