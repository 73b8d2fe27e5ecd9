"""95th percentile of the traced window's request latencies, each timed
from when it was due (nearest rank; a failed request counts as missing
every limit).  The tail of 160-250 requests is set by where the schedule's
few bursts fall into the dispatcher's fused groups, so it swings by 10-17 %
from run to run and carries no bound."""
LAYER = "serving (dispatcher)"
UNIT = "s"
SOURCE = "host_clock"
MOVES = "serve_p50_s"


def read(rec):
    s = rec.get("serve")
    if not s or s.get("p95_s") is None:
        return None
    return s["p95_s"]
