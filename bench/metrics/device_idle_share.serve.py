"""Share of the serving window in which no operation ran on the device,
from the profiler trace."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_p50_s"


def read(rec):
    tr = rec.get("trace")
    if tr is None or not tr.ops or not rec.get("serve"):
        return None
    t0, t1 = tr.window()
    return 100.0 * (1.0 - tr.busy_s(t0, t1) / (t1 - t0))
