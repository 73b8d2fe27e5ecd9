"""Share of the window in which no operation ran on the device, averaged
over the cell's devices, from the profiler trace of the batch window."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "batch_rows_per_s"


def read(rec):
    tr = rec.get("trace")
    if tr is None or not tr.ops or not rec.get("jobs"):
        return None
    t0, t1 = tr.window()
    return 100.0 * (1.0 - tr.busy_s(t0, t1) / (t1 - t0))
