"""Rehearse ``batch-tiny-mesh4`` on four CPU devices, in a process of its
own (the test process keeps one device).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python -m bench.tests.rehearse_mesh4 <tmp dir> <out.json>

Runs the cell at a small size (``SMALL``: n = 12,000, W = 8, so each
device solves two machines a wave) sound, under each planted fault, and
traced, all in one process so programs compile once; writes every result
line (and, for the traced run, what its kept trace holds) to ``out.json``.

The CPU runs each device's programs on the client's worker threads, not
on device planes, so for the traced run ``cpu_trace`` presents those
thread lines as device planes: the device readers then read the
collectives the four devices really ran.
"""
from __future__ import annotations

import json
import os
import sys
import types

SMALL = {"n": 12_000, "wave_machines": 8}
SEED = 2_147_483_671          # above 2**31: seeds exceed 32 signed bits
CPU_OPS_LINE = "tf_XLA"        # prefix of the CPU client's worker lines


def cpu_trace(path: str):
    """A ``Trace`` of a CPU profile, each worker line as a device plane."""
    import jax

    from bench.lib import trace
    planes = []
    for pl in jax.profiler.ProfileData.from_file(path).planes:
        lines = list(pl.lines)
        if not pl.name.startswith("/host:"):
            planes.append(pl)
            continue
        planes.append(types.SimpleNamespace(name=pl.name, lines=[
            ln for ln in lines if not ln.name.startswith(CPU_OPS_LINE)]))
        planes += [types.SimpleNamespace(
            name=f"/device:TPU:{i}", lines=[types.SimpleNamespace(
                name=trace.OPS_LINE, events=list(ln.events))])
            for i, ln in enumerate(l for l in lines
                                   if l.name.startswith(CPU_OPS_LINE))]
    return trace.Trace(planes)


def fault_exchange_left_out(monkeypatch):
    """One device's share of the union A_1 is dropped on its way from
    round 0 to the round-1 repartition: the rows the last device of the
    mesh holds are masked out."""
    from repro.core import tree
    real = tree._stream_round0

    def dropped(*a, **kw):
        out = list(real(*a, **kw))
        rows_in, mask_in = out[6], out[7]
        last = max(rows_in.addressable_shards,
                   key=lambda s: s.index[0].start or 0)
        out[7] = mask_in.at[last.index[0]].set(False)
        return tuple(out)
    monkeypatch.setattr(tree, "_stream_round0", dropped)


def main(tmp: str, out_path: str) -> None:
    import jax
    import pytest

    from bench.lib import trace
    from bench.tests import test_bench_rehearsal as faults
    from bench.tests.rehearse import run_cell
    assert len(jax.devices()) == 4, jax.devices()
    cell = "batch-tiny-mesh4"
    out = {}
    rc, res, err = run_cell(tmp, cell, SEED, overrides=SMALL)
    out["sound"] = {"rc": rc, "res": res, "err": err[-4000:]}

    planted = {"state_unchanged": faults._fault_state_unchanged,
               "half_batch": faults._fault_half_batch,
               "answer_altered": faults._fault_answer_altered,
               "answer_swapped": faults._fault_answer_swapped,
               "exchange_left_out": fault_exchange_left_out}
    for i, (name, fault) in enumerate(planted.items()):
        mp = pytest.MonkeyPatch()
        try:
            fault(mp)
            rc, res, err = run_cell(tmp, cell, SEED + 1 + i,
                                    overrides=SMALL)
        finally:
            mp.undo()
        out[name] = {"rc": rc, "res": res, "err": err[-4000:]}

    keep = os.path.join(tmp, "kept")
    mp = pytest.MonkeyPatch()
    mp.setattr(trace.Trace, "load", staticmethod(cpu_trace))
    try:
        rc, res, err = run_cell(tmp, cell, SEED + 10, "--keep-trace", keep,
                                overrides=SMALL, trace=1)
    finally:
        mp.undo()
    tr = cpu_trace(os.path.join(keep, "trace.xplane.pb"))
    t0, t1 = tr.window()
    inside = [s for s, _ in tr.spans("wave.stage") if t0 <= s <= t1]
    out["traced"] = {"rc": rc, "res": res, "err": err[-4000:],
                     "stages": len(inside),
                     "puts": len([s for s, _ in tr.spans("wave.put")
                                  if t0 <= s <= t1])}
    with open(out_path, "w") as f:
        json.dump(out, f)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
