"""The trace reduction: a small trace recorded on one TPU v5e
(``data/record_trace.py``), and hand-made planes for the arithmetic."""
import os
import types

import pytest

from bench.lib import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "tpu_small.xplane.pb")


@pytest.fixture(scope="module")
def tpu():
    return trace.Trace.load(DATA)


def test_recorded_trace_has_one_device_and_the_bench_spans(tpu):
    assert list(tpu.ops) == [0] and list(tpu.modules) == [0]
    assert len(tpu.ops[0]) == 45 and len(tpu.modules[0]) == 15
    assert len(tpu.spans("bench.job")) == 3
    t0, t1 = tpu.window()
    assert 0.2 < t1 - t0 < 0.25


def test_recorded_trace_busy_and_idle(tpu):
    t0, t1 = tpu.window()
    busy = tpu.busy_s(t0, t1)
    assert 0.001 < busy < 0.002           # 15 small matmuls
    gaps = dict(tpu.idle_gaps(t0, t1))
    # the sleeps inside jobs (3 x 50 ms) and between them (3 x 20 ms)
    assert gaps["bench.job"] == pytest.approx(0.15, abs=0.01)
    assert gaps["bench.window"] == pytest.approx(0.06, abs=0.01)
    assert sum(gaps.values()) == pytest.approx(t1 - t0 - busy, rel=1e-9)


def test_recorded_trace_ops_are_named_by_program(tpu):
    t0, t1 = tpu.window()
    top = tpu.top_ops(t0, t1, n=3)
    assert len(top) == 3
    assert top[0][0] == "jit__lambda/fusion"
    assert all(s > 0 for _, s in top)
    mods = tpu.module_s(t0, t1)
    assert list(map(trace.program_name, mods)) == ["jit__lambda"]
    assert tpu.collective_s(t0, t1) == 0.0


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[
            types.SimpleNamespace(name=n, start_ns=s * 1e9,
                                  duration_ns=(e - s) * 1e9)
            for n, s, e in evs]) for ln, evs in lines.items()])


def test_union_clip_collectives_and_gap_labels():
    ops0 = [("%fusion.1 = f32[8] fusion(...)", 1.0, 2.0),
            ("%fusion.2 = f32[8] fusion(...)", 1.5, 2.5),   # overlaps
            ("%all-reduce.3 = f32[8] all-reduce(...)", 4.0, 4.5),
            ("%fusion.4 = f32[8] fusion(...)", 9.5, 11.0)]  # past the end
    ops1 = [("%all-gather.1 = f32[8] all-gather(...)", 2.0, 3.0)]
    planes = [
        _plane("/device:TPU:0", {"XLA Ops": ops0, "XLA Modules": [
            ("jit_step(1)", 0.9, 4.6), ("jit_step(1)", 9.4, 11.0)]}),
        _plane("/device:TPU:1", {"XLA Ops": ops1, "XLA Modules": [
            ("jit_step(1)", 1.9, 3.1)]}),
        _plane("/host:CPU", {"python": [("bench.window", 0.0, 10.0),
                                        ("bench.job", 0.5, 6.0),
                                        ("other", 0.0, 10.0)]}),
    ]
    tr = trace.Trace(planes)
    t0, t1 = tr.window()
    assert (t0, t1) == (0.0, 10.0)
    # device 0 busy [1, 2.5] + [4, 4.5] + [9.5, 10]; device 1 [2, 3]
    assert tr.busy_s(t0, t1) == pytest.approx((1.5 + 0.5 + 0.5 + 1.0) / 2)
    assert tr.collective_s(t0, t1) == pytest.approx((0.5 + 1.0) / 2)
    top = dict(tr.top_ops(t0, t1))
    assert top["jit_step/all-gather.1"] == pytest.approx(0.5)
    gaps = dict(tr.idle_gaps(t0, t1, spans=[("wave.gather", 6.0, 9.0)]))
    # device-0 gaps: [0,1] [2.5,4] [4.5,9.5]; [0, 0.5] lies in the window
    # only, [0.5, 1] and [2.5, 4] and [4.5, 6] in the job, [6, 9] in the
    # gather span, [9, 9.5] in the window again
    assert gaps["bench.job"] == pytest.approx(0.5 + 1.5 + 1.5)
    assert gaps["bench.window"] == pytest.approx(0.5 + 0.5)
    assert gaps["wave.gather"] == pytest.approx(3.0)
    assert "other" not in gaps
