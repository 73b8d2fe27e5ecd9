"""Program spans on the profiler's clock (``repro.engine.telemetry.span``).

Every span lands in an active ``jax.profiler`` trace and, when a Tracer is
attached, in the Tracer too, under the same names; spans never change an
answer; the serve programs carry their kind's name; and the benchmark's
per-layer readers of these spans compute what they say on hand-made
traces and read nothing where the spans are absent.
"""
import collections
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import manifest
from bench.lib import trace as trace_lib
from repro.core import (ArraySource, ExemplarClustering, TreeConfig,
                        tree_maximize)
from repro.engine import Tracer
from repro.serve import (Dispatcher, SelectionRequest, SelectionService,
                         ingest)
from repro.serve.service import CompileCache

GATHER_SLEEP_S = 0.02


class _SlowSource(ArraySource):
    """A source whose every read takes at least ``GATHER_SLEEP_S``, so a
    wave's gather is long against the spans' own cost."""

    def gather(self, idx):
        time.sleep(GATHER_SLEEP_S)
        return super().gather(idx)


def _batch_setup(seed=0):
    r = np.random.default_rng(seed)
    data = r.standard_normal((1200, 16)).astype(np.float32)
    E = data[r.choice(len(data), 64, replace=False)]
    return data, ExemplarClustering(jnp.asarray(E))


def _tree(data, obj, source_cls=ArraySource, tracer=None):
    cfg = TreeConfig(k=6, capacity=100, seed=1, engine="pipelined",
                     telemetry=tracer)
    return tree_maximize(obj, source_cls(data), cfg, wave_machines=4)


def _profiled(fn):
    """``fn()`` under a profile; its result and the reduced trace."""
    with trace_lib.profiled(True) as th:
        with jax.profiler.TraceAnnotation("bench.window"):
            out = fn()
    try:
        return out, trace_lib.Trace.load(th["path"])
    finally:
        trace_lib.cleanup(th)


def _inside(inner, outer):
    return all(any(s <= a and b <= e for s, e in outer) for a, b in inner)


# ---------------------------------------------------------------------------
# (a) batch: one span of each kind per wave, on the profiler and the Tracer
# ---------------------------------------------------------------------------


def test_batch_wave_spans_on_the_profiler_clock():
    data, obj = _batch_setup()
    _tree(data, obj, _SlowSource)                    # compile outside
    tr = Tracer()
    res, prof = _profiled(lambda: _tree(data, obj, _SlowSource, tr))
    waves = res.engine_stats.waves
    assert waves == 3
    for name in ("wave.gather", "wave.read", "wave.mask", "wave.stage",
                 "wave.put", "wave.dispatch", "wave.fold", "wave.block",
                 "wave.solve"):
        assert len(prof.spans(name)) == waves, name                # 1 device
    assert _inside(prof.spans("wave.put"), prof.spans("wave.stage"))
    assert _inside(prof.spans("wave.read"), prof.spans("wave.gather"))
    assert _inside(prof.spans("wave.mask"), prof.spans("wave.gather"))
    for name in ("wave.stage", "wave.dispatch", "wave.fold", "wave.block"):
        assert _inside(prof.spans(name), prof.spans("wave.solve")), name
    rounds = prof.spans("round.round")
    assert len(rounds) == res.rounds
    assert len(prof.spans("round.dispatch")) == res.rounds - 1
    assert _inside(prof.spans("round.dispatch"), rounds)
    # the profiler's gather agrees with the engine's own record
    prof_gather = np.mean([e - s for s, e in prof.spans("wave.gather")])
    engine_gather = np.mean([t.gather_s for t in res.engine_stats.traces])
    assert engine_gather > GATHER_SLEEP_S
    assert prof_gather == pytest.approx(engine_gather, rel=0.05)
    # the attached Tracer saw the same spans, name for name
    seen = collections.Counter(f"{e.cat}.{e.name}" for e in tr.events
                               if e.phase == "X")
    assert seen["wave.gather"] == waves
    for name, count in seen.items():
        assert len(prof.spans(name)) == count, name


# ---------------------------------------------------------------------------
# (b) serving: submit/drain/reply and the group's phases
# ---------------------------------------------------------------------------

N, D, MU, K = 112, 5, 12, 4
MAX_BATCH = 2


@pytest.fixture(scope="module")
def serve_world():
    rng = np.random.default_rng(11)
    X = rng.normal(size=(N, D)).astype(np.float32)
    attrs = np.zeros((N, 2), np.float32)
    attrs[:, 0] = rng.uniform(0.2, 1.0, N).astype(np.float32)
    attrs[:, 1] = rng.integers(0, 3, N).astype(np.float32)
    E = X[rng.choice(N, 24, replace=False)]
    st = ingest(ArraySource(X), TreeConfig(k=K, capacity=MU, seed=5),
                attrs=attrs)
    return X, E, st


def test_serve_spans_cover_each_request(serve_world):
    X, E, st = serve_world
    tr = Tracer()
    svc = SelectionService(st, E, tracer=tr)
    fresh = SelectionRequest(k=K, query=X[3], seed=1)
    repeat = SelectionRequest(k=K, query=X[3], seed=2)     # same round 0
    burst = [SelectionRequest(k=K, query=X[10 + i], seed=i)
             for i in range(5)]

    def session():
        dp = Dispatcher(svc, max_batch=MAX_BATCH)
        try:
            dp.map([fresh])
            dp.map([repeat])
            return dp.map(burst)
        finally:
            dp.close()

    out, prof = _profiled(session)
    assert all(r.feasible for r in out)
    n_req = 2 + len(burst)
    submits, replies = prof.spans("serve.submit"), prof.spans("serve.reply")
    drains, groups = prof.spans("serve.drain"), prof.spans("serve.group")
    assert len(submits) == len(replies) == n_req
    per_drain = [sum(d0 <= r0 and r1 <= d1 for r0, r1 in replies)
                 for d0, d1 in drains]
    assert sum(per_drain) == n_req                  # each in one drain
    assert max(per_drain) <= MAX_BATCH
    # the i-th submit is answered after it was made (one FIFO queue)
    assert all(s1 <= r0 for (_s0, s1), (r0, _r1) in zip(submits, replies))
    # the repeated spec hits the round-0 cache: its group has no round 0
    r0 = prof.spans("serve.round0")
    in_group = [sum(g0 <= a and b <= g1 for a, b in r0) for g0, g1 in groups]
    assert in_group[0] == 1 and in_group[1] == 0
    assert svc.sol_hits == 1
    assert len(r0) == len(prof.spans("serve.round0.fetch"))
    for name in ("serve.tail.stack", "serve.tail.upload", "serve.tail",
                 "serve.tail.fetch", "serve.check"):
        assert len(prof.spans(name)) == len(groups), name
        assert _inside(prof.spans(name), groups), name
    assert _inside(groups, drains)
    # the service's Tracer saw the same spans
    seen = collections.Counter(f"{e.cat}.{e.name}" for e in tr.events
                               if e.phase == "X")
    assert seen["serve.drain"] == len(drains)
    for name, count in seen.items():
        assert len(prof.spans(name)) == count, name
    # the programs that ran carry their kind's name
    names = {n for n, _s, _e in prof.host}
    assert {"PjitFunction(serve_round0)",
            "PjitFunction(serve_tail)"} <= names


def test_serve_programs_are_named_by_kind():
    cc = CompileCache()
    fn = cc.entry("tail", ("fk",), 1, lambda: (lambda x: x + 1))
    assert "@jit_serve_tail" in fn.lower(np.float32(1)).as_text()
    fn(np.float32(1))
    fn(np.float32(2))
    assert cc.compiles == 1 and cc.steady_retraces() == 0
    fn0 = cc.entry("round0", ("fk",), (1, 2), lambda: (lambda x: x * 2))
    assert "@jit_serve_round0" in fn0.lower(np.float32(1)).as_text()


# ---------------------------------------------------------------------------
# (c) a profile never changes an answer
# ---------------------------------------------------------------------------


def test_batch_bit_identical_with_a_profile_active():
    data, obj = _batch_setup(seed=3)
    plain = _tree(data, obj)
    traced, _ = _profiled(lambda: _tree(data, obj))
    np.testing.assert_array_equal(plain.sel_rows, traced.sel_rows)
    np.testing.assert_array_equal(plain.sel_mask, traced.sel_mask)
    assert plain.value == traced.value
    assert plain.round_values == traced.round_values
    assert plain.oracle_calls == traced.oracle_calls


@pytest.mark.parametrize("constraint", [None, "knapsack:budget=2.0:col=0"])
def test_serve_bucket1_bit_identical_with_a_profile_active(serve_world,
                                                          constraint):
    X, E, st = serve_world
    req = SelectionRequest(k=K, query=X[7], seed=9, constraint=constraint)
    plain = SelectionService(st, E).serve([req])[0]
    (traced,), _ = _profiled(lambda: SelectionService(st, E).serve([req]))
    np.testing.assert_array_equal(plain.rows, traced.rows)
    np.testing.assert_array_equal(plain.mask, traced.mask)
    assert plain.value == traced.value
    assert plain.feasible and traced.feasible


# ---------------------------------------------------------------------------
# (d) the benchmark's readers of these spans, on hand-made traces
# ---------------------------------------------------------------------------


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[
            types.SimpleNamespace(name=n, start_ns=round(s * 1e9),
                                  duration_ns=round((e - s) * 1e9))
            for n, s, e in evs]) for ln, evs in lines.items()])


def _rec(host, ops=()):
    planes = [_plane("/host:CPU", {"python": [("bench.window", 0.0, 10.0)]
                                   + list(host)}),
              _plane("/device:TPU:0", {"XLA Ops": list(ops)})]
    return {"trace": trace_lib.Trace(planes)}


READINGS = {
    "wave_read_s.batch": (
        [("wave.read", 1.0, 2.0), ("wave.read", 3.0, 3.5),
         ("wave.read", 11.0, 12.0)], (), 0.75),
    "wave_stage_s.batch": (
        [("wave.stage", 2.0, 2.2), ("wave.stage", 4.0, 4.6)], (), 0.4),
    "wave_put_s.batch": (
        [("wave.put", 2.0, 2.1), ("wave.put", 2.1, 2.3),
         ("wave.put", 11.0, 12.0)], (), 0.15),
    "program_dispatch_s.batch": (
        [("bench.job", 0.5, 5.0), ("bench.job", 5.5, 9.0),
         ("wave.dispatch", 1.0, 1.1), ("wave.dispatch", 2.0, 2.2),
         ("round.dispatch", 4.0, 4.3), ("wave.dispatch", 6.0, 6.5)],
        (), (0.6 + 0.5) / 2),
    "queue_wait_s.serve": (
        [("serve.submit", 1.0, 1.01), ("serve.submit", 2.0, 2.02),
         ("serve.submit", 2.1, 2.2),
         ("serve.drain", 1.5, 1.9), ("serve.drain", 2.5, 3.5),
         ("serve.reply", 1.8, 1.85), ("serve.reply", 3.0, 3.05),
         ("serve.reply", 3.2, 3.25)], (), 0.48),
    "round0_s.serve": (
        [("serve.round0", 1.0, 1.3), ("serve.round0", 3.0, 3.5)], (), 0.4),
    "sol_roundtrip_s.serve": (
        [("serve.group", 1.0, 2.0), ("serve.group", 3.0, 4.0),
         ("serve.round0.fetch", 1.3, 1.4),
         ("serve.tail.stack", 1.5, 1.55), ("serve.tail.stack", 3.1, 3.2),
         ("serve.tail.upload", 1.6, 1.65), ("serve.tail.upload", 3.3, 3.35)],
        (), 0.35 / 2),
    "tail_s.serve": (
        [("serve.tail", 1.7, 1.9), ("serve.tail", 3.5, 3.9)], (), 0.3),
    "idle_in_service_share.serve": (
        [("serve.drain", 1.0, 2.0), ("serve.drain", 3.0, 5.0)],
        [("%fusion.1 = f32[8] fusion(...)", 1.2, 1.7),
         ("%fusion.2 = f32[8] fusion(...)", 4.0, 6.0)], 50.0),
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_span_metric_reads_hand_made_trace(name):
    host, ops, want = READINGS[name]
    got = manifest.metric_reader(name).read(_rec(host, ops))
    assert got == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("name", sorted(READINGS))
def test_span_metric_reads_nothing_without_its_spans(name):
    reader = manifest.metric_reader(name)
    ops = [("%fusion.1 = f32[8] fusion(...)", 1.0, 2.0)]
    assert reader.read(_rec([("bench.job", 0.5, 5.0)], ops)) is None
    assert reader.read({"trace": None}) is None
