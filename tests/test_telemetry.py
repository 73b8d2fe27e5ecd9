"""Unified telemetry layer (repro.engine.telemetry): the tracer must be a
pure *observer* — an instrumented run is bit-identical to an
uninstrumented one across engines, constraints, and dtypes — while its
exported span stream carries enough to reconstruct the engine's reported
overlap ratio to float precision, the metrics registry is a faithful
projection of the stats dataclasses, and the run manifest survives a
kill mid-write."""
import json
import os
import threading

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (ChunkedSource, ExemplarClustering, Knapsack,
                        QuantizedSource, TreeConfig, tree_maximize)
from repro.engine import (MetricsRegistry, RunManifest, Tracer,
                          build_manifest, dtype_label, feed_result_metrics,
                          format_report, profiler_session, span, top_spans,
                          wave_overlap_from_spans)
from repro.engine.telemetry import (MANIFEST_NAME, SCHEMA_VERSION,
                                    config_fingerprint)
from repro.launch import tracetool


def _setup(n=601, d=8, ne=96, seed=0):
    r = np.random.default_rng(seed)
    data = r.standard_normal((n, d)).astype(np.float32)
    E = data[r.choice(n, ne, replace=False)]
    return data, ExemplarClustering(jnp.asarray(E))


def _assert_identical(a, b):
    np.testing.assert_array_equal(a.sel_rows, b.sel_rows)
    np.testing.assert_array_equal(a.sel_mask, b.sel_mask)
    assert a.value == b.value                      # bit-identical, no rtol
    assert a.oracle_calls == b.oracle_calls
    assert a.rounds == b.rounds
    assert a.machines_per_round == b.machines_per_round
    assert a.round_values == b.round_values


def _run(data, obj, *, tracer=None, engine="sync", dtype=None,
         constraint=None, attrs=None, W=3, **cfg_kw):
    src = ChunkedSource.from_array(data, 128, attrs=attrs)
    if dtype is not None and dtype != "fp32":
        src = QuantizedSource(src, store_dtype=dtype)
    cfg = TreeConfig(k=6, capacity=60, seed=4, engine=engine,
                     telemetry=tracer, **cfg_kw)
    return tree_maximize(obj, src, cfg, wave_machines=W,
                         constraint=constraint)


# ---------------------------------------------------------------------------
# tracer core: spans, instants, tracks, thread safety
# ---------------------------------------------------------------------------


def test_span_context_manager_nests_and_orders():
    tr = Tracer()
    with span("round.outer", tracer=tr, step=1) as sp:
        with span("wave.inner", tracer=tr):
            pass
        sp.args["rows"] = 7
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "outer"]   # end order
    inner, outer = spans
    # proper nesting: outer brackets inner on the same clock
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert outer.args == {"step": 1, "rows": 7}            # late attrs stick
    assert (outer.t0, outer.t1) == (sp.t0, sp.t1)          # same readings
    assert tr.spans(cat="wave") == [inner]
    assert tr.spans(name="outer") == [outer]


def test_instants_and_named_tracks():
    tr = Tracer()
    tr.instant("evict", "fault", host=2)
    tr.emit("host-gather", "host", 1.0, 2.0, track="host-1", rows=5)
    ev_i, ev_x = tr.events
    assert ev_i.phase == "i" and ev_i.t0 == ev_i.t1
    assert ev_x.phase == "X" and ev_x.dur_s == 1.0
    names = tr.track_names()
    # the instant's track is the emitting thread; the span's is named
    assert names[ev_i.track] == threading.current_thread().name
    assert names[ev_x.track] == "host-1"
    assert ev_i.track != ev_x.track


def test_tracer_thread_safety():
    tr = Tracer()
    n_threads, n_spans = 8, 200
    # hold every thread at the gate so all are alive at once (Python
    # recycles thread idents, so early exits would fold tracks together)
    gate = threading.Barrier(n_threads)

    def work(i):
        gate.wait()
        for j in range(n_spans):
            with span(f"wave.w{i}", tracer=tr, j=j):
                pass

    threads = [threading.Thread(target=work, args=(i,), name=f"t{i}")
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr.events) == n_threads * n_spans
    # one auto-registered track per emitting thread, none lost
    assert sorted(tr.track_names().values()) == sorted(
        f"t{i}" for i in range(n_threads))
    per = {}
    for e in tr.events:
        per[e.name] = per.get(e.name, 0) + 1
    assert all(v == n_spans for v in per.values())


# ---------------------------------------------------------------------------
# exporters: schema round-trips
# ---------------------------------------------------------------------------


def test_chrome_trace_schema_roundtrip(tmp_path):
    tr = Tracer()
    with span("wave.gather", tracer=tr, wave=0, rows=10):
        pass
    tr.instant("hedge", "fault", wave=0)
    path = str(tmp_path / "trace.json")
    tr.export_chrome_trace(path)
    doc = json.load(open(path))
    assert doc["otherData"]["schema_version"] == SCHEMA_VERSION
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert meta and meta[0]["name"] == "thread_name"
    xs = [e for e in evs if e["ph"] == "X"]
    ins = [e for e in evs if e["ph"] == "i"]
    assert len(xs) == 1 and len(ins) == 1
    assert xs[0]["cat"] == "wave" and xs[0]["args"] == {"wave": 0, "rows": 10}
    assert isinstance(xs[0]["ts"], float) and isinstance(xs[0]["dur"], float)
    assert ins[0]["s"] == "t"
    # tracetool reads it back with timestamps intact to ~float precision
    events, tracks = tracetool.load_trace(path)
    assert len(events) == 2 and tracks
    got = next(e for e in events if e.phase == "X")
    want = next(e for e in tr.events if e.phase == "X")
    assert abs(got.dur_s - want.dur_s) < 1e-9


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_instruments_and_keys(tmp_path):
    reg = MetricsRegistry()
    reg.counter("engine.waves", engine="sync").inc(3)
    reg.counter("engine.waves", engine="sync").inc()        # same instrument
    reg.gauge("overlap", engine="pipelined").set(0.75)
    h = reg.histogram("gather_s", engine="pipelined", host=1)
    for v in (0.1, 0.3, 0.2):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["engine.waves{engine=sync}"] == 4
    assert snap["gauges"]["overlap{engine=pipelined}"] == 0.75
    # labels sort in the key regardless of call order
    hs = snap["histograms"]["gather_s{engine=pipelined,host=1}"]
    assert hs["count"] == 3 and hs["min"] == 0.1 and hs["max"] == 0.3
    path = str(tmp_path / "metrics.json")
    reg.export_json(path)
    assert json.load(open(path))["counters"] == snap["counters"]


def test_feed_result_metrics_projects_stats():
    data, obj = _setup()
    res = _run(data, obj, engine="pipelined")
    reg = MetricsRegistry()
    feed_result_metrics(reg, res)
    snap = reg.snapshot()
    es = res.engine_stats
    assert snap["counters"]["engine.waves{engine=pipelined}"] == es.waves
    assert (snap["counters"]["engine.bytes_moved{engine=pipelined}"]
            == es.bytes_moved)
    assert (snap["gauges"]["engine.overlap_ratio{engine=pipelined}"]
            == es.overlap_ratio)
    gh = snap["histograms"]["engine.gather_s{engine=pipelined}"]
    assert gh["count"] == es.waves
    assert abs(gh["sum"] - es.gather_s) < 1e-9
    # no mesh: one device, which every wave's blocks and mask were put on
    assert snap["gauges"]["mesh.devices"] == es.mesh_devices == 1
    assert len(es.stage_bytes) == es.waves
    assert (snap["counters"]["engine.stage_bytes{device=0}"]
            == sum(b[0] for b in es.stage_bytes) > es.bytes_moved)


# ---------------------------------------------------------------------------
# engine instrumentation: span invariants, stall accounting, overlap
# ---------------------------------------------------------------------------


def test_span_counts_pipelined_equals_sync():
    data, obj = _setup(seed=3)
    tr_s, tr_p = Tracer(), Tracer()
    a = _run(data, obj, tracer=tr_s, engine="sync")
    b = _run(data, obj, tracer=tr_p, engine="pipelined")
    _assert_identical(a, b)
    for name in ("gather", "solve"):
        assert (len(tr_s.spans(cat="wave", name=name))
                == len(tr_p.spans(cat="wave", name=name))
                == a.engine_stats.waves)
    # both engines close the run with one run-span and per-round spans
    for tr, res in ((tr_s, a), (tr_p, b)):
        assert len(tr.spans(cat="run")) == 1
        assert len(tr.spans(cat="round", name="round")) == res.rounds
    # stall spans exist only where a second thread can block
    assert tr_s.spans(cat="stall") == []
    # pipelined producer runs on its own named thread → ≥ 2 tracks
    assert len(tr_p.track_names()) >= 2
    assert "wave-prefetch" in tr_p.track_names().values()


def test_wave_traces_carry_timestamps_and_stall():
    data, obj = _setup(seed=5)
    res = _run(data, obj, engine="pipelined")
    traces = res.engine_stats.traces
    assert traces and all(t.t_end > t.t_start > 0.0 for t in traces)
    assert all(t.stall_s >= 0.0 for t in traces)
    # span-based wall is what the stamps reconstruct, and the scheduler
    # loop can only add wall *around* the waves, never remove it
    es = res.engine_stats
    assert 0.0 < es.span_wall_s <= es.wall_s + 1e-9


def test_trace_overlap_matches_engine_stats(tmp_path):
    data, obj = _setup(seed=7)
    tr = Tracer()
    res = _run(data, obj, tracer=tr, engine="pipelined")
    path = str(tmp_path / "trace.json")
    tr.export_chrome_trace(path)
    events, _ = tracetool.load_trace(path)
    _, ov, n_waves = tracetool.span_overlap(events)
    assert n_waves == res.engine_stats.waves
    # acceptance bound: the exported trace reconstructs the reported
    # overlap within 1e-6 (float µs round-trip keeps it far tighter)
    assert abs(ov - res.engine_stats.overlap_ratio) < 1e-6


def test_host_gather_spans_on_named_tracks():
    data, obj = _setup(seed=9)
    tr = Tracer()
    _run(data, obj, tracer=tr, engine="pipelined", hosts=2)
    host_spans = tr.spans(cat="host", name="host-gather")
    assert host_spans
    names = tr.track_names()
    lanes = {names[s.track] for s in host_spans}
    assert lanes == {"host-0", "host-1"}
    assert all("wave" in s.args and "rows" in s.args for s in host_spans)


# ---------------------------------------------------------------------------
# bit-identity: telemetry is observation only
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["sync", "pipelined"])
@pytest.mark.parametrize("dtype", ["fp32", "int8"])
def test_instrumented_bit_identical(engine, dtype):
    data, obj = _setup(seed=11)
    plain = _run(data, obj, engine=engine, dtype=dtype)
    traced = _run(data, obj, tracer=Tracer(), engine=engine, dtype=dtype)
    _assert_identical(plain, traced)


def test_instrumented_bit_identical_constrained():
    data, obj = _setup(seed=13)
    r = np.random.default_rng(7)
    attrs = r.uniform(0.2, 1.0, (len(data), 1)).astype(np.float32)
    spec = Knapsack(budget=3.0, col=0)
    plain = _run(data, obj, engine="pipelined", constraint=spec, attrs=attrs)
    traced = _run(data, obj, tracer=Tracer(), engine="pipelined",
                  constraint=spec, attrs=attrs)
    _assert_identical(plain, traced)
    np.testing.assert_array_equal(plain.sel_attrs, traced.sel_attrs)


def test_config_fingerprint_ignores_telemetry():
    a = TreeConfig(k=6, capacity=60, seed=4)
    b = TreeConfig(k=6, capacity=60, seed=4, telemetry=Tracer())
    c = TreeConfig(k=6, capacity=61, seed=4)
    assert config_fingerprint(a) == config_fingerprint(b)
    assert config_fingerprint(a) != config_fingerprint(c)


# ---------------------------------------------------------------------------
# run manifest: build, validate, atomicity, report formatting
# ---------------------------------------------------------------------------


def test_manifest_built_and_written_next_to_checkpoints(tmp_path):
    data, obj = _setup(seed=15)
    tr = Tracer()
    res = _run(data, obj, tracer=tr, engine="pipelined", dtype="int8",
               checkpoint_dir=str(tmp_path))
    m = res.manifest
    assert m is not None and m.validate() == []
    assert m.dtype == "int8" and m.source_fingerprint
    assert m.run["value"] == float(res.value)
    assert m.engine["width_trajectory"] == res.engine_stats.width_trajectory
    assert m.phases["total_wall_s"] > 0
    assert m.phases["round0_wall_s"] == res.round_walls[0]
    assert m.faults is None        # no fault policy armed on this run
    # written atomically next to the checkpoints, loads back equal
    on_disk = RunManifest.load(os.path.join(str(tmp_path), MANIFEST_NAME))
    assert on_disk.validate() == []
    assert on_disk.config_fingerprint == m.config_fingerprint
    assert on_disk.run == m.run
    # ... and the tracer's registry was fed the result's stats
    snap = tr.metrics.snapshot()
    assert (snap["counters"]["engine.waves{engine=pipelined}"]
            == res.engine_stats.waves)


def test_manifest_atomic_under_kill_mid_write(tmp_path, monkeypatch):
    data, obj = _setup(seed=17)
    res = _run(data, obj)
    m = build_manifest(TreeConfig(k=6, capacity=60, seed=4), res,
                       n=len(data), d=data.shape[1], dtype_label="fp32")
    path = str(tmp_path / "run_manifest.json")
    m.write(path)
    before = open(path).read()

    # kill the writer between tmp-file write and the atomic rename
    def boom(src, dst):
        raise KeyboardInterrupt("killed mid-write")

    monkeypatch.setattr(os, "replace", boom)
    m.run["value"] = -1.0
    with pytest.raises(KeyboardInterrupt):
        m.write(path)
    monkeypatch.undo()
    # the published manifest is byte-identical to the pre-kill version
    assert open(path).read() == before
    assert RunManifest.load(path).validate() == []


def test_manifest_validate_reports_missing_fields():
    m = RunManifest(config={}, config_fingerprint="", run={})
    problems = m.validate()
    assert any("config" in p for p in problems)
    assert any("'value'" in p for p in problems)
    m = RunManifest(config={"k": 1}, config_fingerprint="ab", dtype="fp32",
                    run={"value": 1.0, "rounds": 1, "oracle_calls": 2},
                    phases={"total_wall_s": 0.1},
                    engine={"engine": "sync"})
    assert any("engine section missing" in p for p in m.validate())


def test_format_report_matches_legacy_lines():
    data, obj = _setup(seed=19)
    res = _run(data, obj, engine="pipelined")
    cfg = TreeConfig(k=6, capacity=60, seed=4, engine="pipelined")
    m = build_manifest(cfg, res, n=len(data), d=data.shape[1],
                       dtype_label="fp32")
    m.feasibility = {"ok": True, "detail": "knapsack 2.9/3.0"}
    m.recheck = {"fp32": 0.5, "solve": 0.5, "rel_gap": 0.0, "status": "PASS"}
    lines = format_report(m)
    es, ing = res.engine_stats, res.ingest
    assert lines[0] == (f"TREE: f={res.value:.6f} rounds={res.rounds} "
                        f"machines/round={res.machines_per_round} "
                        f"oracle_calls={res.oracle_calls}")
    engine_line = next(l for l in lines if l.startswith("engine:"))
    assert engine_line == (
        f"engine: {es.engine} hosts={es.hosts} wall={es.wall_s:.3f}s "
        f"gather={es.gather_s:.3f}s solve={es.solve_s:.3f}s "
        f"overlap={es.overlap_ratio:.2%} bytes={es.bytes_moved} "
        f"max_in_flight={es.max_in_flight}")
    bytes_line = next(l for l in lines if l.startswith("bytes:"))
    assert f"total_bytes={ing.total_bytes}" in bytes_line
    assert "autotune:" not in "".join(lines)       # wave_autotune off
    assert lines[-2] == "feasibility: OK (knapsack 2.9/3.0)"
    assert lines[-1] == ("recheck: fp32=0.500000 solve=0.500000 "
                         "rel_gap=0.00e+00 PASS")


def test_dtype_label_vocabulary():
    assert dtype_label(np.float32) == "fp32"
    assert dtype_label(np.int8) == "int8"
    assert dtype_label(jnp.bfloat16) == "bf16"


# ---------------------------------------------------------------------------
# span-stream views + tracetool CLI
# ---------------------------------------------------------------------------


def test_wave_overlap_from_spans_arithmetic():
    # two waves, second gather fully hidden under first solve
    gathers = [(0.0, 1.0), (1.0, 2.0)]
    solves = [(1.0, 3.0), (3.0, 4.0)]
    wall, ov = wave_overlap_from_spans(gathers, solves)
    assert wall == 4.0
    assert ov == pytest.approx((2.0 + 3.0 - 4.0) / 2.0)
    assert wave_overlap_from_spans([], []) == (0.0, 0.0)
    # serialized spans → zero overlap, clamped
    wall, ov = wave_overlap_from_spans([(0.0, 1.0)], [(1.5, 2.0)])
    assert ov == 0.0


def test_top_spans_aggregates():
    tr = Tracer()
    for w in range(3):
        tr.emit("gather", "wave", 0.0, 1.0, wave=w)
    tr.emit("solve", "wave", 0.0, 5.0)
    tr.instant("hedge", "fault")
    rows = top_spans(tr.events)
    assert rows[0]["name"] == "solve" and rows[0]["total_s"] == 5.0
    assert rows[1] == {"cat": "wave", "name": "gather", "count": 3,
                       "total_s": 3.0, "mean_s": 1.0}


def test_tracetool_main_validates_and_cross_checks(tmp_path, capsys):
    data, obj = _setup(seed=21)
    tr = Tracer()
    res = _run(data, obj, tracer=tr, engine="pipelined")
    trace = str(tmp_path / "trace.json")
    manifest = str(tmp_path / "m.json")
    tr.export_chrome_trace(trace)
    res.manifest = build_manifest(
        TreeConfig(k=6, capacity=60, seed=4, engine="pipelined"), res,
        n=len(data), d=data.shape[1], dtype_label="fp32")
    res.manifest.write(manifest)
    assert tracetool.main([trace, "--manifest", manifest]) == 0
    out = capsys.readouterr().out
    assert "manifest: OK" in out
    assert "PASS" in next(l for l in out.splitlines()
                          if l.startswith("cross-check:"))
    # corrupt the reported overlap → cross-check must fail the run
    bad = json.load(open(manifest))
    bad["engine"]["overlap_ratio"] = 0.123456
    json.dump(bad, open(manifest, "w"))
    assert tracetool.main([trace, "--manifest", manifest]) != 0


def test_tracetool_rejects_invalid_manifest(tmp_path, capsys):
    tr = Tracer()
    tr.emit("gather", "wave", 0.0, 1.0)
    trace = str(tmp_path / "t.json")
    tr.export_chrome_trace(trace)
    bad = str(tmp_path / "bad.json")
    json.dump({"schema_version": 1, "run": {}}, open(bad, "w"))
    assert tracetool.main([trace, "--manifest", bad]) != 0
    assert "INVALID" in capsys.readouterr().out


def test_profiler_session_noop_without_dir():
    with profiler_session(None):
        pass
    with profiler_session(""):
        pass


def test_profiler_session_fails_when_the_profiler_cannot_start(
        tmp_path, monkeypatch):
    import jax

    def refuse(_dir):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        with profiler_session(str(tmp_path / "prof")):
            pass
