"""serve-fresh rehearsed on the CPU, and its comparison shown to fail when
the served path is broken underneath."""
import pytest

from bench.tests import serve_rehearsal as sr

CELL = "serve-fresh"


def test_serve_cells_are_in_the_manifest():
    sr.check_in_manifest(CELL)


def test_serve_cell_runs_and_is_correct(tmp_path):
    rc, res, err = sr.serve(tmp_path, CELL, sr.SEED, trace=1)
    assert rc == 0, err
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    m = res["metrics"]
    assert m["sol_cache_hit_share.serve"]["value"] == 0.0   # all misses
    assert m["batch_size_mean.serve"]["value"] >= 1.0
    assert m["tail_p95_s.serve"]["value"] > 0
    assert "compiles_in_window=0" in err


def test_serve_end_to_end_metrics(tmp_path):
    rc, res, err = sr.serve(tmp_path, CELL, sr.SEED + 1)
    assert rc == 0 and res["correct"], err
    m = res["metrics"]
    assert set(m) == sr.E2E
    assert m["serve_p50_s"]["value"] > 0
    assert m["serve_req_per_s"]["value"] > 0


def test_serve_control_is_not_correct(tmp_path):
    sr.check_control_fails(tmp_path, CELL)


@pytest.mark.parametrize("fault", list(sr.FAULTS))
def test_serve_fault_is_not_correct(tmp_path, monkeypatch, fault):
    sr.check_fault_fails(tmp_path, monkeypatch, CELL, fault)
