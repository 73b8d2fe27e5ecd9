"""serve-hot rehearsed on the CPU, and its comparison shown to fail when
the served path is broken underneath (round 0 runs in set-up, where the
pool is solved, and its solutions are served from the cache)."""
import pytest

from bench.tests import serve_rehearsal as sr

CELL = "serve-hot"


def test_serve_hot_cell_is_in_the_manifest():
    sr.check_in_manifest(CELL)


def test_serve_hot_cell_hits_the_cache_and_is_correct(tmp_path):
    rc, res, err = sr.serve(tmp_path, CELL, sr.SEED + 4, trace=1)
    assert rc == 0, err
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    # the pool was solved in set-up: every request of the window hits
    assert res["metrics"]["sol_cache_hit_share.serve"]["value"] == 100.0
    assert "compiles_in_window=0" in err


def test_serve_hot_control_is_not_correct(tmp_path):
    sr.check_control_fails(tmp_path, CELL)


@pytest.mark.parametrize("fault", list(sr.FAULTS))
def test_serve_hot_fault_is_not_correct(tmp_path, monkeypatch, fault):
    sr.check_fault_fails(tmp_path, monkeypatch, CELL, fault)
