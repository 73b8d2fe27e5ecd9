"""Each cell rehearsed on the CPU through the drivers' code paths, and the
comparison shown to fail when the timed path is broken underneath."""
import numpy as np
import pytest

from bench.tests.rehearse import run_cell

SEED = 2_147_483_659          # above 2**31, as the driver's seeds are


def test_batch_cell_runs_and_is_correct(tmp_path):
    rc, res, err = run_cell(tmp_path, "batch-tiny", SEED)
    assert rc == 0, err
    assert res["correct"] is True, err
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"batch_rows_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert "compiles_in_window=0" in err
    tail = err.strip().splitlines()[-len(res["checks"]) - 1:]
    assert all(line.startswith("check ") for line in tail), tail


def test_batch_traced_run_reads_host_layers(tmp_path):
    rc, res, err = run_cell(tmp_path, "batch-tiny", SEED + 1, trace=1)
    assert rc == 0 and res["correct"], err
    m = res["metrics"]
    for name in ("wave_gather_s.batch", "wave_solve_s.batch",
                 "rounds_tail_s.batch"):
        assert m[name]["value"] > 0
    # the CPU trace has no TPU plane: device readers find nothing, so their
    # metrics are left out rather than reported as 0
    assert "round0_solve_roofline.batch" not in m
    assert "device_idle_share.batch" not in m
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _fault_state_unchanged(monkeypatch):
    """Rounds t >= 1 hand back nothing: the state after round 0 stands."""
    from repro.core import tree
    from repro.core.distributed import RoundResult
    real = tree._dispatch_round

    def frozen(obj, blocks, bmask, *a, **kw):
        r = real(obj, blocks, bmask, *a, **kw)
        return RoundResult(r.sol_rows, r.sol_mask & False,
                           r.values * 0 - np.inf, r.oracle_calls, r.depth)
    monkeypatch.setattr(tree, "_dispatch_round", frozen)


def _fault_half_batch(monkeypatch):
    """Round-0 waves solve only their first half of machines."""
    from repro.core import tree
    real = tree.stage_wave_inputs

    def half(mesh, blocks, valid, *rest):
        valid = valid.copy()
        valid[valid.shape[0] // 2:] = False
        return real(mesh, blocks, valid, *rest)
    monkeypatch.setattr(tree, "stage_wave_inputs", half)


def _fault_answer_altered(monkeypatch):
    """One selected row is changed where the answer is produced."""
    import repro.core
    real = repro.core.tree_maximize

    def altered(*a, **kw):
        res = real(*a, **kw)
        res.sel_rows = res.sel_rows.copy()
        res.sel_rows[0, 0] += np.float32(1e-3)
        return res
    monkeypatch.setattr(repro.core, "tree_maximize", altered)


def _fault_answer_swapped(monkeypatch):
    """The answer's rows are the weakest round-0 machine's solution of the
    first wave, byte-exact ground-set rows, with the reported value left as
    it was."""
    import repro.core
    from repro.core import tree
    real_wave, real_tree = tree._dispatch_blocks, repro.core.tree_maximize
    first: list = []

    def spy(*a, **kw):
        r = real_wave(*a, **kw)
        if not first:
            first.append(r)
        return r

    def swapped(*a, **kw):
        first.clear()
        res = real_tree(*a, **kw)
        r = first[0]
        values = np.asarray(r.values)
        worst = int(np.argmin(np.where(np.isfinite(values), values, np.inf)))
        res.sel_rows = np.asarray(r.sol_rows[worst]).copy()
        res.sel_mask = np.asarray(r.sol_mask[worst]).copy()
        return res
    monkeypatch.setattr(tree, "_dispatch_blocks", spy)
    monkeypatch.setattr(repro.core, "tree_maximize", swapped)


@pytest.mark.parametrize("fault", [_fault_state_unchanged, _fault_half_batch,
                                   _fault_answer_altered,
                                   _fault_answer_swapped],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered", "answer_swapped"])
def test_batch_fault_is_not_correct(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    rc, res, err = run_cell(tmp_path, "batch-tiny", SEED + 2)
    assert rc == 0, err
    assert res["correct"] is False, res["checks"]
    if fault is _fault_answer_swapped:
        # every row is a ground-set row and the value is TREE's: only the
        # re-score of the returned rows against the reference's can tell
        c = res["checks"]
        assert c["rows_off_set"]["value"] == 0, c
        assert c["value_gap_ref"]["value"] <= c["value_gap_ref"]["limit"]
        assert c["answer_gap_ref"]["value"] > c["answer_gap_ref"]["limit"]


def test_batch_control_is_not_correct(tmp_path):
    """The control: the program with its own bf16 wire format switched on
    for the fp32 ground set (see PERF.md for the readings on the chip)."""
    rc, res, err = run_cell(tmp_path, "batch-tiny", SEED + 3,
                            "--control", "program-bf16-wire")
    assert rc == 0, err
    assert res["correct"] is False
    assert res["checks"]["rows_off_set"]["value"] > 0
