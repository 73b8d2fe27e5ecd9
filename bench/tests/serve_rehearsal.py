"""The serving cells rehearsed on the CPU through the serve driver, and
faults planted underneath the served path; the tests of each cell sit in a
file of their own so that they run side by side."""
import numpy as np

from bench.lib import manifest
from bench.tests.rehearse import run_cell

SEED = 2_147_483_671
SMALL = {"n": 4_000}        # 4 machines of mu = 1,000 rows at d = 3,072
E2E = {"serve_p50_s", "serve_req_per_s", "setup_s"}


def serve(tmp_path, workload, seed, *args, seconds=8.0, trace=0):
    return run_cell(tmp_path, workload, seed, *args, overrides=SMALL,
                    seconds=seconds, trace=trace)


def check_in_manifest(workload):
    bm = manifest.load()
    assert manifest.validate(bm) == []
    cell = manifest.cell(bm, workload)
    assert cell["config"]["driver"] == "serve"
    assert cell["traffic"]["kind"] == "open_poisson"
    assert {m["name"] for m in cell["end_to_end"]} == E2E


def _tail_unchanged(monkeypatch):
    """The tail's rounds hand back nothing: round 0's best stands."""
    from repro.core.distributed import RoundResult
    from repro.serve import service
    real = service._run_round_in_turn

    def frozen(*a, **kw):
        r = real(*a, **kw)
        return RoundResult(r.sol_rows, r.sol_mask & False,
                           r.values * 0 - np.inf, r.oracle_calls, r.depth)
    monkeypatch.setattr(service, "_run_round_in_turn", frozen)


def _half_batch(monkeypatch):
    """Round 0 solves only the first half of the resident machines."""
    from repro.serve import service
    real = service.make_round0_fn

    def half(fk):
        body = real(fk)

        def round0(blocks, bmask, *rest):
            keep = (np.arange(bmask.shape[0]) < bmask.shape[0] // 2)
            return body(blocks, bmask & keep[:, None], *rest)
        return round0
    monkeypatch.setattr(service, "make_round0_fn", half)


def _answer_altered(monkeypatch):
    """One served row is changed where the answer is produced."""
    from repro.serve import service
    real = service.SelectionService._serve_group

    def altered(self, fk, items):
        outs = real(self, fk, items)
        for o in outs:
            o.rows = o.rows.copy()
            o.rows[0, 0] += np.float32(1e-3)
        return outs
    monkeypatch.setattr(service.SelectionService, "_serve_group", altered)


def _answer_swapped(monkeypatch):
    """Each answer's rows are an earlier answer's of the same size, byte-exact
    ground-set rows, with this request's value left as it was."""
    from repro.serve import service
    real = service.SelectionService._serve_group
    last: dict = {}

    def swapped(self, fk, items):
        outs = real(self, fk, items)
        for o in outs:
            mine = (o.rows, o.attrs, o.mask)
            if o.rows.shape in last:
                o.rows, o.attrs, o.mask = last[o.rows.shape]
            last[o.rows.shape] = mine
        return outs
    monkeypatch.setattr(service.SelectionService, "_serve_group", swapped)


FAULTS = {"state_unchanged": _tail_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered,
          "answer_swapped": _answer_swapped}


def check_control_fails(tmp_path, workload):
    rc, res, err = serve(tmp_path, workload, SEED + 2, "--control",
                         "program-bf16-wire")
    assert rc == 0, err
    assert res["correct"] is False
    assert res["checks"]["rows_off_set"]["value"] > 0


def check_fault_fails(tmp_path, monkeypatch, workload, fault):
    FAULTS[fault](monkeypatch)
    rc, res, err = serve(tmp_path, workload, SEED + 3)
    assert rc == 0, err
    assert res["correct"] is False, res["checks"]
    if fault == "answer_swapped":
        # ground-set rows, each request's own value: the re-score of the
        # returned rows against the reference's tells
        c = res["checks"]
        assert c["rows_off_set"]["value"] == 0, c
        assert c["answer_gap_ref"]["value"] > c["answer_gap_ref"]["limit"]
