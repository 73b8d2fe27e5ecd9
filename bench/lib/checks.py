"""The comparison that decides ``correct``, and its report.

Each number compared has a limit of its own (the config's ``limits``); a
run is correct when no job failed and every number is at or under its
limit.  The numbers go to standard error as the run's last lines and into
the result line under ``checks``.
"""
from __future__ import annotations

import sys

import numpy as np

from bench.lib import rescore


def match_ids(data_np: np.ndarray, data_dev, rows: np.ndarray):
    """Ground-set ids of ``rows`` and whether each is a byte-exact copy.

    Rows are unit norm and distinct, so the nearest row by inner product is
    the only candidate; the bytes then decide."""
    import jax
    import jax.numpy as jnp
    if len(rows) == 0:
        return np.zeros((0,), np.int64), np.zeros((0,), bool)
    dots = jax.lax.dot_general(
        data_dev, jnp.asarray(rows, jnp.float32), (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)
    ids = np.asarray(jnp.argmax(dots, axis=0)).astype(np.int64)
    exact = np.array([np.array_equal(data_np[i], r)
                      for i, r in zip(ids, rows)], bool)
    return ids, exact


def answer_readings(data_np, data_dev, E, k: int, job, ref,
                    weights=None) -> dict:
    """Readings of one answer (a batch job's or a served request's) against
    the reference's answer to the same input.

    Where the returned rows are not the reference's own, an answer that a
    near-tie leaves open (``ref["answers"]``, :mod:`ties`) with the same
    rows stands in for it; ``ref_answer`` reads which (0: its own).
    ``answer_gap_ref`` re-scores in fp64 the rows the program returned and
    the rows the reference picked, both in ground-set order and under the
    request's ``weights``: it reads 0 when the program returns TREE's
    answer, whatever value it reports beside it."""
    mask = np.asarray(job["mask"], bool)
    rows = np.asarray(job["rows"], np.float32)[mask]
    ids, exact = match_ids(data_np, data_dev, rows)
    prog = set(ids.tolist())

    def ids_of(answer):
        return set(int(i) for i in answer["ids"] if i >= 0)

    used = 0
    if prog != ids_of(ref):
        for n, alt in enumerate(ref.get("answers", ())):
            if n and ids_of(alt) == prog:
                ref, used = alt, n
                break
    want = np.array(sorted(ids_of(ref)), np.int64)
    v_ref = rescore.exemplar_value(E, data_np[want], weights=weights)
    v_prog = rescore.exemplar_value(E, rows[np.argsort(ids, kind="stable")],
                                    weights=weights)
    return {
        "picks_off_ref": float(len(prog ^ set(want.tolist()))),
        "ref_answer": float(used),
        "value_gap_ref": abs(job["value"] - ref["value"]) / abs(ref["value"]),
        "answer_gap_ref": abs(v_prog - v_ref) / abs(v_ref),
        "rows_off_set": float((~exact).sum()),
        "over_k": float(max(0, int(mask.sum()) - k)),
    }


def worst(readings: list[dict]) -> dict:
    """Each number at its worst over the compared jobs."""
    out: dict[str, float] = {}
    for r in readings:
        for key, v in r.items():
            v = float(v) if np.isfinite(v) else float("inf")
            out[key] = max(out.get(key, v), v)
    return out


def judge(readings: dict, limits: dict, failed: int) -> tuple[bool, dict]:
    """``(correct, checks)``: ``checks`` maps each compared number to its
    reading and limit; numbers without a limit are not compared."""
    checks = {name: {"value": readings.get(name, float("inf")),
                     "limit": limit} for name, limit in limits.items()}
    ok = failed == 0 and bool(readings) and all(
        c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def report(checks: dict, failed: int, readings: dict, stream=None) -> None:
    """Readings that are not compared first, then each compared number
    with its limit as the last lines."""
    stream = stream or sys.stderr
    for name, v in readings.items():
        if name not in checks:
            print(f"reading {name} {v!r} (not compared)", file=stream)
    print(f"check failed_jobs {failed} limit 0", file=stream)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=stream)
    stream.flush()
