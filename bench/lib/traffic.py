"""One general generator for every traffic mix.

A mix is a data file ``bench/traffic/<name>.json``; nothing here knows a
mix by name.  Everything drawn is a function of ``--seed`` and the file, so
two runs with one seed send the same work in the same order.

Kinds:
  ``closed_jobs``   batch jobs back to back, ``clients`` at a time (1: one
                    job waits for the last); each job gets a fresh partition
                    seed from the stream ``(seed, stream)``.
  ``open_poisson``  requests sent at their due times whether or not earlier
                    ones finished.  The due times are one Poisson process of
                    ``rate_per_s`` given its count: round(rate x seconds)
                    times, sorted uniform over the window.  They and the
                    order of the kinds (``mix``, split exactly by ``share``)
                    come from the file's ``schedule_seed``, so every
                    ``--seed`` sends the same arrivals and the same kinds;
                    ``--seed`` draws each request's parameters (a query row
                    of the ground set, a knapsack budget from a range, a
                    tail seed) from the stream ``(seed, stream)``.
                    With ``pool`` (``size``, ``zipf_s``) the requests repeat
                    a pool of ``size`` request specs, drawn from ``--seed``
                    (``pool_specs``), the kinds split exactly; each request
                    takes the pool's rank r with weight 1 / r^zipf_s, in an
                    order fixed by ``schedule_seed``, and a fresh tail seed.
"""
from __future__ import annotations

import json

import numpy as np

from bench.lib import manifest

KINDS = ("closed_jobs", "open_poisson")
SEED_HI = 2**31 - 1


def load(name: str) -> dict:
    with open(manifest.traffic_path(name)) as f:
        spec = json.load(f)
    if spec.get("kind") not in KINDS:
        raise ValueError(f"traffic {name}: kind {spec.get('kind')!r}")
    return spec


def job_seeds(spec: dict, seed: int):
    """Endless partition seeds for ``closed_jobs``: the warm-up job's first,
    then one per job of the window."""
    rng = np.random.default_rng((int(seed), int(spec.get("stream", 1))))
    while True:
        yield int(rng.integers(1, SEED_HI))


def _spec(kind: dict, rng, n_rows: int) -> dict:
    out = {"k": int(kind["k"])}
    if kind.get("query") == "ground_row":
        out["query_row"] = int(rng.integers(0, n_rows))
    if "knapsack_budget" in kind:
        lo, hi = kind["knapsack_budget"]
        out["budget"] = float(rng.uniform(lo, hi))
    return out


def _exact_counts(shares: np.ndarray, n: int) -> np.ndarray:
    """``n`` split by ``shares``; the remainder goes to the largest
    fractions, ties to the earlier kind."""
    raw = shares / shares.sum() * n
    counts = np.floor(raw).astype(int)
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[:n - counts.sum()]] += 1
    return counts


def pool_specs(spec: dict, seed: int, n_rows: int) -> list[dict]:
    """The pool of request specs a ``pool`` mix repeats, rank order."""
    pool = spec["pool"]
    sched = np.random.default_rng((int(spec["schedule_seed"]), 1))
    counts = _exact_counts(np.asarray([m["share"] for m in spec["mix"]],
                                      float), int(pool["size"]))
    kinds = sched.permutation(np.repeat(np.arange(len(counts)), counts))
    rng = np.random.default_rng((int(seed), int(spec.get("stream", 2)), 1))
    return [_spec(spec["mix"][int(kind)], rng, n_rows) for kind in kinds]


def open_requests(spec: dict, seed: int, n_rows: int, seconds: float,
                  rate: float | None = None,
                  pool: list[dict] | None = None) -> list[dict]:
    """Every request due in ``[0, seconds)``: ``{"due": s, "k": ..,
    "query_row"?: .., "budget"?: .., "seed": tail seed}``, in due order.
    A ``pool`` mix repeats ``pool`` (by default ``pool_specs`` of ``seed``)."""
    rate = float(spec["rate_per_s"] if rate is None else rate)
    n = int(round(rate * seconds))
    sched = np.random.default_rng(int(spec["schedule_seed"]))
    due = np.sort(sched.uniform(0.0, seconds, n))
    rng = np.random.default_rng((int(seed), int(spec.get("stream", 2))))
    mix = spec["mix"]
    if "pool" in spec:
        if pool is None:
            pool = pool_specs(spec, seed, n_rows)
        w = np.arange(1, len(pool) + 1, dtype=float) ** -float(
            spec["pool"]["zipf_s"])
        picks = sched.choice(len(pool), size=n, p=w / w.sum())
    else:
        counts = _exact_counts(np.asarray([m["share"] for m in mix], float),
                               n)
        kinds = sched.permutation(np.repeat(np.arange(len(mix)), counts))
    out = []
    for i, t in enumerate(due):
        req = (dict(pool[int(picks[i])]) if "pool" in spec
               else _spec(mix[int(kinds[i])], rng, n_rows))
        req["due"] = float(t)
        req["seed"] = int(rng.integers(0, SEED_HI))
        out.append(req)
    return out
