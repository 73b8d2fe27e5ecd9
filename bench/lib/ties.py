"""Near-ties in the plain references, and the answers they leave open.

Greedy takes the candidate of largest gain, lowest index on ties, and TREE
keeps the best machine solution, a later one winning only by strict
improvement.  Two fp32 gains (or values) within rounding of each other are
a tie that the arithmetic decides, not the algorithm: the program and the
reference sum in different orders, may break it differently, and their
paths then part for good (on a TPU v5e, a knapsack greedy's answer moved
by 16 % after one such step, whose two gains differed by 2.5e-8 of their
size in fp64).

So a reference records, at every greedy step, the gap from the best gain
to the runner-up as a share of the best; under ``TAU`` it is a near-tie.
The answers TREE may give are then the reference's own, every machine
solution whose value lies within ``TAU`` of the best, and, one near-tie at
a time (smallest gap first, at most ``MAX_FLIPS``), the same of the run
that takes the runner-up at that step.  Only one near-tie is ever decided
the other way: two on one path are left to fail.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterator

import jax.numpy as jnp
import numpy as np

TAU = 1e-6
MAX_FLIPS = 64
NONE = -5e29          # gains at or below this mark a candidate unavailable


def choose(g, j, force):
    """Step ``j``'s pick from gains ``g`` (unavailable: -1e30): the lowest
    argmax, or ``force = (step, position)``'s position at that step.
    Returns ``(pick, ok, gap, runner_up)``; ``gap`` is ``inf`` where there
    is no runner-up or the best gain is exactly 0."""
    b0 = jnp.argmax(g)
    gb = g[b0]
    rest = jnp.where(jnp.arange(g.shape[0]) == b0, -jnp.inf, g)
    r = jnp.argmax(rest)
    gr = rest[r]
    gap = jnp.where((gb > NONE) & (gr > NONE) & (gb != 0),
                    (gb - gr) / jnp.abs(gb), jnp.inf)
    b = b0 if force is None else jnp.where(j == force[0], force[1], b0)
    return b, g[b] > NONE, gap.astype(jnp.float32), r.astype(jnp.int32)


@dataclasses.dataclass
class Round:
    slots: np.ndarray       # (M, mu) global ids, -1 empty
    picks: np.ndarray       # (M, k) block positions, -1 none
    values: np.ndarray      # (M,) f(S), -inf for a machine that took none
    gaps: np.ndarray        # (M, k) near-tie gap of each step
    runners: np.ndarray     # (M, k) runner-up position of each step

    @property
    def sel(self) -> np.ndarray:
        """(M, k) global ids of each machine's picks, -1 none."""
        return np.where(self.picks >= 0, np.take_along_axis(
            self.slots, np.maximum(self.picks, 0), 1), -1)


@dataclasses.dataclass
class Plan:
    """One TREE instance: ``slots(t, sel)`` places round ``t`` (``sel`` the
    round before's ``Round.sel``, None for round 0); ``greedy(slots,
    force)`` runs every machine of ``slots`` and returns ``(picks, values,
    gaps, runners)``, ``force`` a pair of (M,) step and position arrays."""
    slots: Callable
    greedy: Callable


def run(plan: Plan, base: list | None = None, flip=None) -> list:
    """TREE's rounds, down to one machine.  With ``flip = (t, m, j, pos)``
    the rounds of ``base`` with machine ``m`` of round ``t`` taking
    position ``pos`` at step ``j``; the rounds before ``t`` are reused."""
    rounds: list[Round] = []
    sel = None
    t = 0
    while True:
        if flip is not None and t < flip[0]:
            rnd = base[t]
        elif flip is not None and t == flip[0]:
            b, (_, m, j, pos) = base[t], flip
            one = plan.greedy(b.slots[m:m + 1],
                              (np.array([j], np.int32),
                               np.array([pos], np.int32)))
            rnd = Round(b.slots, *(a.copy() for a in (
                b.picks, b.values, b.gaps, b.runners)))
            for arr, new in zip((rnd.picks, rnd.values, rnd.gaps,
                                 rnd.runners), one):
                arr[m] = new[0]
        else:
            slots = plan.slots(t, sel)
            rnd = Round(slots, *plan.greedy(slots, None))
        rounds.append(rnd)
        if rnd.slots.shape[0] == 1:
            return rounds
        sel = rnd.sel
        t += 1


def best(rounds: list) -> dict:
    """The best machine solution over all rounds, in order, a later one
    winning only by strict improvement."""
    val, ids = -np.inf, None
    for rnd in rounds:
        i = int(np.argmax(rnd.values))                  # lowest on ties
        if rnd.values[i] > val:
            val, ids = float(rnd.values[i]), rnd.sel[i].astype(np.int64)
    return {"ids": ids, "value": val}


def _near_best(rounds: list, top: float) -> Iterator[dict]:
    """Every machine solution within ``TAU`` of ``top``, best first."""
    out = [(float(v), t, i) for t, rnd in enumerate(rounds)
           for i, v in enumerate(rnd.values)
           if np.isfinite(v) and v >= top - TAU * abs(top)]
    for v, t, i in sorted(out, key=lambda x: (-x[0], x[1], x[2])):
        yield {"ids": rounds[t].sel[i].astype(np.int64), "value": v}


def near_ties(rounds: list) -> list:
    """``(gap, t, m, j, runner_up)`` of every near-tie on the path,
    smallest gap first, at most ``MAX_FLIPS``."""
    out = []
    for t, rnd in enumerate(rounds):
        ms, js = np.nonzero((rnd.gaps < TAU) & (rnd.picks >= 0))
        out += [(float(rnd.gaps[m, j]), t, int(m), int(j),
                 int(rnd.runners[m, j])) for m, j in zip(ms, js)]
    return sorted(out)[:MAX_FLIPS]


def answers(plan: Plan, rounds: list) -> Iterator[dict]:
    """The answers TREE may give, the reference's own first; each flipped
    run is made only when the ones before did not serve."""
    yield from _near_best(rounds, best(rounds)["value"])
    for _, t, m, j, r in near_ties(rounds):
        alt = run(plan, rounds, (t, m, j, r))
        yield from _near_best(alt, best(alt)["value"])


def reference(plan: Plan) -> dict:
    """TREE's answer under ``plan`` (``ids``, ``value``) and, under
    ``answers``, every answer a near-tie leaves open, made on demand."""
    rounds = run(plan)
    return {**best(rounds), "answers": answers(plan, rounds)}
