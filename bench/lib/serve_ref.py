"""Plain reference of a served selection request.

A request against a resident session is TREE with the session's round-0
partition and a per-request objective and constraint (the service's
documented contract), importing nothing of the program:

* session: ``key = PRNGKey(session_seed)``, ``key1, kpart, _ = split(key,
  3)``; round 0 is the virtual-location partition of ``n`` items into
  ``M0 = ceil(n / mu)`` machines by ``permutation(kpart, M0 * mu)``;
* ladder: ``m_0 = M0``, ``m_{t+1} = ceil(m_t * k / mu)`` down to one;
* tail: ``chain = fold_in(key1, request_seed)``; each round ``chain, kpart,
  _ = split(chain, 3)``; the valid picks of the round before, in machine
  and pick order, go to slots ``perm[j]`` of ``permutation(kpart, m * mu)``;
* objective: exemplar clustering with eval weights ``w`` (all 1 for an
  unweighted request; query relevance for a query): gain
  ``sum_e w_e max(cur_e - d(e, x), 0) / |E|``, value
  ``mean(w e0) - mean(w cur)``;
* knapsack: an item is a candidate while ``used + weight <= budget + 1e-6``;
* answer: the best machine solution over all rounds, strict improvement;
* a near-tie in fp32 may go either way, one to a request (:mod:`ties`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import ties
from bench.lib.tree_ref import contract

KNAPSACK_TOL = 1e-6


def query_weights(query, E) -> np.ndarray:
    """RBF relevance of each exemplar to the query, mean 1 (the serve
    layer's documented reweighting: bandwidth = median squared distance)."""
    E = np.asarray(E, np.float32)
    q = np.asarray(query, np.float32).reshape(-1)
    d2 = np.sum((E - q[None, :]) ** 2, axis=1, dtype=np.float64)
    scale = float(np.median(d2))
    if scale <= 0.0:
        return np.ones((E.shape[0],), np.float32)
    rel = np.exp(-d2 / scale)
    return np.asarray(rel * (rel.shape[0] / rel.sum()), np.float32)


@functools.partial(jax.jit, static_argnames=("k", "knapsack"))
def greedy_machines(data, wcol, slots, E, ew, budget, force=None, *, k: int,
                    knapsack: bool):
    """Weighted (and knapsack-bounded) greedy on every machine of ``slots``.
    Returns ``(picks, values, gaps, runners)`` and takes ``force`` as
    :func:`tree_ref.greedy_blocks`."""
    m = E.shape[0]
    e0 = jnp.sum(E * E, axis=-1)

    def one(ids, force):
        valid = ids >= 0
        safe = jnp.maximum(ids, 0)
        X = jnp.where(valid[:, None], data[safe], 0.0)
        w = jnp.where(valid, wcol[safe], 0.0)
        x2 = jnp.sum(X * X, axis=-1, keepdims=True)
        d2 = jnp.maximum(x2 + e0[None, :] - 2.0 * contract(X, E),
                         0.0)

        def step(carry, j):
            cm, avail, used = carry
            g = jnp.sum(jnp.maximum(cm[None, :] - d2, 0.0) * ew[None, :],
                        axis=-1) / m
            cand = avail
            if knapsack:
                cand = cand & (used + w <= budget + KNAPSACK_TOL)
            g = jnp.where(cand, g, -1e30)
            b, ok, gap, runner = ties.choose(g, j, force)
            db = jnp.sum((E - X[b][None, :]) ** 2, axis=-1)
            cm = jnp.where(ok, jnp.minimum(cm, db), cm)
            used = jnp.where(ok, used + w[b], used)
            avail = avail & ~(ok & (jnp.arange(ids.shape[0]) == b))
            return (cm, avail, used), (
                jnp.where(ok, b, -1).astype(jnp.int32),
                jnp.where(ok, gap, jnp.inf), runner)

        (cm, _, _), (picks, gaps, runners) = jax.lax.scan(
            step, (e0, valid, jnp.float32(0.0)), jnp.arange(k))
        value = jnp.where(jnp.any(picks >= 0),
                          jnp.mean(ew * e0) - jnp.mean(ew * cm), -jnp.inf)
        return picks, value, gaps, runners

    return jax.vmap(one)(slots, force)


def ladder(M0: int, k: int, mu: int) -> list[int]:
    ms = [M0]
    while ms[-1] > 1:
        ms.append(max(1, math.ceil(ms[-1] * k / mu)))
    return ms


def session_slots(n: int, mu: int, session_seed: int) -> np.ndarray:
    """Round-0 machine slots (global ids, -1 empty) of a session."""
    key = jax.random.PRNGKey(session_seed)
    _, kpart, _ = jax.random.split(key, 3)
    M0 = max(1, math.ceil(n / mu))
    perm = np.asarray(jax.random.permutation(kpart, M0 * mu))
    return np.where(perm < n, perm, -1).reshape(M0, mu).astype(np.int32)


def solve(data_dev, wcol_dev, E, slots0: np.ndarray, *, k: int, mu: int,
          session_seed: int, request_seed: int, ew=None, budget=None,
          machines_per_call: int = 100) -> dict:
    """The answer to one request as global row ids and its value, and under
    ``answers`` those a near-tie leaves open (:mod:`ties`)."""
    E = jnp.asarray(E)
    ew = jnp.ones((E.shape[0],), jnp.float32) if ew is None else \
        jnp.asarray(ew, jnp.float32)
    knapsack = budget is not None
    bud = jnp.float32(budget if knapsack else 0.0)
    key1, _, _ = jax.random.split(jax.random.PRNGKey(session_seed), 3)
    chain, kparts = [jax.random.fold_in(key1, np.int32(request_seed))], [None]
    ms = ladder(slots0.shape[0], k, mu)

    def slots(t, sel):
        if t == 0:
            return slots0
        while len(kparts) <= t:               # chain, kpart, _ per round
            nxt, kpart, _ = jax.random.split(chain[-1], 3)
            chain.append(nxt)
            kparts.append(kpart)
        m = ms[t]
        items = sel.reshape(-1)
        items = items[items >= 0][:m * mu]
        perm = np.asarray(jax.random.permutation(kparts[t], m * mu))
        flat = np.full((m * mu,), -1, np.int64)
        flat[perm[:len(items)]] = items
        return flat.reshape(m, mu).astype(np.int32)

    def greedy(slots_, force):
        outs = []
        for w0 in range(0, len(slots_), machines_per_call):
            part = jnp.asarray(slots_[w0:w0 + machines_per_call])
            f = None if force is None else tuple(
                jnp.asarray(a[w0:w0 + machines_per_call]) for a in force)
            outs.append([np.asarray(a) for a in greedy_machines(
                data_dev, wcol_dev, part, E, ew, bud, f, k=k,
                knapsack=knapsack)])
        return tuple(np.concatenate(a) for a in zip(*outs))

    return ties.reference(ties.Plan(slots, greedy))
