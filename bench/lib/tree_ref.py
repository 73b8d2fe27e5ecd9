"""Plain reference of TREE-BASED COMPRESSION (Algorithm 1) with greedy.

Written from the paper and the documented partition/key contract, importing
nothing of the program:

* key chain: ``key = PRNGKey(seed)``; every round ``key, kpart, kalg =
  split(key, 3)``;
* round 0: ``L = ceil(n / mu)`` machines of ``mu`` slots; slot ``s`` holds
  item ``perm[s]`` of ``perm = permutation(kpart, L * mu)`` (``-1`` where
  ``perm[s] >= n``) — the paper's virtual-location scheme;
* round t >= 1: the union of round t-1's selections, in machine order and
  selection order, is placed so that item ``j`` lands on slot ``perm[j]``
  of ``perm = permutation(kpart, L_t * mu)``, ``L_t = ceil(|A_t| / mu)``;
* every machine runs greedy (lowest index on ties) for ``k`` steps on the
  exemplar objective ``f(S) = mean ||e||^2 - mean min(||e||^2, d(e, S))``;
* the answer is the best machine solution over all rounds, a later one
  winning only by strict improvement; rounds end with one machine;
* a near-tie in fp32 may go either way, one to a run (:mod:`ties`).

The reference works on global row ids on the device, with the ground set
uploaded once; its contractions are fp32 at ``HIGHEST``, as the
configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import ties

MAX_PIECE_BYTES = 1 << 30        # host->device transfers stay below 1 GiB


def contract(X, E):
    """(n, d) x (m, d) -> (n, m) in fp32 at ``HIGHEST``, as stated."""
    return jax.lax.dot_general(X, E, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("k",))
def greedy_blocks(data, slots, E, force=None, *, k: int):
    """Greedy on every machine of ``slots`` ((M, mu) global ids, -1 empty).

    Returns ``(picks, values, gaps, runners)``: (M, k) block positions (-1
    none), (M,) f(S) (-inf for a machine that picked nothing), and each
    step's near-tie gap and runner-up (:func:`ties.choose`).  ``force``,
    a pair of (M,) arrays, makes step ``force[0]`` take ``force[1]``.
    """
    m = E.shape[0]
    e0 = jnp.sum(E * E, axis=-1)

    def one(ids, force):
        valid = ids >= 0
        X = jnp.where(valid[:, None], data[jnp.maximum(ids, 0)], 0.0)
        x2 = jnp.sum(X * X, axis=-1, keepdims=True)
        d2 = jnp.maximum(x2 + e0[None, :] - 2.0 * contract(X, E),
                         0.0)

        def step(carry, j):
            cm, avail = carry
            g = jnp.sum(jnp.maximum(cm[None, :] - d2, 0.0), axis=-1) / m
            g = jnp.where(avail, g, -1e30)
            b, ok, gap, runner = ties.choose(g, j, force)
            db = jnp.sum((E - X[b][None, :]) ** 2, axis=-1)
            cm = jnp.where(ok, jnp.minimum(cm, db), cm)
            avail = avail & ~(ok & (jnp.arange(ids.shape[0]) == b))
            return (cm, avail), (jnp.where(ok, b, -1).astype(jnp.int32),
                                 jnp.where(ok, gap, jnp.inf), runner)

        (cm, _), (picks, gaps, runners) = jax.lax.scan(
            step, (e0, valid), jnp.arange(k))
        value = jnp.where(jnp.any(picks >= 0),
                          jnp.mean(e0) - jnp.mean(cm), -jnp.inf)
        return picks, value, gaps, runners

    return jax.vmap(one)(slots, force)


def upload(x: np.ndarray, device=None) -> jax.Array:
    """The ground set on one device, sent in pieces below 1 GiB and written
    in place into one buffer (no second copy on the device)."""
    n, d = x.shape
    rows = max(1, MAX_PIECE_BYTES // (d * x.itemsize))
    buf = jax.device_put(jnp.zeros((n, d), x.dtype), device)
    put = jax.jit(lambda b, p, s: jax.lax.dynamic_update_slice(b, p, (s, 0)),
                  donate_argnums=0)
    for s in range(0, n, rows):
        buf = put(buf, jax.device_put(x[s:s + rows], device), s)
    return buf


def tree(data_dev, E, seed: int, *, k: int, mu: int,
         machines_per_call: int = 100) -> dict:
    """Algorithm 1 on ``data_dev``: the answer as global row ids, its value,
    and under ``answers`` those a near-tie leaves open (:mod:`ties`)."""
    n = int(data_dev.shape[0])
    E = jnp.asarray(E)
    chain, kparts = [jax.random.PRNGKey(seed)], []

    def slots(t, sel):
        while len(kparts) <= t:               # key, kpart, _ per round
            key, kpart, _ = jax.random.split(chain[-1], 3)
            chain.append(key)
            kparts.append(kpart)
        items = None if sel is None else sel.reshape(-1)
        if items is not None:
            items = items[items >= 0]
        count = n if items is None else len(items)
        L = max(1, math.ceil(count / mu))
        perm = np.asarray(jax.random.permutation(kparts[t], L * mu))
        if items is None:
            out = np.where(perm < n, perm, -1)
        else:
            out = np.full((L * mu,), -1, np.int64)
            out[perm[:count]] = items
        return out.reshape(L, mu).astype(np.int32)

    def greedy(slots_, force):
        outs = []
        for w0 in range(0, len(slots_), machines_per_call):
            part = jnp.asarray(slots_[w0:w0 + machines_per_call])
            f = None if force is None else tuple(
                jnp.asarray(a[w0:w0 + machines_per_call]) for a in force)
            outs.append([np.asarray(a) for a in
                         greedy_blocks(data_dev, part, E, f, k=k)])
        return tuple(np.concatenate(a) for a in zip(*outs))

    return ties.reference(ties.Plan(slots, greedy))
