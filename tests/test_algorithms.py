"""β-nice algorithms: equivalence with numpy references, β-nice properties,
constraint handling, and approximation quality vs brute force."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (ExemplarClustering, ActiveSetSelection,
                        WeightedCoverage, greedy, stochastic_greedy,
                        threshold_greedy, Knapsack, PartitionMatroid)
from repro.core.reference import (ExemplarOracle, LogDetOracle, lazy_greedy,
                                  plain_greedy)


def _setup(n=200, d=6, ne=64, seed=0):
    r = np.random.default_rng(seed)
    data = r.standard_normal((n, d)).astype(np.float32)
    E = data[r.choice(n, min(ne, n), replace=False)]
    return data, E


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_jax_greedy_equals_numpy_greedy_and_lazy(seed):
    data, E = _setup(seed=seed)
    k = 8
    obj = ExemplarClustering(jnp.asarray(E))
    res = greedy(obj, jnp.asarray(data), jnp.ones((len(data),), bool), k)
    ref_p = plain_greedy(ExemplarOracle(data, E), np.arange(len(data)), k)
    ref_l = lazy_greedy(ExemplarOracle(data, E), np.arange(len(data)), k)
    assert list(np.asarray(res.sel_idx)) == list(ref_p.sel_idx)
    assert list(ref_p.sel_idx) == list(ref_l.sel_idx)  # lazy == plain (Minoux)
    np.testing.assert_allclose(float(res.value), ref_p.value, rtol=1e-4)
    # lazy evaluates strictly fewer gains
    assert ref_l.oracle_calls < ref_p.oracle_calls


def test_jax_greedy_logdet_equals_numpy():
    data, _ = _setup(n=80, seed=3)
    data = (data * 0.15).astype(np.float32)
    k = 6
    obj = ActiveSetSelection(k_max=k)
    res = greedy(obj, jnp.asarray(data), jnp.ones((len(data),), bool), k)
    ref = plain_greedy(LogDetOracle(data), np.arange(len(data)), k)
    assert list(np.asarray(res.sel_idx)) == list(ref.sel_idx)
    np.testing.assert_allclose(float(res.value), ref.value, rtol=1e-3)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 100), drop=st.integers(0, 30))
def test_beta_nice_consistency(seed, drop):
    """Def 3.2 property (1): removing a NON-selected item never changes the
    greedy output (consistent tie-breaking)."""
    data, E = _setup(n=40, seed=seed)
    k = 5
    obj = ExemplarClustering(jnp.asarray(E))
    T = jnp.asarray(data)
    mask = jnp.ones((40,), bool)
    res = greedy(obj, T, mask, k)
    sel = set(np.asarray(res.sel_idx)[np.asarray(res.sel_mask)].tolist())
    if drop in sel:
        return  # only non-selected removals are constrained
    res2 = greedy(obj, T, mask.at[drop].set(False), k)
    assert list(np.asarray(res.sel_idx)) == list(np.asarray(res2.sel_idx))


def test_beta_nice_marginal_bound():
    """Def 3.2 property (2) with β=1 for GREEDY: any unselected item has
    marginal gain ≤ f(A(T))/k."""
    data, E = _setup(n=60, seed=9)
    k = 6
    obj = ExemplarClustering(jnp.asarray(E))
    T = jnp.asarray(data)
    res = greedy(obj, T, jnp.ones((60,), bool), k)
    # rebuild final state
    state = obj.init_state(T, jnp.ones((60,), bool))
    for i in np.asarray(res.sel_idx):
        state = obj.update(state, T, jnp.int32(int(i)))
    gains = np.asarray(obj.gains(state, T, jnp.ones((60,), bool)))
    sel = set(np.asarray(res.sel_idx).tolist())
    unsel = [i for i in range(60) if i not in sel]
    fS = float(res.value)
    assert max(gains[unsel]) <= fS / k + 1e-5


def test_greedy_approximation_vs_bruteforce():
    """(1 - 1/e) bound on weighted coverage with exact OPT."""
    r = np.random.default_rng(4)
    n, U, k = 14, 10, 3
    inc = (r.random((n, U)) < 0.35).astype(np.float32)
    w = jnp.asarray(r.random(U).astype(np.float32))
    obj = WeightedCoverage(w)
    T = jnp.asarray(inc)
    res = greedy(obj, T, jnp.ones((n,), bool), k)
    opt = max(float(obj.evaluate(T[jnp.asarray(c)], jnp.ones((k,), bool)))
              for c in itertools.combinations(range(n), k))
    assert float(res.value) >= (1 - 1 / np.e) * opt - 1e-6


def test_stochastic_greedy_quality_and_calls():
    data, E = _setup(n=400, seed=5)
    k = 10
    obj = ExemplarClustering(jnp.asarray(E))
    T = jnp.asarray(data)
    g = greedy(obj, T, jnp.ones((400,), bool), k)
    s = stochastic_greedy(obj, T, jnp.ones((400,), bool), k,
                          jax.random.PRNGKey(0), eps=0.1)
    assert float(s.value) >= 0.85 * float(g.value)
    assert int(s.oracle_calls) < int(g.oracle_calls)


def test_threshold_greedy_quality():
    data, E = _setup(n=300, seed=6)
    k = 8
    obj = ExemplarClustering(jnp.asarray(E))
    T = jnp.asarray(data)
    g = greedy(obj, T, jnp.ones((300,), bool), k)
    t = threshold_greedy(obj, T, jnp.ones((300,), bool), k, eps=0.1)
    # BV14: (1 - 1/e - ε) guarantee vs OPT; vs greedy it is ≥ (1-1/e-ε)/(1-1/e)
    assert float(t.value) >= 0.8 * float(g.value)


def test_knapsack_constraint_respected():
    data, E = _setup(n=100, seed=7)
    obj = ExemplarClustering(jnp.asarray(E))
    T = jnp.asarray(data)
    r = np.random.default_rng(7)
    w = r.uniform(0.2, 1.0, 100).astype(np.float32)
    attrs = jnp.asarray(w[:, None])
    budget = 2.0
    res = greedy(obj, T, jnp.ones((100,), bool), 20,
                 constraint=Knapsack(budget), attrs=attrs)
    sel = np.asarray(res.sel_idx)[np.asarray(res.sel_mask)]
    assert w[sel].sum() <= budget + 1e-5
    assert len(sel) > 0


def test_partition_matroid_respected():
    data, E = _setup(n=90, seed=8)
    obj = ExemplarClustering(jnp.asarray(E))
    T = jnp.asarray(data)
    groups = np.arange(90) % 3
    attrs = jnp.asarray(groups[:, None].astype(np.float32))
    caps = (2, 3, 1)
    res = greedy(obj, T, jnp.ones((90,), bool), 10,
                 constraint=PartitionMatroid(caps), attrs=attrs)
    sel = np.asarray(res.sel_idx)[np.asarray(res.sel_mask)]
    for g in range(3):
        assert (groups[sel] == g).sum() <= caps[g]
