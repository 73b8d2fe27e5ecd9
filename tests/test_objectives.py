"""Property tests: the objectives really are monotone submodular, and the
incremental oracle state matches the set-function evaluation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (ActiveSetSelection, ExemplarClustering,
                        FacilityLocation, WeightedCoverage)

N, D, NE = 24, 5, 16


def _data(seed):
    r = np.random.default_rng(seed)
    return jnp.asarray(r.standard_normal((N, D)).astype(np.float32))


def _objective(name, seed):
    data = _data(seed)
    if name == "exemplar":
        return ExemplarClustering(data[:NE]), data
    if name == "activeset":
        return ActiveSetSelection(k_max=N), data * 0.2
    if name == "facility":
        return FacilityLocation(data[:NE], h=1.5), data
    r = np.random.default_rng(seed)
    inc = (r.random((N, 7)) < 0.4).astype(np.float32)
    return WeightedCoverage(jnp.asarray(r.random(7).astype(np.float32))), \
        jnp.asarray(inc)


def _f(obj, T, S_idx):
    """Set-function value via the incremental oracle."""
    mask = jnp.ones((T.shape[0],), bool)
    state = obj.init_state(T, mask)
    for i in S_idx:
        state = obj.update(state, T, jnp.int32(i))
    return float(obj.value(state))


OBJ_NAMES = ["exemplar", "activeset", "facility", "coverage"]


@pytest.mark.parametrize("name", OBJ_NAMES)
@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_monotone_submodular(name, data):
    seed = data.draw(st.integers(0, 50))
    obj, T = _objective(name, seed)
    items = data.draw(st.lists(st.integers(0, N - 1), min_size=0, max_size=6,
                               unique=True))
    x = data.draw(st.integers(0, N - 1).filter(lambda i: i not in items))
    y = data.draw(st.integers(0, N - 1).filter(
        lambda i: i not in items and i != x))
    X = items
    Y = items + [y]
    fX = _f(obj, T, X)
    fY = _f(obj, T, Y)
    # monotone: f(Y) >= f(X) for X ⊆ Y
    assert fY >= fX - 1e-4
    # diminishing returns: Δ(x|X) >= Δ(x|Y)
    gain_X = _f(obj, T, X + [x]) - fX
    gain_Y = _f(obj, T, Y + [x]) - fY
    assert gain_X >= gain_Y - 1e-3


@pytest.mark.parametrize("name", OBJ_NAMES)
def test_gains_match_value_delta(name):
    obj, T = _objective(name, 7)
    mask = jnp.ones((N,), bool)
    state = obj.init_state(T, mask)
    for step in range(4):
        gains = obj.gains(state, T, mask)
        i = int(jnp.argmax(gains))
        before = float(obj.value(state))
        state2 = obj.update(state, T, jnp.int32(i))
        after = float(obj.value(state2))
        np.testing.assert_allclose(after - before, float(gains[i]),
                                   rtol=2e-3, atol=2e-4)
        state = state2
        mask = mask.at[i].set(False)


@pytest.mark.parametrize("name", ["exemplar", "activeset", "coverage"])
def test_evaluate_matches_incremental(name):
    obj, T = _objective(name, 3)
    idx = [2, 5, 11, 17]
    inc = _f(obj, T, idx)
    rows = T[jnp.asarray(idx)]
    ev = float(obj.evaluate(rows, jnp.ones((len(idx),), bool)))
    np.testing.assert_allclose(ev, inc, rtol=2e-3, atol=2e-4)


def test_nonnegative_and_empty_zero():
    for name in OBJ_NAMES:
        obj, T = _objective(name, 1)
        assert abs(_f(obj, T, [])) < 1e-5
        assert _f(obj, T, [0, 3]) >= -1e-5
