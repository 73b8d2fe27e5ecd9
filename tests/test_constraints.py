"""Constraint engine: hereditary-family properties of core/constraints.py
(heredity under removal, intersection correctness, knapsack boundary,
partition cap saturation) and the constraint subsystem threaded through the
tree pipeline (streaming == resident bit-identity per constraint class,
fused-knapsack == scan, independent NumPy feasibility on every coreset,
constrained baselines, checkpoint resume with attribute-carrying rows)."""
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (ArraySource, ChunkedSource, ExemplarClustering,
                        Intersection, Knapsack, PartitionMatroid, TreeConfig,
                        Unconstrained, centralized_greedy, check_feasible,
                        constraint_from_spec, randgreedi, tree_maximize)
from repro.core.algorithms import greedy, run_algorithm
from repro.core.constraints import KNAPSACK_TOL, attr_dim
from repro.kernels import ops


def _setup(n=400, d=8, ne=96, seed=0):
    r = np.random.default_rng(seed)
    data = r.standard_normal((n, d)).astype(np.float32)
    E = data[r.choice(n, min(ne, n), replace=False)]
    return data, ExemplarClustering(jnp.asarray(E))


def _attrs(n, seed=0, groups=4):
    r = np.random.default_rng(seed)
    w = r.uniform(0.2, 1.0, n).astype(np.float32)
    g = r.integers(0, groups, n).astype(np.float32)
    return np.stack([w, g], axis=1)


def _greedy_feasible_set(constraint, attrs, size, seed):
    """Build a feasible set by random feasible insertions (jit interface)."""
    r = np.random.default_rng(seed)
    attrs_j = jnp.asarray(attrs)
    cstate = constraint.init_state()
    chosen = []
    for i in r.permutation(len(attrs)):
        if len(chosen) >= size:
            break
        if bool(np.asarray(constraint.feasible(cstate, attrs_j))[i]):
            cstate = constraint.update(cstate, attrs_j, i)
            chosen.append(int(i))
    return chosen


CLASSES = {
    "knapsack": lambda: Knapsack(2.0),
    "partition": lambda: PartitionMatroid((2, 3, 1, 2), col=1),
    "intersection": lambda: Intersection(
        (Knapsack(3.0), PartitionMatroid((2, 2, 2, 2), col=1))),
}


# ---------------------------------------------------------------------------
# hereditary-family properties (pure constraint layer)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(CLASSES))
@pytest.mark.parametrize("seed", range(5))
def test_heredity_feasible_under_removal(name, seed):
    """S ∈ ℐ ⇒ every S \\ {x} ∈ ℐ — the defining property, checked with the
    independent NumPy verifier on randomly built feasible sets."""
    constraint = CLASSES[name]()
    attrs = _attrs(60, seed=seed)
    chosen = _greedy_feasible_set(constraint, attrs, size=8, seed=seed)
    assert chosen, "degenerate: empty feasible set"
    mask = np.zeros(len(attrs), bool)
    mask[chosen] = True
    ok, detail = check_feasible(constraint, attrs, mask)
    assert ok, detail
    for drop in chosen:                       # remove any single element
        sub = mask.copy()
        sub[drop] = False
        ok, detail = check_feasible(constraint, attrs, sub)
        assert ok, f"heredity violated dropping {drop}: {detail}"


@pytest.mark.parametrize("seed", range(3))
def test_intersection_equals_conjunction(seed):
    """Intersection.feasible/update/check must agree with the component-wise
    conjunction at every step of a random insertion sequence."""
    p1, p2 = Knapsack(2.5), PartitionMatroid((2, 2, 1, 3), col=1)
    inter = Intersection((p1, p2))
    attrs = _attrs(40, seed=seed)
    attrs_j = jnp.asarray(attrs)
    s1, s2, si = p1.init_state(), p2.init_state(), inter.init_state()
    r = np.random.default_rng(seed)
    taken = np.zeros(len(attrs), bool)
    for i in r.permutation(len(attrs))[:15]:
        f1 = np.asarray(p1.feasible(s1, attrs_j))
        f2 = np.asarray(p2.feasible(s2, attrs_j))
        fi = np.asarray(inter.feasible(si, attrs_j))
        np.testing.assert_array_equal(fi, f1 & f2)
        if fi[i]:
            s1 = p1.update(s1, attrs_j, i)
            s2 = p2.update(s2, attrs_j, i)
            si = inter.update(si, attrs_j, i)
            taken[i] = True
    ok_i, _ = check_feasible(inter, attrs, taken)
    ok_1, _ = check_feasible(p1, attrs, taken)
    ok_2, _ = check_feasible(p2, attrs, taken)
    assert ok_i == (ok_1 and ok_2) == True  # noqa: E712


def test_knapsack_exact_budget_boundary():
    """An item whose weight equals the budget exactly must be admissible
    (the tolerance exists for fp32 accumulation, not to forbid equality),
    and after taking it nothing else fits."""
    budget = 1.5
    c = Knapsack(budget)
    attrs = jnp.asarray(np.array([[1.5], [0.1], [1.5]], np.float32))
    st = c.init_state()
    feas = np.asarray(c.feasible(st, attrs))
    assert feas.all(), "exact-budget item rejected at the start"
    st = c.update(st, attrs, 0)
    assert not np.asarray(c.feasible(st, attrs)).any()
    ok, _ = check_feasible(c, np.asarray(attrs), np.array([True, False, False]))
    assert ok
    ok, _ = check_feasible(c, np.asarray(attrs),
                           np.array([True, True, False]))
    assert not ok, "checker admits an over-budget set"
    # greedy under the same instance: selects the boundary item it values
    data, obj = _setup(n=3)
    res = greedy(obj, jnp.asarray(data), jnp.ones((3,), bool), 3,
                 constraint=c, attrs=attrs)
    w = np.asarray(attrs)[:, 0]
    sel = np.asarray(res.sel_idx)[np.asarray(res.sel_mask)]
    assert w[sel].sum() <= budget + KNAPSACK_TOL * max(1, len(sel))


def test_partition_matroid_cap_saturation():
    """With k larger than Σcaps and every group populated, greedy fills each
    group exactly to its cap — no quota leaks, no early stop."""
    caps = (2, 1, 3)
    n = 90
    data, obj = _setup(n=n, seed=3)
    gid = (np.arange(n) % len(caps)).astype(np.float32)
    attrs = jnp.asarray(gid[:, None])
    c = PartitionMatroid(caps)
    res = greedy(obj, jnp.asarray(data), jnp.ones((n,), bool), 20,
                 constraint=c, attrs=attrs)
    sel = np.asarray(res.sel_idx)[np.asarray(res.sel_mask)]
    counts = np.bincount(gid[sel].astype(int), minlength=len(caps))
    np.testing.assert_array_equal(counts, caps)   # saturated, not just ≤
    ok, detail = check_feasible(c, np.asarray(attrs)[sel],
                                np.ones(len(sel), bool))
    assert ok, detail


def test_knapsack_checker_tolerates_fp32_accumulation_at_large_budgets():
    """The NumPy checker's slack must cover what the fp32 selection loop can
    legitimately admit: at large budget magnitudes the running-sum rounding
    (~k·ulp) dwarfs the absolute KNAPSACK_TOL, and a genuine violation must
    still be rejected."""
    budget = 1000.0
    c = Knapsack(budget)
    k = 32
    # adversarial weights: exact fp64 total lands just over budget while the
    # fp32 sequential sum stays admissible (each partial sum rounds down)
    w32 = np.full(k, np.float32(budget / k))
    run = np.float32(0.0)
    for x in w32:                                     # fp32 loop admission
        assert run + x <= np.float32(budget) + KNAPSACK_TOL
        run += x
    ok, detail = check_feasible(c, w32[:, None].astype(np.float32),
                                np.ones(k, bool))
    assert ok, f"checker rejects a selection its own loop admitted: {detail}"
    # a real violation (one whole extra item) is still caught
    big = np.concatenate([w32, [np.float32(budget / k)]])
    ok, _ = check_feasible(c, big[:, None], np.ones(k + 1, bool))
    assert not ok


def test_partition_checker_rejects_out_of_range_ids():
    """The NumPy checker must return an infeasibility verdict — not crash —
    for group ids outside [0, len(caps)); the jit path silently clamps
    those, so the checker is the only layer that can surface them."""
    c = PartitionMatroid((2, 2))
    bad_hi = np.array([[2.0], [0.0]], np.float32)   # id == len(caps)
    ok, detail = check_feasible(c, bad_hi, np.array([True, True]))
    assert not ok and "outside" in detail
    bad_lo = np.array([[-1.0], [1.0]], np.float32)
    ok, _ = check_feasible(c, bad_lo, np.array([True, False]))
    assert not ok
    ok, _ = check_feasible(c, bad_hi, np.array([False, True]))  # masked out
    assert ok


def test_spec_parser_roundtrip():
    c = constraint_from_spec("knapsack:budget=2.5:col=1")
    assert isinstance(c, Knapsack) and c.budget == 2.5 and c.col == 1
    c = constraint_from_spec("partition:caps=2,3,4")
    assert isinstance(c, PartitionMatroid) and c.caps == (2, 3, 4)
    c = constraint_from_spec(
        "intersection:knapsack:budget=1.0+partition:caps=1,1:col=1")
    assert isinstance(c, Intersection) and len(c.parts) == 2
    assert constraint_from_spec("none") is None
    assert attr_dim(c) == 2 and attr_dim(None) == 0
    assert attr_dim(Unconstrained()) == 0
    with pytest.raises(ValueError):
        constraint_from_spec("cardinality:k=3")


# ---------------------------------------------------------------------------
# constraint subsystem through the tree pipeline
# ---------------------------------------------------------------------------


def _assert_identical(a, b):
    np.testing.assert_array_equal(a.sel_rows, b.sel_rows)
    np.testing.assert_array_equal(a.sel_mask, b.sel_mask)
    assert a.value == b.value                      # bit-identical, no rtol
    assert a.oracle_calls == b.oracle_calls
    assert a.rounds == b.rounds
    if a.sel_attrs is not None or b.sel_attrs is not None:
        np.testing.assert_array_equal(a.sel_attrs, b.sel_attrs)


@pytest.mark.parametrize("name", sorted(CLASSES))
def test_streaming_bit_identical_per_constraint_class(name):
    """The tentpole invariant: streaming and all-resident drivers agree bit
    for bit under every hereditary constraint class, and the coreset passes
    the independent NumPy feasibility check."""
    constraint = CLASSES[name]()
    data, obj = _setup(n=401, seed=1)
    attrs = _attrs(len(data), seed=1)
    cfg = TreeConfig(k=8, capacity=60, seed=5)
    resident = tree_maximize(obj, jnp.asarray(data), cfg,
                             constraint=constraint, attrs=attrs)
    streamed = tree_maximize(obj,
                             ChunkedSource.from_array(data, 97, attrs=attrs),
                             cfg, wave_machines=3, constraint=constraint)
    _assert_identical(resident, streamed)
    assert streamed.ingest.attr_dim == attrs.shape[1]
    assert streamed.ingest.peak_wave_bytes == (
        streamed.ingest.peak_wave_rows * (data.shape[1] + attrs.shape[1]) * 4)
    ok, detail = check_feasible(constraint, streamed.sel_attrs,
                                streamed.sel_mask)
    assert ok, detail
    assert np.asarray(streamed.sel_mask).any(), "empty constrained coreset"


@pytest.mark.parametrize("alg", ["stochastic_greedy", "threshold_greedy"])
def test_constrained_streaming_other_algorithms(alg):
    """Constraint state lives inside the stochastic/threshold loops too —
    same bit-identity and feasibility bar as the greedy path."""
    data, obj = _setup(n=350, seed=2)
    attrs = _attrs(len(data), seed=2)
    constraint = Knapsack(2.5)
    cfg = TreeConfig(k=6, capacity=50, seed=4, algorithm=alg, eps=0.3)
    resident = tree_maximize(obj, jnp.asarray(data), cfg,
                             constraint=constraint, attrs=attrs)
    streamed = tree_maximize(obj,
                             ChunkedSource.from_array(data, 64, attrs=attrs),
                             cfg, wave_machines=3, constraint=constraint)
    _assert_identical(resident, streamed)
    ok, detail = check_feasible(constraint, resident.sel_attrs,
                                resident.sel_mask)
    assert ok, detail


def test_fused_knapsack_bit_identical_to_scan():
    """The megakernel's weight-operand encoding must reproduce the
    feasibility-masked scan exactly: selection order, ties, value bits,
    and the reconstructed oracle-call count."""
    data, obj = _setup(n=128, seed=4)
    T = jnp.asarray(data)
    msk = jnp.ones((len(data),), bool)
    attrs = jnp.asarray(_attrs(len(data), seed=4)[:, :1])
    for budget in (0.5, 2.0, 1e9):      # binding, loose, never-binding
        c = Knapsack(budget)
        scan = greedy(obj, T, msk, 20, constraint=c, attrs=attrs, fused=False)
        fused = greedy(obj, T, msk, 20, constraint=c, attrs=attrs, fused=True)
        np.testing.assert_array_equal(np.asarray(scan.sel_idx),
                                      np.asarray(fused.sel_idx))
        np.testing.assert_array_equal(np.asarray(scan.sel_mask),
                                      np.asarray(fused.sel_mask))
        assert float(scan.value) == float(fused.value)
        assert int(scan.oracle_calls) == int(fused.oracle_calls)


def test_fused_partition_bit_identical_to_scan():
    """The megakernel's per-group count-vector encoding must reproduce the
    feasibility-masked scan exactly: selection order, ties, value bits,
    the reconstructed oracle-call count, and the failure step when every
    group saturates (caps are exhausted before k)."""
    data, obj = _setup(n=128, seed=4)
    T = jnp.asarray(data)
    msk = jnp.ones((len(data),), bool)
    attrs = jnp.asarray(_attrs(len(data), seed=4))
    for caps in ((1, 1, 1, 1),          # saturating: failure step before k
                 (3, 2, 4, 1),          # uneven binding caps
                 (99, 99, 99, 99)):     # never-binding
        c = PartitionMatroid(caps, col=1)
        scan = greedy(obj, T, msk, 20, constraint=c, attrs=attrs, fused=False)
        fused = greedy(obj, T, msk, 20, constraint=c, attrs=attrs, fused=True)
        np.testing.assert_array_equal(np.asarray(scan.sel_idx),
                                      np.asarray(fused.sel_idx))
        np.testing.assert_array_equal(np.asarray(scan.sel_mask),
                                      np.asarray(fused.sel_mask))
        assert float(scan.value) == float(fused.value)
        assert int(scan.oracle_calls) == int(fused.oracle_calls)
        if caps == (1, 1, 1, 1):
            assert int(np.asarray(scan.sel_mask).sum()) == 4  # Σcaps


def test_fused_intersection_knapsack_partition_bit_identical():
    """An Intersection of one knapsack + one partition matroid fuses (both
    operand encodings ride the kernel, masks AND) and must match the
    scan's conjunction semantics bit for bit."""
    data, obj = _setup(n=96, seed=6)
    T = jnp.asarray(data)
    msk = jnp.ones((len(data),), bool)
    attrs = jnp.asarray(_attrs(len(data), seed=6))
    c = Intersection((Knapsack(2.0, col=0),
                      PartitionMatroid((3, 3, 3, 3), col=1)))
    scan = greedy(obj, T, msk, 16, constraint=c, attrs=attrs, fused=False)
    fused = greedy(obj, T, msk, 16, constraint=c, attrs=attrs, fused=True)
    np.testing.assert_array_equal(np.asarray(scan.sel_idx),
                                  np.asarray(fused.sel_idx))
    np.testing.assert_array_equal(np.asarray(scan.sel_mask),
                                  np.asarray(fused.sel_mask))
    assert float(scan.value) == float(fused.value)
    assert int(scan.oracle_calls) == int(fused.oracle_calls)


def test_fused_dispatch_falls_back_for_unfusable_constraints():
    """Only knapsack, partition matroid, and an intersection of at most
    one of each have fused encodings: anything else must take the
    feasibility-masked scan, and fused=True must refuse rather than
    silently drop the constraint."""
    from repro.core.algorithms import _fusable
    data, obj = _setup(n=64, seed=5)
    attrs = jnp.asarray(_attrs(len(data), seed=5))
    assert _fusable(obj, None, None)
    assert _fusable(obj, None, attrs)       # attributes without a constraint
    assert _fusable(obj, Knapsack(1.0), attrs)
    assert _fusable(obj, PartitionMatroid((2, 2, 2, 2), col=1), attrs)
    assert _fusable(obj, Intersection((Knapsack(1.0),)), attrs)
    assert _fusable(obj, Intersection(
        (Knapsack(1.0), PartitionMatroid((2, 2, 2, 2), col=1))), attrs)
    # two knapsacks would need two SMEM used-weight scalars — scan path
    assert not _fusable(obj, Intersection(
        (Knapsack(1.0, col=0), Knapsack(2.0, col=0))), attrs)
    assert not _fusable(obj, Intersection(
        (PartitionMatroid((2, 2), col=1), PartitionMatroid((3, 3), col=1))),
        attrs)
    with pytest.raises(AssertionError):
        greedy(obj, jnp.asarray(data), jnp.ones((len(data),), bool), 4,
               constraint=Intersection((Knapsack(1.0), Knapsack(2.0))),
               attrs=attrs, fused=True)


def test_ops_greedy_select_knapsack_pallas_matches_ref():
    """Kernel-level contract: interpret-mode Pallas == pure-jnp reference
    for the weight-operand path (ties, failure steps included)."""
    r = np.random.default_rng(7)
    X = jnp.asarray(r.standard_normal((96, 8)).astype(np.float32))
    E = jnp.asarray(r.standard_normal((48, 8)).astype(np.float32))
    w = jnp.asarray(r.uniform(0.1, 1.0, 96).astype(np.float32))
    cm0 = jnp.sum(E * E, axis=-1)
    mask = jnp.ones((96,), bool)
    s_ref, c_ref = ops.greedy_select(X, E, cm0, mask, 12, impl="ref",
                                     weights=w, budget=1.5)
    s_pal, c_pal = ops.greedy_select(X, E, cm0, mask, 12, impl="pallas",
                                     weights=w, budget=1.5)
    np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_pal))
    np.testing.assert_allclose(np.asarray(c_ref), np.asarray(c_pal),
                               rtol=1e-6)
    # knapsack masking ⇒ prefix property: once a step fails, all later fail
    sel = np.asarray(s_ref)
    first_fail = np.argmax(sel < 0) if (sel < 0).any() else len(sel)
    assert (sel[first_fail:] < 0).all()


def test_ops_greedy_select_partition_pallas_matches_ref():
    """Kernel-level contract: interpret-mode Pallas == pure-jnp reference
    for the per-group count-vector path, alone and composed with the
    weight operand (padding rows exercise the inert-gid contract)."""
    r = np.random.default_rng(8)
    X = jnp.asarray(r.standard_normal((100, 8)).astype(np.float32))  # pads
    E = jnp.asarray(r.standard_normal((48, 8)).astype(np.float32))
    gid = jnp.asarray(r.integers(0, 3, 100).astype(np.float32))
    w = jnp.asarray(r.uniform(0.1, 1.0, 100).astype(np.float32))
    cm0 = jnp.sum(E * E, axis=-1)
    mask = jnp.ones((100,), bool)
    for kw in (dict(group_ids=gid, caps=(4, 2, 3)),
               dict(group_ids=gid, caps=(2, 2, 2),
                    weights=w, budget=2.0)):
        s_ref, c_ref = ops.greedy_select(X, E, cm0, mask, 12, impl="ref",
                                         **kw)
        s_pal, c_pal = ops.greedy_select(X, E, cm0, mask, 12, impl="pallas",
                                         **kw)
        np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_pal))
        np.testing.assert_allclose(np.asarray(c_ref), np.asarray(c_pal),
                                   rtol=1e-6)
        # selected group counts never exceed the caps
        sel = np.asarray(s_ref)
        gids = np.asarray(gid)[sel[sel >= 0]].astype(int)
        counts = np.bincount(gids, minlength=len(kw["caps"]))
        assert (counts <= np.asarray(kw["caps"])).all(), (counts, kw)


def test_constrained_tree_uses_fused_partition_path():
    """End-to-end: a partition-constrained tree run dispatches the fused
    selection (no scan fallback on this hot path) and stays bit-identical
    to the scan-forced driver."""
    import repro.core.algorithms as alg_lib
    data, obj = _setup(n=240, seed=9)
    attrs = _attrs(len(data), seed=9)
    c = PartitionMatroid((4, 4, 4, 4), col=1)
    cfg = TreeConfig(k=8, capacity=40, seed=3)
    res = tree_maximize(obj, jnp.asarray(data), cfg, constraint=c,
                        attrs=attrs)
    assert alg_lib._fusable(obj, c, jnp.asarray(attrs))  # the hot path fuses
    ok, detail = check_feasible(c, res.sel_attrs, res.sel_mask)
    assert ok, detail
    # scan-forced reference: monkeypatch _fusable to refuse, outputs equal
    real = alg_lib._fusable
    alg_lib._fusable = lambda *a: False
    try:
        ref_res = tree_maximize(obj, jnp.asarray(data), cfg, constraint=c,
                                attrs=attrs)
    finally:
        alg_lib._fusable = real
    np.testing.assert_array_equal(res.sel_rows, ref_res.sel_rows)
    np.testing.assert_array_equal(res.sel_mask, ref_res.sel_mask)
    assert res.value == ref_res.value
    assert res.oracle_calls == ref_res.oracle_calls


def test_constrained_baselines_and_source_identity():
    """randgreedi: chunked-source partition pass == all-resident array pass
    bit for bit, and both comparison columns respect the constraint."""
    data, obj = _setup(n=360, seed=6)
    attrs = _attrs(len(data), seed=6)
    c = Knapsack(3.0)
    key = jax.random.PRNGKey(3)
    b_arr = randgreedi(obj, jnp.asarray(data), 8, 6, key, constraint=c,
                       attrs=attrs)
    b_src = randgreedi(obj, ChunkedSource.from_array(data, 100, attrs=attrs),
                       8, 6, key, constraint=c, machine_chunk=2)
    assert float(b_arr.value) == float(b_src.value)
    np.testing.assert_array_equal(np.asarray(b_arr.sel_rows),
                                  np.asarray(b_src.sel_rows))
    np.testing.assert_array_equal(np.asarray(b_arr.sel_attrs),
                                  np.asarray(b_src.sel_attrs))
    for b in (b_arr, b_src):
        ok, detail = check_feasible(c, np.asarray(b.sel_attrs),
                                    np.asarray(b.sel_mask))
        assert ok, detail
    cg = centralized_greedy(obj, jnp.asarray(data), 8, constraint=c,
                            attrs=attrs)
    ok, detail = check_feasible(c, np.asarray(cg.sel_attrs),
                                np.asarray(cg.sel_mask))
    assert ok, detail


def test_randgreedi_unconstrained_source_identity():
    """The chunked partition pass must also match for the plain (no attrs)
    baseline — the column the PR-2 scaling sweep reports."""
    data, obj = _setup(n=300, seed=8)
    key = jax.random.PRNGKey(9)
    b_arr = randgreedi(obj, jnp.asarray(data), 6, 5, key)
    b_src = randgreedi(obj, ArraySource(data), 6, 5, key, machine_chunk=2)
    assert float(b_arr.value) == float(b_src.value)
    np.testing.assert_array_equal(np.asarray(b_arr.sel_rows),
                                  np.asarray(b_src.sel_rows))
    assert b_arr.sel_attrs is None and b_src.sel_attrs is None


def test_constrained_checkpoint_resume_bit_identical(tmp_path):
    """Attribute columns ride through round checkpoints: a crash-resumed
    constrained run finishes bit-identically to the uninterrupted one."""
    from repro.core import tree as tree_lib

    data, obj = _setup(n=500, seed=9)
    attrs = _attrs(len(data), seed=9)
    c = Knapsack(3.0)
    mk = lambda **kw: TreeConfig(k=8, capacity=60, seed=9, **kw)
    full = tree_maximize(obj, jnp.asarray(data), mk(), constraint=c,
                         attrs=attrs)
    assert full.rounds >= 2

    td = str(tmp_path)
    real_save = tree_lib._save_round
    state = {"crashed": False}

    def crash_after_round_1(d, round_idx, *a):
        real_save(d, round_idx, *a)
        if round_idx == 1 and not state["crashed"]:
            state["crashed"] = True
            raise KeyboardInterrupt("simulated crash")

    tree_lib._save_round = crash_after_round_1
    try:
        with pytest.raises(KeyboardInterrupt):
            tree_maximize(obj, jnp.asarray(data), mk(checkpoint_dir=td),
                          constraint=c, attrs=attrs)
    finally:
        tree_lib._save_round = real_save

    resumed = tree_maximize(obj, jnp.asarray(data),
                            mk(checkpoint_dir=td, resume=True),
                            constraint=c, attrs=attrs)
    np.testing.assert_array_equal(resumed.sel_rows, full.sel_rows)
    np.testing.assert_array_equal(resumed.sel_attrs, full.sel_attrs)
    assert resumed.value == full.value
    assert resumed.oracle_calls == full.oracle_calls


def test_attrs_without_constraint_rejected():
    data, obj = _setup(n=80)
    with pytest.raises(AssertionError):
        tree_maximize(obj, jnp.asarray(data), TreeConfig(k=4, capacity=40),
                      attrs=_attrs(len(data)))


def test_constraint_without_attrs_rejected():
    data, obj = _setup(n=80)
    with pytest.raises(AssertionError):
        tree_maximize(obj, jnp.asarray(data), TreeConfig(k=4, capacity=40),
                      constraint=Knapsack(1.0))


def test_run_algorithm_threads_constraint_everywhere():
    """All subprocedure loops honor the constraint (not just greedy)."""
    data, obj = _setup(n=120, seed=11)
    T = jnp.asarray(data)
    attrs = jnp.asarray(_attrs(len(data), seed=11))
    c = PartitionMatroid((1, 1, 1, 1), col=1)
    per_alg = {"greedy": {},
               "stochastic_greedy": {"key": jax.random.PRNGKey(0),
                                     "eps": 0.3},
               "threshold_greedy": {"eps": 0.3},
               "threshold_batch": {"eps": 0.3}}
    for alg, kw in per_alg.items():
        res = run_algorithm(alg, obj, T, jnp.ones((len(data),), bool), 10,
                            constraint=c, attrs=attrs, **kw)
        sel = np.asarray(res.sel_idx)[np.asarray(res.sel_mask)]
        ok, detail = check_feasible(c, np.asarray(attrs)[sel],
                                    np.ones(len(sel), bool))
        assert ok, (alg, detail)
        assert len(sel) <= 4
