"""β-nice single-machine algorithms (paper Def. 3.2), shape-static JAX.

All algorithms operate on a ``(cap, d)`` item block ``T`` with a ``(cap,)``
validity mask and return at most ``k`` selected block positions.  Shapes never
depend on data, so every algorithm can be jit'd, vmapped over machines, and
shard_mapped over the device mesh.

β-niceness (established in the paper / its citations):
  * :func:`greedy` — classic greedy with *consistent tie-breaking*
    (``argmax`` → lowest index): **1-nice**.  Equals lazy greedy output.
  * :func:`threshold_greedy` — Badanidiyuru & Vondrák descending-threshold
    algorithm: **(1+2ε)-nice**.
  * :func:`stochastic_greedy` — Mirzasoleiman et al. 2015; no β-nice proof,
    used empirically (paper §4.4).

TPU adaptation note (DESIGN.md §3): the paper runs *lazy* greedy per machine
to cut oracle calls on CPUs.  On TPU, one greedy step evaluates all ``cap``
marginal gains as a single MXU contraction (the exemplar_gains kernel), so
plain greedy *is* the fast variant — priority queues would serialise the VPU.
Lazy greedy (identical output) lives in :mod:`repro.core.reference` and is
used for large centralized-baseline runs on CPU.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.constraints import (DynamicKnapsack, DynamicPartitionMatroid,
                                    Intersection, Knapsack, PartitionMatroid,
                                    Unconstrained)

NEG_INF = -1e30


class SelectResult(NamedTuple):
    """Result of a single-machine selection run."""

    sel_idx: jax.Array    # (k,) int32 block positions, -1 where unused
    sel_mask: jax.Array   # (k,) bool
    value: jax.Array      # f(selected)
    oracle_calls: jax.Array  # scalar int32 — number of marginal-gain evals
    depth: jax.Array      # scalar int32 — sequential solve depth: the number
    #   of dependent kernel launches (argmax steps / τ-levels) the solve
    #   cannot parallelise away.  Greedy variants pay k; threshold tiers pay
    #   one init pass plus their τ-ladder length.


def _tree_where(pred, a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.where(
            jnp.reshape(pred, (1,) * x.ndim) if x.ndim else pred, x, y),
        a, b)


def _dummy_attrs(T: jax.Array) -> jax.Array:
    return jnp.zeros((T.shape[0], 1), jnp.float32)


def _dequant_block(T: jax.Array, qmeta: jax.Array | None) -> jax.Array:
    """Narrow candidate block → fp32 (identity for fp32 blocks).

    ``qmeta`` is the per-row ``(cap, qcols)`` dequant params gathered
    out-of-band by the source (scale, zero-point for int8; zero-width for
    bf16, whose upcast is exact).  The scan algorithms dequantize once up
    front, so their per-row values are bit-equal to the fused kernels'
    in-kernel dequant of the same bytes.
    """
    Tf = T.astype(jnp.float32)
    if qmeta is not None and qmeta.shape[1] >= 2:
        Tf = Tf * qmeta[:, 0:1] + qmeta[:, 1:2]
    return Tf


def _fused_quant_kwargs(qmeta: jax.Array | None) -> dict:
    if qmeta is None or qmeta.shape[1] < 2:
        return {}
    return {"x_scale": qmeta[:, 0], "x_zp": qmeta[:, 1]}


# ---------------------------------------------------------------------------
# GREEDY — 1-nice
# ---------------------------------------------------------------------------


def _fused_parts(constraint) -> tuple | None:
    """Decompose a constraint into fused-encodable parts, or None.

    Fused encodings exist for :class:`Knapsack` (one SMEM used-weight
    scalar) and :class:`PartitionMatroid` (one SMEM per-group count
    vector); an :class:`Intersection` of at most one of each composes
    (masks AND = the scan's conjunction).  Anything else — duplicated
    classes (two knapsacks need two scalars the kernel doesn't carry),
    nested intersections, custom constraints — returns None.

    The Dynamic* variants (traced per-request parameters, serve layer)
    count as their static family: same encoding, the parameter simply
    rides as an operand instead of a compile-time constant (the kernel
    wrapper dispatches traced parameters to the fused reference impl).
    """
    parts = (constraint.parts if isinstance(constraint, Intersection)
             else (constraint,))
    n_knap = sum(isinstance(p, _KNAPSACK_KINDS) for p in parts)
    n_part = sum(isinstance(p, _PARTITION_KINDS) for p in parts)
    if n_knap + n_part != len(parts):
        return None
    if n_knap > 1 or n_part > 1:
        return None
    return parts


_KNAPSACK_KINDS = (Knapsack, DynamicKnapsack)
_PARTITION_KINDS = (PartitionMatroid, DynamicPartitionMatroid)


def _fused_constraint_kwargs(constraint, attrs) -> dict:
    """``fused_select`` operands for a fused-encodable constraint."""
    kw = {}
    for p in _fused_parts(constraint):
        if isinstance(p, _KNAPSACK_KINDS):
            kw["weights"] = attrs[:, p.col]
            kw["budget"] = p.budget
        else:
            kw["group_ids"] = attrs[:, p.col]
            kw["caps"] = p.caps
    return kw


def _fusable(obj, constraint, attrs) -> bool:
    """May the fused single-launch selection replace the step-wise scan?

    Unconstrained selection fuses whenever the objective exposes a
    ``fused_select`` hook, attribute columns or not.  Of the hereditary
    constraint classes, :class:`Knapsack` (a weight operand + SMEM
    used-weight scalar —
    ``fused_knapsack`` on the objective advertises it) and
    :class:`PartitionMatroid` (a group-id operand + SMEM per-group count
    vector — ``fused_partition``) have fused encodings, as does an
    :class:`Intersection` of at most one of each; everything else takes
    the feasibility-masked step-wise scan below.
    """
    if not (getattr(obj, "rowwise_gains", False)
            and hasattr(obj, "fused_select")):
        return False
    if constraint is None or isinstance(constraint, Unconstrained):
        return True            # attributes matter only to a constraint
    parts = _fused_parts(constraint)
    if parts is None or attrs is None:
        return False
    return all(getattr(obj, "fused_knapsack"
                       if isinstance(p, _KNAPSACK_KINDS)
                       else "fused_partition", False) for p in parts)


def greedy(obj, T: jax.Array, mask: jax.Array, k: int, *,
           constraint=None, attrs: jax.Array | None = None,
           fused: bool | None = None,
           qmeta: jax.Array | None = None) -> SelectResult:
    """Classic greedy with consistent (lowest-index) tie-breaking.

    Supports any hereditary constraint; the cardinality bound is the loop
    bound ``k`` (for pure cardinality problems pass ``constraint=None``).

    ``fused=None`` (auto) routes unconstrained — and, when the objective
    advertises the matching encoding, knapsack- / partition-matroid- /
    knapsack∩partition-constrained — selection through the objective's
    ``fused_select`` hook: the whole k-step loop runs as one fused kernel
    launch (kernels/greedy_select.py), with output bit-identical to the
    step-wise scan, tie-breaking and oracle-call counts included.  Other
    constraint classes always take the feasibility-masked scan.
    ``fused=False`` forces the scan; ``fused=True`` asserts the fast path.

    ``qmeta`` marks a quantized candidate block (``(cap, qcols)`` per-row
    dequant params, zero-width for bf16): the fused path ships the narrow
    block with in-kernel dequant, the scan path dequantizes up front —
    both see identical fp32 values for the same bytes.
    """
    if fused is None:
        fused = _fusable(obj, constraint, attrs)
    if fused:
        assert _fusable(obj, constraint, attrs), (
            "fused=True needs a rowwise objective with a fused_select hook "
            "and an unconstrained, fused-knapsack, or fused-partition "
            "selection")
        qkw = _fused_quant_kwargs(qmeta)
        if constraint is not None and not isinstance(constraint, Unconstrained):
            sel_idx, sel_mask, value, calls = obj.fused_select(
                T, mask, k, **_fused_constraint_kwargs(constraint, attrs),
                **qkw)
        else:
            sel_idx, sel_mask, value, calls = obj.fused_select(T, mask, k,
                                                               **qkw)
        return SelectResult(sel_idx, sel_mask, value, calls, jnp.int32(k))

    cap = T.shape[0]
    T = _dequant_block(T, qmeta)
    constraint = constraint or Unconstrained()
    attrs = _dummy_attrs(T) if attrs is None else attrs

    def step(carry, _):
        state, cstate, avail, calls = carry
        cand = avail & constraint.feasible(cstate, attrs)
        gains = obj.gains(state, T, cand)
        best = jnp.argmax(gains)                       # lowest index on ties
        ok = gains[best] > NEG_INF / 2                 # any candidate at all?
        new_state = obj.update(state, T, best)
        state = _tree_where(ok, new_state, state)
        cstate = _tree_where(ok, constraint.update(cstate, attrs, best), cstate)
        avail = avail & ~(ok & (jnp.arange(cap) == best))
        calls = calls + jnp.sum(cand.astype(jnp.int32))
        idx = jnp.where(ok, best.astype(jnp.int32), jnp.int32(-1))
        return (state, cstate, avail, calls), (idx, ok)

    init = (obj.init_state(T, mask), constraint.init_state(), mask,
            jnp.int32(0))
    (state, _, _, calls), (sel_idx, sel_mask) = jax.lax.scan(
        step, init, None, length=k)
    return SelectResult(sel_idx, sel_mask, obj.value(state), calls,
                        jnp.int32(k))


# ---------------------------------------------------------------------------
# STOCHASTIC GREEDY (lazier-than-lazy) — paper §4.4 subprocedure
# ---------------------------------------------------------------------------


def stochastic_greedy(obj, T: jax.Array, mask: jax.Array, k: int,
                      key: jax.Array, *, eps: float = 0.5,
                      constraint=None,
                      attrs: jax.Array | None = None,
                      qmeta: jax.Array | None = None) -> SelectResult:
    """Each step draws a uniform random candidate subset of size
    s = ⌈(cap/k)·ln(1/ε)⌉ and takes its best element.

    For row-wise objectives the gain evaluation is restricted to the sampled
    rows (a genuinely smaller MXU contraction); otherwise gains are computed
    masked-full (same semantics, SIMD-style).

    Hereditary constraints restrict both the sample pool and the take: a
    step samples from ``avail ∩ feasible(cstate)`` and commits the
    constraint state on every successful take.
    """
    import math

    cap = T.shape[0]
    T = _dequant_block(T, qmeta)
    s = min(cap, max(1, math.ceil(cap / k * math.log(1.0 / eps))))
    rowwise = getattr(obj, "rowwise_gains", False)
    constraint = constraint or Unconstrained()
    attrs = _dummy_attrs(T) if attrs is None else attrs

    def step(carry, key_t):
        state, cstate, avail, calls = carry
        cand = avail & constraint.feasible(cstate, attrs)
        # uniform random s-subset of candidate positions:
        scores = jax.random.uniform(key_t, (cap,))
        scores = jnp.where(cand, scores, 2.0)         # non-candidates to end
        _, sub_idx = jax.lax.top_k(-scores, s)        # s smallest scores
        if rowwise:
            # ascending indices ⇒ the T[sub_idx] gather walks memory forward
            sub_idx = jnp.sort(sub_idx)
            sub_cand = cand[sub_idx]
            g = obj.gains(state, T[sub_idx], sub_cand)
        else:
            sub_cand = cand[sub_idx]
            g = obj.gains(state, T, cand)[sub_idx]
            g = jnp.where(sub_cand, g, NEG_INF)
        b = jnp.argmax(g)
        best = sub_idx[b]
        ok = g[b] > NEG_INF / 2
        state = _tree_where(ok, obj.update(state, T, best), state)
        cstate = _tree_where(ok, constraint.update(cstate, attrs, best), cstate)
        avail = avail & ~(ok & (jnp.arange(cap) == best))
        calls = calls + jnp.sum(sub_cand.astype(jnp.int32))
        return (state, cstate, avail, calls), (
            jnp.where(ok, best.astype(jnp.int32), jnp.int32(-1)), ok)

    keys = jax.random.split(key, k)
    init = (obj.init_state(T, mask), constraint.init_state(), mask,
            jnp.int32(0))
    (state, _, _, calls), (sel_idx, sel_mask) = jax.lax.scan(step, init, keys)
    return SelectResult(sel_idx, sel_mask, obj.value(state), calls,
                        jnp.int32(k))


# ---------------------------------------------------------------------------
# THRESHOLD GREEDY (Badanidiyuru & Vondrák 2014) — (1+2ε)-nice
# ---------------------------------------------------------------------------


def threshold_greedy(obj, T: jax.Array, mask: jax.Array, k: int, *,
                     eps: float = 0.1, constraint=None,
                     attrs: jax.Array | None = None,
                     qmeta: jax.Array | None = None) -> SelectResult:
    """Descending thresholds τ = d_max·(1-ε)^l down to (ε/2k)·d_max; one
    sequential pass per threshold adding every item whose current marginal
    gain meets τ (stopping at k items).

    Hereditary constraints gate each take on single-item feasibility under
    the running constraint state (the oracle only fires — and is only
    counted — for currently-feasible items), committing the state on take.
    """
    import math

    cap = T.shape[0]
    T = _dequant_block(T, qmeta)
    n_levels = max(1, math.ceil(math.log(2.0 * k / eps) / eps))
    constraint = constraint or Unconstrained()
    attrs = _dummy_attrs(T) if attrs is None else attrs

    state0 = obj.init_state(T, mask)
    cstate0 = constraint.init_state()
    cand0 = mask & constraint.feasible(cstate0, attrs)
    g0 = obj.gains(state0, T, cand0)
    d_max = jnp.maximum(jnp.max(g0), 1e-12)

    def gain_at(state, i):
        if getattr(obj, "rowwise_gains", False):
            return obj.gains(state, T[i][None, :], jnp.ones((1,), bool))[0]
        return obj.gains(state, T, jnp.ones((cap,), bool))[i]

    def item_pass(i, carry):
        state, cstate, avail, count, calls, sel_idx, tau = carry
        feas = constraint.feasible(cstate, attrs[i][None, :])[0]
        # the marginal-gain oracle fires for every still-available feasible
        # item, so count it *before* the take flips the bit
        calls = calls + (avail[i] & feas).astype(jnp.int32)
        g = gain_at(state, i)
        take = avail[i] & feas & (count < k) & (g >= tau)
        state = _tree_where(take, obj.update(state, T, i), state)
        cstate = _tree_where(take, constraint.update(cstate, attrs, i), cstate)
        sel_idx = jnp.where(take, sel_idx.at[count].set(i), sel_idx)
        count = count + take.astype(jnp.int32)
        avail = avail & ~(take & (jnp.arange(cap) == i))
        return state, cstate, avail, count, calls, sel_idx, tau

    def level(l, carry):
        state, cstate, avail, count, calls, sel_idx = carry
        tau = d_max * (1.0 - eps) ** l.astype(jnp.float32)
        state, cstate, avail, count, calls, sel_idx, _ = jax.lax.fori_loop(
            0, cap, item_pass,
            (state, cstate, avail, count, calls, sel_idx, tau))
        return state, cstate, avail, count, calls, sel_idx

    sel_idx = jnp.full((k,), -1, jnp.int32)
    # the d_max pass above evaluated one gain per valid feasible item
    init_calls = jnp.sum(cand0.astype(jnp.int32))
    state, _, _, count, calls, sel_idx = jax.lax.fori_loop(
        0, n_levels, level,
        (state0, cstate0, mask, jnp.int32(0), init_calls, sel_idx))
    sel_mask = jnp.arange(k) < count
    # depth: the d_max init pass plus one sequential item sweep per τ-level
    # (each level's fori_loop is one dependent chain regardless of takes)
    return SelectResult(sel_idx, sel_mask, obj.value(state), calls,
                        jnp.int32(1 + n_levels))


# ---------------------------------------------------------------------------
# THRESHOLD BATCH — low-adaptivity tier (τ-ladder of batch accepts)
# ---------------------------------------------------------------------------


def threshold_batch(obj, T: jax.Array, mask: jax.Array, k: int, *,
                    eps: float = 0.5, constraint=None,
                    attrs: jax.Array | None = None,
                    qmeta: jax.Array | None = None) -> SelectResult:
    """Batch-accepting descending-threshold selection (adaptive sequencing).

    One kernel launch per τ-level scores *all* candidates against the
    current threshold and accepts the prefix-feasible batch of qualifying
    items in-kernel; the driver only lowers τ ← τ(1−ε).  Sequential solve
    depth is O(log(2k/ε)/ε) launches instead of greedy's k, at a
    (1−1/e−O(ε)) quality floor — the same ladder as
    :func:`threshold_greedy` but with the per-level item sweep collapsed
    into a single launch.

    Unlike the scan algorithms this tier *requires* a row-wise objective
    exposing the ``fused_threshold_select`` hook (the batch-accept
    semantics live in kernels/threshold_select.py), and constraints must
    be fused-encodable (knapsack / partition matroid / one of each) —
    anything else raises rather than silently degrading to a sequential
    path.
    """
    if not (getattr(obj, "rowwise_gains", False)
            and hasattr(obj, "fused_threshold_select")):
        raise ValueError(
            "threshold_batch needs a row-wise objective with a "
            f"fused_threshold_select hook; {type(obj).__name__} has none "
            "(use algorithm='threshold_greedy' for the sequential ladder)")
    ckw = {}
    if constraint is not None and not isinstance(constraint, Unconstrained):
        parts = _fused_parts(constraint)
        if parts is None:
            raise ValueError(
                "threshold_batch supports knapsack, partition-matroid, and "
                "one-of-each intersection constraints; "
                f"{type(constraint).__name__} has no fused encoding")
        if attrs is None:
            raise ValueError(
                "constrained threshold_batch needs per-item attrs")
        ckw = _fused_constraint_kwargs(constraint, attrs)
    qkw = _fused_quant_kwargs(qmeta)
    sel_idx, sel_mask, value, calls, launches = obj.fused_threshold_select(
        T, mask, k, eps=eps, **ckw, **qkw)
    # depth: the d_max init pass plus the launches the ladder actually ran
    # (early-exits when k fills or candidates drain — data-dependent)
    return SelectResult(sel_idx, sel_mask, value, calls,
                        jnp.int32(1) + launches.astype(jnp.int32))


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


#: kwargs each algorithm actually consumes; anything else passed explicitly
#: to :func:`run_algorithm` is an error, not a silent no-op.
ALGORITHM_KWARGS = {
    "greedy": frozenset({"constraint", "attrs", "fused", "qmeta"}),
    "stochastic_greedy": frozenset({"key", "eps", "constraint", "attrs",
                                    "qmeta"}),
    "threshold_greedy": frozenset({"eps", "constraint", "attrs", "qmeta"}),
    "threshold_batch": frozenset({"eps", "constraint", "attrs", "qmeta"}),
}


def driver_kwargs(name: str, *, key=None, eps=None) -> dict:
    """The subset of uniform driver state the named algorithm accepts.

    Driver layers (distributed rounds, the tree, the serve tier) hold a
    PRNG key and an ε for every machine regardless of algorithm; forwarding
    an inapplicable one through :func:`run_algorithm` is a hard error, so
    they filter here instead of special-casing each algorithm inline.
    Unknown names return ``{}`` — :func:`run_algorithm` owns that error.
    """
    allowed = ALGORITHM_KWARGS.get(name, frozenset())
    kw = {}
    if "key" in allowed and key is not None:
        kw["key"] = key
    if "eps" in allowed and eps is not None:
        kw["eps"] = eps
    return kw


def run_algorithm(name: str, obj, T, mask, k, *, key=None, eps=None,
                  constraint=None, attrs=None,
                  fused: bool | None = None,
                  qmeta=None) -> SelectResult:
    """Dispatch to a selection algorithm by name, rejecting misuse.

    Unknown names and algorithm-inapplicable kwargs (a PRNG ``key`` for
    anything but stochastic_greedy, ``eps`` for plain greedy, ``fused``
    for anything but greedy) raise ``ValueError`` instead of being
    silently dropped.  ``eps=None`` means "the algorithm's own default"
    (they differ: 0.1 for threshold_greedy, 0.5 elsewhere).
    """
    allowed = ALGORITHM_KWARGS.get(name)
    if allowed is None:
        raise ValueError(
            f"unknown algorithm {name!r}; expected one of "
            f"{sorted(ALGORITHM_KWARGS)}")
    extras = [n for n, v in (("key", key), ("eps", eps), ("fused", fused))
              if v is not None and n not in allowed]
    if extras:
        raise ValueError(
            f"algorithm {name!r} does not accept {extras} "
            f"(it takes {sorted(allowed)})")
    ekw = {} if eps is None else {"eps": eps}
    if name == "greedy":
        return greedy(obj, T, mask, k, constraint=constraint, attrs=attrs,
                      fused=fused, qmeta=qmeta)
    if name == "stochastic_greedy":
        if key is None:
            raise ValueError("stochastic_greedy needs a PRNG key")
        return stochastic_greedy(obj, T, mask, k, key, **ekw,
                                 constraint=constraint, attrs=attrs,
                                 qmeta=qmeta)
    if name == "threshold_greedy":
        return threshold_greedy(obj, T, mask, k, **ekw,
                                constraint=constraint, attrs=attrs,
                                qmeta=qmeta)
    return threshold_batch(obj, T, mask, k, **ekw, constraint=constraint,
                           attrs=attrs, qmeta=qmeta)
