"""Persistent-state fused greedy-selection megakernel (Pallas, TPU target).

## Fused selection

The step-wise greedy oracle launches one :mod:`exemplar_gains` kernel per
selected item: every launch re-streams the candidate block ``X`` and the
eval set ``E`` from HBM, and the subsequent ``cur_min`` refresh streams ``E``
again — 2k full passes over the operands for a k-item selection, O(k·n·m)
HBM traffic when the distance tiles spill.  This kernel runs the *entire*
k-step greedy in a single launch:

  * ``X``, ``E``, ``cur_min`` and the availability mask are loaded into VMEM
    once (constant-index blocks — Pallas fetches them a single time and they
    stay resident for the whole grid),
  * the per-step gains contraction ``-2·X_blk Eᵀ`` runs on the MXU against
    the resident operands,
  * the cross-block argmax is carried in an SMEM scratch accumulator
    (strict ``>`` keeps the lowest index on ties, matching the step-wise
    tie-breaking exactly),
  * the winner's ``cur_min`` refresh and availability clear are applied in
    VMEM before the next step begins.

HBM traffic drops from O(k·n·m) to O((n + m)·d + k·n): the operands cross
HBM once, and per step only the (k, 1) selection scalar leaves the core.
The FLOP count is unchanged (the MXU re-contracts resident tiles), so the
kernel moves the memory roofline, not the compute roofline — which is the
binding constraint for this oracle (see PERF.md).

Grid: ``(k, n/bn)`` — steps major, candidate row blocks minor.  TPU grid
iteration is sequential, so scratch state (``cur_min``, availability, the
argmax accumulator) persists across blocks and steps.

Capacity contract (enforced by ``ops._selection_vmem_bytes`` against the
``VMEM_LIMIT_BYTES`` the kernel is compiled with): ``X``, ``E`` and the
per-row columns must fit VMEM simultaneously, double-buffered and stored in
(sublane, 128-lane) tiles, next to the (bn, m) gains tiles.  A block of
μ = 1,000 rows fits at d = 64 but not at d = 1,024; oversized ``auto``
problems are dispatched to the pure-jnp fused reference instead.

Padding contract: candidate rows are zero-padded with availability 0 (never
selected); ``E`` rows and ``cur_min`` are zero-padded so padded eval columns
contribute ``max(0 - ||x||², 0) = 0`` exactly.  The gains normalisation uses
the *unpadded* eval-set size.

## Constraint extensions

Two hereditary constraint classes reduce to tiny sequential state and ride
inside the kernel (and compose — their feasibility masks AND, matching the
step-wise ``Intersection`` conjunction):

  * **Knapsack** (``weights``/``budget``): the running used-weight lives in
    one SMEM scalar; a step's candidates are masked to
    ``used + w ≤ budget + KNAPSACK_TOL`` before the argmax, and the
    winner's weight is committed alongside the ``cur_min`` refresh.
  * **Partition matroid** (``group_ids``/``caps``): the running per-group
    selection counts live in a ``(G,)`` SMEM int32 vector (caps are small
    static ints, G is tiny); a step's candidates are masked to
    ``counts[gid] < caps[gid]`` via a static unrolled loop over groups
    (SMEM scalar compares broadcast against the block's gid column — no
    gather needed), and the winner's group count is incremented on commit.
    Group ids must lie in ``[0, G)``; the tree layer's independent NumPy
    checker rejects out-of-range ids before they could reach the kernel.

Selection order, ties, and the failure step (no feasible candidate → -1
forever after) are bit-identical to the feasibility-masked step-wise scan
for both classes and their intersection; richer constraint classes keep
the scan path (see ``core/algorithms._fusable``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT_BYTES
from repro.kernels.ref import contract

NEG_INF = -1e30  # python float — jnp scalars would be captured consts in-kernel


def _knapsack_tol() -> float:
    # single source of truth for the feasibility slack — a function-level
    # import (like ref.py's) avoids the kernels↔core import cycle while
    # guaranteeing the fused path can never drift from the scan path
    from repro.core.constraints import KNAPSACK_TOL
    return KNAPSACK_TOL


def _kernel(x_ref, e_ref, cm0_ref, av0_ref, *rest, bn: int, m_true: int,
            compute_dtype, budget: float | None,
            caps: tuple[int, ...] | None, quantized: bool = False,
            tol: float = 0.0):
    # operand/scratch unpacking mirrors the pallas_call assembly below:
    # inputs [w?, gid?, xs?, xz?] → outputs (sel, cmout) → scratch
    # [.., used?, cnt?]
    it = iter(rest)
    w_ref = next(it) if budget is not None else None
    gid_ref = next(it) if caps is not None else None
    xs_ref = next(it) if quantized else None
    xz_ref = next(it) if quantized else None
    sel_ref, cmout_ref, cm_s, av_s, row_s, bv_s, bi_s = (
        next(it), next(it), next(it), next(it), next(it), next(it), next(it))
    used_s = next(it) if budget is not None else None
    cnt_s = next(it) if caps is not None else None
    s = pl.program_id(0)
    i = pl.program_id(1)
    nb = pl.num_programs(1)
    ns = pl.num_programs(0)

    @pl.when((s == 0) & (i == 0))
    def _init():
        cm_s[...] = cm0_ref[...]
        av_s[...] = av0_ref[...]
        if budget is not None:
            used_s[0] = 0.0
        if caps is not None:
            for g in range(len(caps)):
                cnt_s[g] = 0

    def block(ref):
        # rows of candidate block i; a single block is read whole, so narrow
        # dtypes never need a dynamic offset aligned to their packed tiling
        if ref.shape[0] == bn:
            return ref[...]
        return ref[pl.ds(pl.multiple_of(i * bn, bn), bn), :]

    # ---- gains for candidate block i against the resident eval set -------
    x = block(x_ref)                                     # (bn, d) narrow ok
    e = e_ref[...]                                       # (mp, d)
    xf = x.astype(jnp.float32)
    if quantized:
        # in-kernel dequant: VMEM held the narrow rows, the fp32 affine
        # below matches ref.dequantize_rows bit-for-bit (IEEE mult-add)
        xf = xf * block(xs_ref) + block(xz_ref)
    if compute_dtype is not None:
        xc, ec = xf.astype(compute_dtype), e.astype(compute_dtype)
    else:
        xc, ec = xf, e.astype(jnp.float32)
    ef = e.astype(jnp.float32)
    x2 = jnp.sum(xf * xf, axis=-1, keepdims=True)        # (bn, 1)
    e2 = jnp.sum(ef * ef, axis=-1, keepdims=True).T      # (1, mp)
    xy = contract(xc, ec)
    d2 = jnp.maximum(x2 + e2 - 2.0 * xy, 0.0)            # (bn, mp)
    cm = cm_s[...]                                       # (1, mp)
    g = jnp.sum(jnp.maximum(cm - d2, 0.0), axis=-1,
                keepdims=True) / m_true                  # (bn, 1)
    av = block(av_s)                                     # (bn, 1)
    feas = av > 0
    if budget is not None:
        w = block(w_ref)                                 # (bn, 1)
        feas = feas & (used_s[0] + w <= budget + tol)
    if caps is not None:
        gid = block(gid_ref)                             # (bn, 1) int32
        # static unrolled conjunction over the (tiny) group set: each
        # group's open/closed bit is one SMEM scalar compare, broadcast
        # against the block's gid column — no SMEM gather required
        open_any = jnp.zeros_like(gid, dtype=jnp.bool_)
        for grp in range(len(caps)):
            open_any = open_any | ((gid == grp) & (cnt_s[grp] < caps[grp]))
        feas = feas & open_any
    g = jnp.where(feas, g, NEG_INF)

    # ---- cross-block argmax via scratch accumulator ----------------------
    bmax = jnp.max(g)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)
    barg = jnp.min(jnp.where(g == bmax, rows, bn))       # lowest index on ties
    gidx = i * bn + barg
    better = (i == 0) | (bmax > bv_s[0])        # strict: low block wins

    @pl.when(better)
    def _acc():
        bv_s[0] = bmax
        bi_s[0] = gidx
        # keep the leader's fp32 (dequantized) row: one nonzero term per
        # column, so the masked sum is exact and the commit below never
        # slices a single row out of packed bf16/int8 storage
        row_s[...] = jnp.sum(jnp.where(rows == barg, xf, 0.0), axis=0,
                             keepdims=True)

    # ---- end of step: commit winner, refresh state in VMEM ---------------
    @pl.when(i == nb - 1)
    def _finish():
        bi = bi_s[0]
        ok = bv_s[0] > NEG_INF / 2
        xs = row_s[...]                                  # (1, d) winner row
        d2b = jnp.sum((ef - xs) ** 2, axis=-1,
                      keepdims=True).T                   # (1, mp) — objective's
        cur = cm_s[...]                                  # difference form
        cm_s[...] = jnp.where(ok, jnp.minimum(cur, d2b), cur)
        av_cur = av_s[pl.ds(bi, 1), :]
        av_s[pl.ds(bi, 1), :] = jnp.where(ok, jnp.zeros_like(av_cur), av_cur)
        if budget is not None:
            wv = w_ref[pl.ds(bi, 1), :]                  # (1, 1) winner weight
            used_s[0] = jnp.where(ok, used_s[0] + wv[0, 0], used_s[0])
        if caps is not None:
            gv = gid_ref[pl.ds(bi, 1), :][0, 0]          # winner's group id
            for grp in range(len(caps)):
                cnt_s[grp] = jnp.where(ok & (gv == grp), cnt_s[grp] + 1,
                                       cnt_s[grp])
        # step s's pick lands in lane s of the resident (1, k) output row
        lanes = jax.lax.broadcasted_iota(jnp.int32, sel_ref.shape, 1)
        sel_ref[...] = jnp.where(lanes == s, jnp.where(ok, bi, -1),
                                 sel_ref[...])

        @pl.when(s == ns - 1)
        def _flush():
            cmout_ref[...] = cm_s[...]


@functools.partial(jax.jit,
                   static_argnames=("k", "bn", "m_true", "compute_dtype",
                                    "budget", "caps", "interpret"))
def greedy_select_pallas(
    X: jax.Array,        # (n, d) candidates — n % bn == 0 (wrapper pads)
    E: jax.Array,        # (mp, d) eval set — zero-padded rows
    cur_min: jax.Array,  # (mp,)            — zero-padded
    avail: jax.Array,    # (n,) float32 1/0 — padded rows 0
    weights: jax.Array | None = None,  # (n,) knapsack weights — padded rows 0
    group_ids: jax.Array | None = None,  # (n,) int32 group ids — padded 0
    x_scale: jax.Array | None = None,  # (n,) per-row dequant scale — padded 0
    x_zp: jax.Array | None = None,     # (n,) per-row dequant zero-point
    *,
    k: int,
    bn: int = 256,
    m_true: int | None = None,
    compute_dtype=None,
    budget: float | None = None,
    caps: tuple[int, ...] | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    n, d = X.shape
    mp = E.shape[0]
    m_true = mp if m_true is None else m_true
    assert n % bn == 0, (n, bn)
    assert (weights is None) == (budget is None), "weights and budget pair up"
    assert (group_ids is None) == (caps is None), "group_ids and caps pair up"
    assert (x_scale is None) == (x_zp is None), "x_scale and x_zp pair up"
    quantized = x_scale is not None
    grid = (k, n // bn)

    kern = functools.partial(_kernel, bn=bn, m_true=m_true,
                             compute_dtype=compute_dtype, budget=budget,
                             caps=caps, quantized=quantized,
                             tol=_knapsack_tol() if budget is not None else 0.0)
    in_specs = [
        pl.BlockSpec((n, d), lambda s, i: (0, 0)),   # X resident
        pl.BlockSpec((mp, d), lambda s, i: (0, 0)),  # E resident
        pl.BlockSpec((1, mp), lambda s, i: (0, 0)),  # cur_min seed
        pl.BlockSpec((n, 1), lambda s, i: (0, 0)),   # availability seed
    ]
    scratch = [
        pltpu.VMEM((1, mp), jnp.float32),            # running cur_min
        pltpu.VMEM((n, 1), jnp.float32),             # availability
        pltpu.VMEM((1, d), jnp.float32),             # leading row, fp32
        pltpu.SMEM((1,), jnp.float32),               # best value so far
        pltpu.SMEM((1,), jnp.int32),                 # best index so far
    ]
    operands = [X, E, cur_min[None, :], avail[:, None]]
    if budget is not None:
        in_specs.append(pl.BlockSpec((n, 1), lambda s, i: (0, 0)))  # weights
        scratch.append(pltpu.SMEM((1,), jnp.float32))    # used weight so far
        operands.append(weights.astype(jnp.float32)[:, None])
    if caps is not None:
        in_specs.append(pl.BlockSpec((n, 1), lambda s, i: (0, 0)))  # gids
        scratch.append(pltpu.SMEM((len(caps),), jnp.int32))  # per-group counts
        operands.append(group_ids.astype(jnp.int32)[:, None])
    if quantized:
        in_specs.append(pl.BlockSpec((n, 1), lambda s, i: (0, 0)))  # x_scale
        in_specs.append(pl.BlockSpec((n, 1), lambda s, i: (0, 0)))  # x_zp
        operands.append(x_scale.astype(jnp.float32)[:, None])
        operands.append(x_zp.astype(jnp.float32)[:, None])
    sel, cm = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, k), lambda s, i: (0, 0)),   # per-step selection
            pl.BlockSpec((1, mp), lambda s, i: (0, 0)),  # final cur_min
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k), jnp.int32),
            jax.ShapeDtypeStruct((1, mp), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands)
    return sel[0], cm[0]
