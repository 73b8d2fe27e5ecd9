"""Threshold-batch selection megakernel (Pallas, TPU target).

## Low-adaptivity selection

The fused greedy megakernel (kernels/greedy_select.py) still pays k
sequential argmax steps per solve — the grid's step axis is the adaptive
depth.  This kernel is one rung of the *threshold-batch* tier: a single
launch scores **all** candidates against the current threshold τ and
commits a whole batch of qualifying items, so the driver only lowers τ
geometrically (τ ← τ(1−ε)) between launches — O(log(n·Δ)/ε) launches
instead of k (see core/algorithms.threshold_batch for the ladder).

Grid: ``(n/bn,)`` — candidate row blocks, sequential.  TPU grid iteration
is sequential, so the running state (``cur_min``, the stop flag, the
knapsack used-weight, per-group counts, the selected-so-far count)
persists across blocks in VMEM/SMEM scratch, and each block's gains see
the ``cur_min`` produced by every earlier block's accepted rows.

Per block, with block-entry state:

  * *qualify*: available ∧ gain ≥ τ ∧ singly feasible (knapsack slack /
    open partition group) against the block-entry constraint scalars,
  * *prefix-stop accept*: inclusive cumulative counts / weights /
    per-group counts over the qualifying items are checked against
    ``k`` / ``budget`` / ``caps``; every qualifying item before the first
    cumulative violation is accepted, the violation sets a launch-wide
    stop flag (later blocks accept nothing).  Because the cumulative
    sums only move at qualifying items, the violation predicate is
    monotone within the block and the accepted set is prefix-feasible by
    construction — ``check_feasible`` holds on every return.
  * *batch fold*: accepted rows fold into ``cur_min`` as a masked
    row-min over the block's contraction-form distance tile (no
    per-item refresh order to match — this kernel has no step-wise
    counterpart; its contract is bit-identity to ``ref.threshold_select``
    at the same ``bn``).

Scalar launch state rides in two tiny SMEM operands — ``fscal`` (1, 2)
fp32 ``[τ, used]`` and ``iscal`` (1, 1+G) int32 ``[count, counts…]`` —
copied into SMEM scratch at block 0, so the τ-ladder driver can run as a
``lax.while_loop`` without retracing.  The kernel returns only
``(accept, cur_min_out)``; the driver recomputes the scalar-state updates
from ``accept`` in plain jnp, which keeps driver state identical across
impls by construction.

Capacity contract: E stays VMEM-resident (``ops.threshold_select``
reuses the greedy VMEM budget check); X streams block-by-block, so the
kernel admits larger candidate blocks than the greedy megakernel.
Padding contract: padded candidate rows carry availability 0 (never
qualify), padded eval columns are zero (inert in gains and in the
row-min fold, since ``min(0, d2) = 0`` keeps them at 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT_BYTES
from repro.kernels.ref import contract, prefix_sum

INF = float("inf")  # python float — jnp scalars would be captured consts


def _knapsack_tol() -> float:
    from repro.core.constraints import KNAPSACK_TOL
    return KNAPSACK_TOL


def _prefix_sum(v):
    # inclusive prefix sum down the sublane axis; Mosaic has no cumsum, so
    # this is the log-step form ref.prefix_sum computes with the same adds
    return prefix_sum(v, roll=lambda a, off: pltpu.roll(a, off, 0))


def _kernel(x_ref, e_ref, cm0_ref, av_ref, fscal_ref, iscal_ref, *rest,
            bn: int, m_true: int, compute_dtype, k: int,
            budget: float | None, caps: tuple[int, ...] | None,
            quantized: bool = False, tol: float = 0.0):
    # operand/scratch unpacking mirrors the pallas_call assembly below:
    # inputs [w?, gid?, xs?, xz?] → outputs (acc, cmout) → scratch
    # [cm_s, stop_s, count_s, used_s?, cnt_s?]
    it = iter(rest)
    w_ref = next(it) if budget is not None else None
    gid_ref = next(it) if caps is not None else None
    xs_ref = next(it) if quantized else None
    xz_ref = next(it) if quantized else None
    acc_ref, cmout_ref, cm_s, stop_s, count_s = (
        next(it), next(it), next(it), next(it), next(it))
    used_s = next(it) if budget is not None else None
    cnt_s = next(it) if caps is not None else None
    i = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        cm_s[...] = cm0_ref[...]
        stop_s[0] = 0
        count_s[0] = iscal_ref[0, 0]
        if budget is not None:
            used_s[0] = fscal_ref[0, 1]
        if caps is not None:
            for g in range(len(caps)):
                cnt_s[g] = iscal_ref[0, 1 + g]

    # ---- gains for candidate block i against the resident eval set -------
    x = x_ref[...]                                       # (bn, d) narrow ok
    e = e_ref[...]                                       # (mp, d)
    xf = x.astype(jnp.float32)
    if quantized:
        # in-kernel dequant: the fp32 affine matches ref.dequantize_rows
        # bit-for-bit (IEEE mult-add on the same bytes)
        xf = xf * xs_ref[...] + xz_ref[...]
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

    # ---- qualify: available ∧ gain ≥ τ ∧ singly feasible -----------------
    tau = fscal_ref[0, 0]
    av = av_ref[...]                                     # (bn, 1)
    q = (av > 0) & (g >= tau)
    if budget is not None:
        w = w_ref[...]                                   # (bn, 1)
        q = q & (used_s[0] + w <= budget + tol)
    if caps is not None:
        gid = gid_ref[...]                               # (bn, 1) int32
        # static unrolled conjunction over the (tiny) group set: each
        # group's open/closed bit is one SMEM scalar compare, broadcast
        # against the block's gid column — no SMEM gather required
        open_any = jnp.zeros_like(gid, dtype=jnp.bool_)
        for grp in range(len(caps)):
            open_any = open_any | ((gid == grp) & (cnt_s[grp] < caps[grp]))
        q = q & open_any

    # ---- prefix-stop accept: monotone cumulative feasibility -------------
    cumn = _prefix_sum(q.astype(jnp.int32))              # (bn, 1) inclusive
    violate = (count_s[0] + cumn) > k
    if budget is not None:
        cumw = _prefix_sum(jnp.where(q, w, 0.0))
        violate = violate | (used_s[0] + cumw > budget + tol)
    if caps is not None:
        for grp in range(len(caps)):
            cg = _prefix_sum((q & (gid == grp)).astype(jnp.int32))
            violate = violate | ((cnt_s[grp] + cg) > caps[grp])
    acc = q & (_prefix_sum(violate.astype(jnp.int32)) == 0) \
            & (stop_s[0] == 0)

    # ---- commit: scalar state, stop flag, cur_min batch fold -------------
    stop_s[0] = jnp.where(jnp.any(violate & q), 1, stop_s[0])
    count_s[0] = count_s[0] + jnp.sum(acc.astype(jnp.int32))
    if budget is not None:
        used_s[0] = used_s[0] + jnp.sum(jnp.where(acc, w, 0.0))
    if caps is not None:
        for grp in range(len(caps)):
            cnt_s[grp] = cnt_s[grp] + jnp.sum(
                (acc & (gid == grp)).astype(jnp.int32))
    cm_s[...] = jnp.minimum(cm, jnp.min(jnp.where(acc, d2, INF), axis=0,
                                        keepdims=True))
    acc_ref[...] = acc.astype(jnp.int32)

    @pl.when(i == nb - 1)
    def _flush():
        cmout_ref[...] = cm_s[...]


@functools.partial(jax.jit,
                   static_argnames=("k", "bn", "m_true", "compute_dtype",
                                    "budget", "caps", "interpret"))
def threshold_select_pallas(
    X: jax.Array,        # (n, d) candidates — n % bn == 0 (wrapper pads)
    E: jax.Array,        # (mp, d) eval set — zero-padded rows
    cur_min: jax.Array,  # (mp,)            — zero-padded
    avail: jax.Array,    # (n,) float32 1/0 — padded rows 0
    fscal: jax.Array,    # (2,) fp32 [tau, used]
    iscal: jax.Array,    # (1+G,) int32 [count, per-group counts]
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
    G = len(caps) if caps is not None else 0
    grid = (n // bn,)

    kern = functools.partial(_kernel, bn=bn, m_true=m_true,
                             compute_dtype=compute_dtype, k=k, budget=budget,
                             caps=caps, quantized=quantized,
                             tol=_knapsack_tol() if budget is not None else 0.0)
    blk = lambda i: (i, 0)
    res = lambda i: (0, 0)
    in_specs = [
        pl.BlockSpec((bn, d), blk),                  # X streams per block
        pl.BlockSpec((mp, d), res),                  # E resident
        pl.BlockSpec((1, mp), res),                  # cur_min seed
        pl.BlockSpec((bn, 1), blk),                  # availability
        pl.BlockSpec(memory_space=pltpu.SMEM),       # [tau, used] fp32
        pl.BlockSpec(memory_space=pltpu.SMEM),       # [count, counts…] int32
    ]
    scratch = [
        pltpu.VMEM((1, mp), jnp.float32),            # running cur_min
        pltpu.SMEM((1,), jnp.int32),                 # launch-wide stop flag
        pltpu.SMEM((1,), jnp.int32),                 # items selected so far
    ]
    operands = [X, E, cur_min[None, :], avail[:, None],
                fscal.astype(jnp.float32)[None, :],
                iscal.astype(jnp.int32)[None, :]]
    if budget is not None:
        in_specs.append(pl.BlockSpec((bn, 1), blk))  # weights
        scratch.append(pltpu.SMEM((1,), jnp.float32))    # used weight so far
        operands.append(weights.astype(jnp.float32)[:, None])
    if caps is not None:
        in_specs.append(pl.BlockSpec((bn, 1), blk))  # gids
        scratch.append(pltpu.SMEM((G,), jnp.int32))  # per-group counts
        operands.append(group_ids.astype(jnp.int32)[:, None])
    if quantized:
        in_specs.append(pl.BlockSpec((bn, 1), blk))  # x_scale
        in_specs.append(pl.BlockSpec((bn, 1), blk))  # x_zp
        operands.append(x_scale.astype(jnp.float32)[:, None])
        operands.append(x_zp.astype(jnp.float32)[:, None])
    acc, cm = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bn, 1), blk),              # per-row accept bit
            pl.BlockSpec((1, mp), res),              # final cur_min
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, mp), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands)
    return acc[:, 0], cm[0]
