"""Public jit'd wrappers for every kernel: pad → dispatch → slice.

Dispatch policy (``impl``):
  * ``"auto"``   — compiled Pallas on TPU; pure-jnp reference elsewhere
                   (this CPU container lowers the reference path; the Pallas
                   path is validated with interpret=True in tests).
  * ``"ref"``    — force the pure-jnp oracle (:mod:`repro.kernels.ref`).
  * ``"pallas"`` — force Pallas, interpret=True off-TPU so it still runs.

The wrappers own the padding contract so kernels can assume exact tiling.

## Fused selection

:func:`greedy_select` is the one *multi-step* kernel in this package: it runs
an entire k-item exemplar-clustering greedy selection in a single launch
(see kernels/greedy_select.py).  Its dispatch adds one rule on top of the
policy above: the Pallas path additionally requires the candidate block and
eval set to fit the kernels' scoped VMEM limit together (tiled, double-
buffered — see ``_selection_vmem_bytes``); oversized ``auto`` problems
take the pure-jnp fused reference instead.  Both impls are bit-identical to
the step-wise greedy, lowest-index tie-breaking included, so β-niceness
guarantees transfer unchanged.  Scope of that contract: exact within an
impl family (ref-vs-ref, certified by tests; interpret-vs-ref likewise).
On TPU hardware the step-wise oracle reduces over ``bm``-tiles
(exemplar_gains) while the megakernel reduces whole rows, so *exactly*
tied gains could in principle resolve differently there — same class of
last-ulp caveat as any reduction-order change, and the kernel_bench
equality assert doubles as the canary.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from repro.kernels import VMEM_LIMIT_BYTES, ref
from repro.kernels.exemplar_gains import exemplar_gains_pallas
from repro.kernels.greedy_select import greedy_select_pallas
from repro.kernels.threshold_select import threshold_select_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rbf_kernel import rbf_kernel_pallas
from repro.kernels.wkv6 import wkv6_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas(impl: str) -> bool:
    if impl == "auto":
        return _on_tpu()
    if impl == "pallas":
        return True
    if impl == "ref":
        return False
    raise ValueError(f"unknown impl {impl!r}")


def _interpret() -> bool:
    return not _on_tpu()


# Which implementation each selection wrapper lowered to, counted when a
# program is traced (one entry per compiled program, not per launch):
# entry points print it, so a fallback to the reference is never silent.
PATHS: collections.Counter = collections.Counter()


def _route(kernel: str, impl: str, oversized: bool = False,
           dynamic_params: bool = False) -> str:
    """Pick ``"pallas"`` or a ``"ref…"`` path for one call and count it."""
    if not _use_pallas(impl):
        path = "ref"
    elif impl == "auto" and oversized:
        path = "ref:oversized"
    elif impl == "auto" and dynamic_params:
        path = "ref:dynamic-params"
    else:
        path = "pallas"
    label = "interpret" if path == "pallas" and not _on_tpu() else path
    PATHS[(kernel, label)] += 1
    return path


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _sublanes(dtype) -> int:
    # rows per (sublane, lane) tile: 8 for 32-bit, 16 for bf16, 32 for int8
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _pad_rows(x: jax.Array, mult: int, value: float = 0.0) -> jax.Array:
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------


def pairwise_sqdist(X: jax.Array, Y: jax.Array) -> jax.Array:
    """(n, d), (m, d) -> (n, m). Always the reference (XLA fuses this fine)."""
    return ref.pairwise_sqdist(X, Y)


def exemplar_gains(
    X: jax.Array,
    E: jax.Array,
    cur_min: jax.Array,
    *,
    impl: str = "auto",
    bn: int = 256,
    bm: int = 128,
    compute_dtype=None,
    x_scale: jax.Array | None = None,
    x_zp: jax.Array | None = None,
    eval_weights: jax.Array | None = None,
) -> jax.Array:
    """Marginal gains for exemplar clustering. See kernels/exemplar_gains.py.

    The (256, 128) blocks fit the scoped VMEM limit up to d = 4,096 with
    full-precision fp32 contraction (tests/test_tpu_compile.py).

    ``x_scale``/``x_zp`` (both or neither, per candidate row) dequantize
    int8-stored candidates in-kernel: VMEM holds the narrow rows, gain math
    runs on the fp32 dequantized values (bf16 candidates need no params —
    the upcast is exact).

    ``eval_weights`` (m,) reweights eval columns (query-conditioned serving);
    ``None`` is the unweighted path, bit-identical to weights of exactly 1.0.
    """
    assert (x_scale is None) == (x_zp is None), "x_scale and x_zp pair up"
    if _route("exemplar_gains", impl) != "pallas":
        return ref.exemplar_gains(X, E, cur_min, compute_dtype=compute_dtype,
                                  x_scale=x_scale, x_zp=x_zp,
                                  eval_weights=eval_weights)
    n, m = X.shape[0], E.shape[0]
    bn = min(bn, max(8, n))
    bm = min(bm, max(8, m))
    Xp = _pad_rows(X, bn)
    Ep = _pad_rows(E, bm)
    cmp_ = _pad_rows(cur_min, bm)  # zero-pad ⇒ padded columns contribute 0
    xsp = None if x_scale is None else _pad_rows(x_scale.astype(jnp.float32), bn)
    xzp = None if x_zp is None else _pad_rows(x_zp.astype(jnp.float32), bn)
    # zero-padded weight columns keep padded eval columns inert
    ewp = (None if eval_weights is None
           else _pad_rows(eval_weights.astype(jnp.float32), bm))
    raw = exemplar_gains_pallas(Xp, Ep, cmp_, xsp, xzp, ewp, bn=bn, bm=bm,
                                interpret=_interpret())
    return raw[:n] / m


def _tile_bytes(rows: int, cols: int, itemsize: int = 4) -> int:
    """VMEM bytes of a (rows, cols) array stored in (sublane, 128-lane)
    tiles — an (n, 1) fp32 column costs n·128·4 bytes, not n·4."""
    return (_round_up(rows, 8 * (4 // itemsize)) * _round_up(cols, 128)
            * itemsize)


def _selection_vmem_bytes(n: int, m: int, d: int, bn: int, *,
                          x_itemsize: int = 4, cols: int = 1,
                          streamed: bool = False) -> int:
    """Scoped VMEM a selection kernel needs, as the tree lays it out.

    Machines are vmapped, so every input block is double-buffered even
    under a constant index map.  ``cols`` counts the per-row (·, 1)
    columns (availability plus knapsack weights, group ids, dequant
    scale/zero-point).  The greedy megakernel keeps X and its columns
    resident (n rows); threshold_select streams them (``streamed``, bn
    rows).  Temporaries: two (bn, m) distance/gain tiles, four (bn, d)
    fp32 tiles and one (m, d) — the dequantized X block and the operand
    splits of the full-precision fp32 contraction.  Calibrated against
    Mosaic's scoped allocations on v5e (libtpu 0.0.34): it overstates them
    by at most ~1.5 MiB; tests/test_tpu_compile.py holds the fit.
    """
    rows = bn if streamed else n
    inputs = (_tile_bytes(rows, d, x_itemsize) + _tile_bytes(m, d)
              + _tile_bytes(1, m) + cols * _tile_bytes(rows, 1))
    outputs = _tile_bytes(1, m) + (_tile_bytes(bn, 1) if streamed
                                   else _tile_bytes(1, 128))
    scratch = _tile_bytes(1, m) + (0 if streamed else
                                   _tile_bytes(n, 1) + _tile_bytes(1, d))
    temps = (2 * _tile_bytes(bn, m) + 4 * _tile_bytes(bn, d)
             + _tile_bytes(m, d))
    return 2 * (inputs + outputs) + scratch + temps


def _fits_vmem(n: int, m: int, d: int, bn: int, **kw) -> bool:
    return _selection_vmem_bytes(n, m, d, bn, **kw) <= VMEM_LIMIT_BYTES


def _n_cols(weights, group_ids, x_scale) -> int:
    return (1 + (weights is not None) + (group_ids is not None)
            + 2 * (x_scale is not None))


def greedy_select(
    X: jax.Array,
    E: jax.Array,
    cur_min: jax.Array,
    mask: jax.Array,
    k: int,
    *,
    impl: str = "auto",
    bn: int = 256,
    bm: int = 128,
    compute_dtype=None,
    weights: jax.Array | None = None,
    budget: float | None = None,
    group_ids: jax.Array | None = None,
    caps: tuple[int, ...] | None = None,
    x_scale: jax.Array | None = None,
    x_zp: jax.Array | None = None,
    eval_weights: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused k-step greedy selection for exemplar clustering.

    Returns ``(sel_idx, cur_min_out)`` — see kernels/greedy_select.py.
    Bit-identical (ties included) to running the step-wise greedy with
    ``ExemplarClustering`` on the same impl family.

    ``weights``/``budget`` (both or neither) thread a knapsack constraint
    through both impls: candidates are feasibility-masked against the
    sequentially accumulated used-weight exactly as ``constraints.Knapsack``
    masks the step-wise scan.  ``group_ids``/``caps`` (both or neither)
    thread a partition matroid the same way — a running per-group count
    vector (SMEM-resident in the Pallas impl) mirrors
    ``constraints.PartitionMatroid``.  The two compose (masks AND, states
    commit independently), matching the step-wise ``Intersection``, so the
    bit-identity contract extends to every fused-constraint combination.

    The Pallas megakernel keeps X and E resident in VMEM, so ``auto``
    additionally requires them to fit (:func:`_selection_vmem_bytes`);
    oversized problems take the reference path (XLA hoists the step-
    invariant contraction, so it degrades gracefully rather than erroring).
    ``impl="pallas"`` overrides the capacity check (tests, experiments).

    ``budget``/``caps`` may be *traced* jax arrays (the serve layer passes
    per-request constraint parameters as operands to avoid retracing); the
    Pallas megakernel bakes them in as compile-time statics, so dynamic
    parameters dispatch to the (tracer-safe) fused reference instead.
    ``eval_weights`` (m,) reweights eval columns as in
    :func:`exemplar_gains`; ``None`` is the bit-identical unweighted path.
    """
    assert (weights is None) == (budget is None), "weights and budget pair up"
    assert (group_ids is None) == (caps is None), "group_ids and caps pair up"
    assert (x_scale is None) == (x_zp is None), "x_scale and x_zp pair up"
    n, m = X.shape[0], E.shape[0]
    # the block size is pure tiling here (the argmax is global, ties go to
    # the lowest index), so round it to the sublane tile of X's dtype
    bn = _round_up(min(bn, max(8, n)), _sublanes(X.dtype))
    bm = min(bm, max(8, m))
    oversized = not _fits_vmem(_round_up(n, bn), _round_up(m, bm),
                               X.shape[1], bn, x_itemsize=X.dtype.itemsize,
                               cols=_n_cols(weights, group_ids, x_scale))
    dynamic_params = (isinstance(budget, jax.Array)
                      or isinstance(caps, jax.Array)
                      or eval_weights is not None)
    if impl == "pallas" and dynamic_params:
        raise ValueError("greedy_select: traced budget/caps and eval_weights "
                         "require the fused reference impl (the Pallas "
                         "megakernel takes them as compile-time statics)")
    if _route("greedy_select", impl, oversized, dynamic_params) != "pallas":
        return ref.greedy_select(X, E, cur_min, mask, k,
                                 compute_dtype=compute_dtype,
                                 weights=weights, budget=budget,
                                 group_ids=group_ids, caps=caps,
                                 x_scale=x_scale, x_zp=x_zp,
                                 eval_weights=eval_weights)
    Xp = _pad_rows(X, bn)
    avp = _pad_rows(mask.astype(jnp.float32), bn)
    Ep = _pad_rows(E, bm)
    cmp_ = _pad_rows(cur_min, bm)  # zero-pad ⇒ padded columns contribute 0
    # padded weight/group rows are availability-0, their values are inert
    wp = None if weights is None else _pad_rows(weights.astype(jnp.float32), bn)
    bud = None if budget is None else float(budget)
    gp = (None if group_ids is None
          else _pad_rows(group_ids.astype(jnp.int32), bn))
    cp = None if caps is None else tuple(int(c) for c in caps)
    # padded dequant rows are availability-0 ⇒ scale/zp values are inert
    xsp = None if x_scale is None else _pad_rows(x_scale.astype(jnp.float32), bn)
    xzp = None if x_zp is None else _pad_rows(x_zp.astype(jnp.float32), bn)
    # score with the dtype the step-wise oracle would actually use in this
    # environment: exemplar_gains' pallas branch (TPU) always contracts
    # fp32, while its ref branch (interpret testing) honors compute_dtype —
    # diverging from the baseline here would let near-tied gains select
    # different items and void the bit-identity contract
    cd = None if _on_tpu() else (
        None if compute_dtype is None else jnp.dtype(compute_dtype).name)
    sel, cm = greedy_select_pallas(Xp, Ep, cmp_, avp, wp, gp, xsp, xzp,
                                   k=k, bn=bn,
                                   m_true=m, compute_dtype=cd, budget=bud,
                                   caps=cp, interpret=_interpret())
    return sel, cm[:m]


def threshold_select(
    X: jax.Array,
    E: jax.Array,
    cur_min: jax.Array,
    mask: jax.Array,
    tau,
    k: int,
    *,
    used=None,
    counts: jax.Array | None = None,
    count=None,
    impl: str = "auto",
    bn: int = 256,
    bm: int = 128,
    compute_dtype=None,
    weights: jax.Array | None = None,
    budget: float | None = None,
    group_ids: jax.Array | None = None,
    caps: tuple[int, ...] | None = None,
    x_scale: jax.Array | None = None,
    x_zp: jax.Array | None = None,
    eval_weights: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One τ-level of threshold-batch selection: batch-accept in one launch.

    Returns ``(accept, cur_min_out)`` — see kernels/threshold_select.py.
    ``accept`` is a (n,) bool mask of items committed at this τ-level;
    the caller recomputes its scalar launch state (``used``, ``counts``,
    ``count``, availability) from it in plain jnp, which keeps the driver
    loop bit-identical across impls by construction.

    The semantics are block-sequential at granularity ``bn`` (prefix-stop
    acceptance — see the kernel docstring), so ``bn`` is part of the
    function's *meaning* here, not just a tile size: both impls honour the
    same ``bn`` and are pinned bit-identical at it.  ``tau``/``used``/
    ``counts``/``count`` are traced scalars (the τ-ladder runs as one
    ``lax.while_loop``); ``budget``/``caps`` may themselves be traced
    (dynamic serve parameters), which — like ``eval_weights`` — dispatches
    to the fused reference, exactly as :func:`greedy_select` does.

    The Pallas path streams X block-by-block but keeps E VMEM-resident,
    so ``auto`` checks E, one X block and the gains tiles against the
    scoped VMEM limit (:func:`_selection_vmem_bytes` with ``streamed``).
    """
    assert (weights is None) == (budget is None), "weights and budget pair up"
    assert (group_ids is None) == (caps is None), "group_ids and caps pair up"
    assert (x_scale is None) == (x_zp is None), "x_scale and x_zp pair up"
    n, m = X.shape[0], E.shape[0]
    bn = min(bn, max(8, n))
    bm = min(bm, max(8, m))
    used0 = jnp.float32(0.0) if used is None else jnp.asarray(used, jnp.float32)
    count0 = jnp.int32(0) if count is None else jnp.asarray(count, jnp.int32)
    G = 0
    if caps is not None:
        G = len(caps) if isinstance(caps, (tuple, list)) else caps.shape[0]
    counts0 = (jnp.zeros((max(G, 1),), jnp.int32) if counts is None
               else jnp.asarray(counts, jnp.int32))
    oversized = not _fits_vmem(_round_up(n, bn), _round_up(m, bm),
                               X.shape[1], bn, x_itemsize=X.dtype.itemsize,
                               cols=_n_cols(weights, group_ids, x_scale),
                               streamed=True)
    dynamic_params = (isinstance(budget, jax.Array)
                      or isinstance(caps, jax.Array)
                      or eval_weights is not None)
    if impl == "pallas" and dynamic_params:
        raise ValueError("threshold_select: traced budget/caps and "
                         "eval_weights require the fused reference impl "
                         "(the Pallas megakernel takes them as "
                         "compile-time statics)")
    if _route("threshold_select", impl, oversized,
              dynamic_params) != "pallas":
        return ref.threshold_select(X, E, cur_min, mask,
                                    jnp.asarray(tau, jnp.float32),
                                    used0, counts0, count0, k=k, bn=bn,
                                    compute_dtype=compute_dtype,
                                    weights=weights, budget=budget,
                                    group_ids=group_ids, caps=caps,
                                    x_scale=x_scale, x_zp=x_zp,
                                    eval_weights=eval_weights)
    Xp = _pad_rows(X, bn)
    avp = _pad_rows(mask.astype(jnp.float32), bn)
    Ep = _pad_rows(E, bm)
    cmp_ = _pad_rows(cur_min, bm)  # zero-pad ⇒ padded columns contribute 0
    # padded weight/group/dequant rows are availability-0, values inert
    wp = None if weights is None else _pad_rows(weights.astype(jnp.float32), bn)
    bud = None if budget is None else float(budget)
    gp = (None if group_ids is None
          else _pad_rows(group_ids.astype(jnp.int32), bn))
    cp = None if caps is None else tuple(int(c) for c in caps)
    xsp = None if x_scale is None else _pad_rows(x_scale.astype(jnp.float32), bn)
    xzp = None if x_zp is None else _pad_rows(x_zp.astype(jnp.float32), bn)
    fscal = jnp.stack([jnp.asarray(tau, jnp.float32), used0])
    iscal = (jnp.concatenate([count0[None], counts0[:G]]) if cp is not None
             else count0[None])
    cd = None if _on_tpu() else (
        None if compute_dtype is None else jnp.dtype(compute_dtype).name)
    acc, cm = threshold_select_pallas(Xp, Ep, cmp_, avp, fscal, iscal,
                                      wp, gp, xsp, xzp, k=k, bn=bn,
                                      m_true=m, compute_dtype=cd, budget=bud,
                                      caps=cp, interpret=_interpret())
    return acc[:n] > 0, cm[:m]


def rbf_kernel(
    X: jax.Array,
    Y: jax.Array,
    h: float,
    *,
    impl: str = "auto",
    bn: int = 256,
    bm: int = 256,
) -> jax.Array:
    """RBF kernel matrix exp(-||x-y||²/h²). See kernels/rbf_kernel.py."""
    if not _use_pallas(impl):
        return ref.rbf_kernel(X, Y, h)
    n, m = X.shape[0], Y.shape[0]
    bn = min(bn, max(8, n))
    bm = min(bm, max(8, m))
    Kp = rbf_kernel_pallas(_pad_rows(X, bn), _pad_rows(Y, bm), h=float(h),
                           bn=bn, bm=bm, interpret=_interpret())
    return Kp[:n, :m]


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    kv_valid_len=None,
    impl: str = "auto",
    bq: int = 128,
    bk: int = 128,
) -> jax.Array:
    """Attention with GQA broadcast. See kernels/flash_attention.py.

    kv_valid_len (decode against a partially filled cache) routes to the
    reference path: decode attention is a memory-bound gather, not the
    flash kernel's target (train/prefill).
    """
    if kv_valid_len is not None or not _use_pallas(impl):
        return ref.flash_attention(q, k, v, causal=causal, scale=scale,
                                   kv_valid_len=kv_valid_len)
    S, T = q.shape[2], k.shape[2]
    bq = min(bq, S)
    bk = min(bk, T)
    assert S % bq == 0 and T % bk == 0, "pad sequence to block multiple"
    return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                  bq=bq, bk=bk, interpret=_interpret())


def wkv6(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    *,
    impl: str = "auto",
    bt: int = 128,
) -> jax.Array:
    """RWKV-6 WKV recurrence. See kernels/wkv6.py."""
    if not _use_pallas(impl):
        return ref.wkv6(r, k, v, w, u)
    T = r.shape[2]
    bt = min(bt, T)
    assert T % bt == 0, "pad time to block multiple"
    return wkv6_pallas(r, k, v, w, u, bt=bt, interpret=_interpret())
