"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: each Pallas kernel is validated against
the function of the same name here (tests/test_kernels.py sweeps shapes and
dtypes with ``assert_allclose``).  They are also the production implementation
on backends without Pallas support (this CPU container).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def contract(X: jax.Array, Y: jax.Array) -> jax.Array:
    """(n, d), (m, d) -> (n, m) = X Yᵀ, accumulated in fp32.

    fp32 operands contract at full fp32 precision: the TPU's default would
    round them to bf16 first, and both the Pallas kernels and this oracle
    promise fp32 gains.  (The CPU computes fp32 either way.)
    """
    full = X.dtype == jnp.float32 and Y.dtype == jnp.float32
    return jax.lax.dot_general(
        X, Y, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if full else None,
        preferred_element_type=jnp.float32)


def pairwise_sqdist(X: jax.Array, Y: jax.Array) -> jax.Array:
    """(n, d), (m, d) -> (n, m) squared euclidean distances."""
    x2 = jnp.sum(X * X, axis=-1, keepdims=True)          # (n, 1)
    y2 = jnp.sum(Y * Y, axis=-1, keepdims=True).T        # (1, m)
    d2 = x2 + y2 - 2.0 * contract(X, Y)
    return jnp.maximum(d2, 0.0)


def _sqdist(X: jax.Array, E: jax.Array, compute_dtype=None) -> jax.Array:
    """Squared distances with optional reduced-precision contraction.

    Shared by :func:`exemplar_gains` and :func:`greedy_select` — the fused
    path's bit-identity contract requires both to run exactly these ops.
    compute_dtype=bfloat16 halves the d2-tile HBM traffic (§Perf); the
    contraction still accumulates fp32 (preferred_element_type).
    """
    if compute_dtype is None:
        return pairwise_sqdist(X, E)
    Xc, Ec = X.astype(compute_dtype), E.astype(compute_dtype)
    x2 = jnp.sum(X.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    e2 = jnp.sum(E.astype(jnp.float32) ** 2, axis=-1, keepdims=True).T
    xy = contract(Xc, Ec)
    return jnp.maximum(x2 + e2 - 2.0 * xy, 0.0)


def dequantize_rows(X: jax.Array, x_scale: jax.Array | None = None,
                    x_zp: jax.Array | None = None) -> jax.Array:
    """Narrow candidate rows → fp32: per-row affine for int8 (scale/zp),
    plain exact upcast for bf16/fp32.

    The single dequant definition the fused kernels and the generic scan
    path both reduce to — an elementwise IEEE fp32 multiply-add, so device
    and host dequantization of the same bytes are bit-equal.
    """
    Xf = X.astype(jnp.float32)
    if x_scale is not None:
        Xf = Xf * x_scale[:, None] + x_zp[:, None]
    return Xf


def prefix_sum(v: jax.Array, roll=None) -> jax.Array:
    """Inclusive prefix sum along axis 0 in log-step (Hillis–Steele) form.

    ``threshold_select`` needs cumulative counts and weights inside its
    Pallas kernel, where Mosaic has no cumsum; both impls run exactly this
    sequence of shifted elementwise adds (the kernel passes ``pltpu.roll``
    as ``roll``), so their float sums agree bit for bit.
    """
    roll = roll or (lambda a, off: jnp.roll(a, off, axis=0))
    rows = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    off = 1
    while off < v.shape[0]:
        v = v + jnp.where(rows >= off, roll(v, off), jnp.zeros_like(v))
        off *= 2
    return v


def exemplar_gains(X: jax.Array, E: jax.Array, cur_min: jax.Array,
                   compute_dtype=None, x_scale: jax.Array | None = None,
                   x_zp: jax.Array | None = None,
                   eval_weights: jax.Array | None = None) -> jax.Array:
    """Marginal gains of the exemplar-clustering objective.

    gains[i] = (1/m) * sum_j w_j * max(0, cur_min[j] - ||X[i] - E[j]||^2)

    X: (n, d) candidates (optionally quantized — see
    :func:`dequantize_rows`), E: (m, d) eval set, cur_min: (m,).
    ``eval_weights`` (m,) reweights the eval columns (query-conditioned
    relevance, serve layer); ``None`` takes the unweighted reduction and a
    weight of exactly 1.0f takes the weighted one to the same bits (the
    1.0-multiply is IEEE-exact and the reduction order is unchanged).
    """
    Xf = dequantize_rows(X, x_scale, x_zp)
    d2 = _sqdist(Xf, E, compute_dtype)                    # (n, m)
    contrib = jnp.maximum(cur_min[None, :] - d2, 0.0)
    if eval_weights is not None:
        contrib = contrib * eval_weights[None, :]
    return jnp.sum(contrib, axis=-1) / E.shape[0]


def greedy_select(X: jax.Array, E: jax.Array, cur_min: jax.Array,
                  mask: jax.Array, k: int,
                  compute_dtype=None, weights: jax.Array | None = None,
                  budget: float | None = None,
                  group_ids: jax.Array | None = None,
                  caps: tuple[int, ...] | None = None,
                  x_scale: jax.Array | None = None,
                  x_zp: jax.Array | None = None,
                  eval_weights: jax.Array | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Fused k-step exemplar-clustering greedy selection (pure-jnp oracle).

    Runs the entire k-item greedy loop in one call and returns
    ``(sel_idx, cur_min_out)``:

      sel_idx[t]  — block position selected at step t (int32, -1 if none)
      cur_min_out — (m,) running minimum after all selections

    Bit-identical to composing :func:`repro.core.algorithms.greedy` with
    ``ExemplarClustering`` (lowest-index tie-breaking included): gains use
    exactly the :func:`exemplar_gains` formula and the ``cur_min`` refresh
    uses the objective's difference form ``Σ(E - x)²``, in the same order.
    The distance matrix is contracted once up front (it is step-invariant),
    so per-step work drops from O(n·m·d) to O(n·m) — the fusion win.

    ``weights``/``budget`` (both or neither) encode a knapsack constraint:
    step t's candidates are the available items with
    ``used + weights ≤ budget + KNAPSACK_TOL`` under the sequentially
    accumulated fp32 ``used`` — exactly the feasibility test and update
    order of ``constraints.Knapsack`` inside the step-wise scan.

    ``group_ids``/``caps`` (both or neither) encode a partition matroid:
    the running per-group count vector admits item i while
    ``counts[gid_i] < caps[gid_i]`` and the winner's group is incremented
    on commit — exactly ``constraints.PartitionMatroid``'s feasibility
    test and update (group ids must lie in ``[0, len(caps))``; the
    independent NumPy checker rejects out-of-range ids at the tree layer).
    Both constraint encodings compose (their masks AND), matching the
    step-wise ``Intersection`` conjunction.

    ``budget`` and ``caps`` also accept *traced* jax arrays (the serve
    layer passes per-request constraint parameters as operands so repeated
    requests never retrace) — every use below is tracer-safe.

    ``eval_weights`` (m,) reweights the eval columns exactly as in
    :func:`exemplar_gains`; ``None`` keeps the unweighted reduction and a
    weight of exactly 1.0f is bit-identical to it.
    """
    from repro.core.constraints import KNAPSACK_TOL

    n, _ = X.shape
    m = E.shape[0]
    # quantized candidates dequantize once up front: every later read of a
    # candidate row (gain matrix + cur_min refresh) sees the same fp32 value
    # the unfused scan path computes from the same bytes
    X = dequantize_rows(X, x_scale, x_zp)
    d2 = _sqdist(X, E, compute_dtype)                 # (n, m), step-invariant
    neg_inf = jnp.float32(-1e30)
    assert (weights is None) == (budget is None), "weights and budget pair up"
    assert (group_ids is None) == (caps is None), "group_ids and caps pair up"
    if caps is not None:
        caps_arr = jnp.asarray(caps, jnp.int32)
        gid = group_ids.astype(jnp.int32)

    def step(carry, _):
        cm, avail, used, counts = carry
        contrib = jnp.maximum(cm[None, :] - d2, 0.0)
        if eval_weights is not None:
            contrib = contrib * eval_weights[None, :]
        g = jnp.sum(contrib, axis=-1) / m
        cand = avail
        if weights is not None:
            cand = cand & (used + weights <= budget + KNAPSACK_TOL)
        if caps is not None:
            cand = cand & (counts[gid] < caps_arr[gid])
        g = jnp.where(cand, g, neg_inf)
        best = jnp.argmax(g)                          # lowest index on ties
        ok = g[best] > neg_inf / 2
        x = X[best]
        d2b = jnp.sum((E - x[None, :]) ** 2, axis=-1)
        cm = jnp.where(ok, jnp.minimum(cm, d2b), cm)
        if weights is not None:
            used = jnp.where(ok, used + weights[best], used)
        if caps is not None:
            counts = jnp.where(ok, counts.at[gid[best]].add(1), counts)
        avail = avail & ~(ok & (jnp.arange(n) == best))
        idx = jnp.where(ok, best.astype(jnp.int32), jnp.int32(-1))
        return (cm, avail, used, counts), idx

    counts0 = jnp.zeros((len(caps) if caps is not None else 1,), jnp.int32)
    (cur_min, _, _, _), sel_idx = jax.lax.scan(
        step, (cur_min, mask, jnp.float32(0.0), counts0), None, length=k)
    return sel_idx, cur_min


def threshold_select(X: jax.Array, E: jax.Array, cur_min: jax.Array,
                     mask: jax.Array, tau: jax.Array,
                     used: jax.Array, counts: jax.Array, count: jax.Array,
                     k: int, bn: int = 256,
                     compute_dtype=None, weights: jax.Array | None = None,
                     budget: float | None = None,
                     group_ids: jax.Array | None = None,
                     caps: tuple[int, ...] | None = None,
                     x_scale: jax.Array | None = None,
                     x_zp: jax.Array | None = None,
                     eval_weights: jax.Array | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """One τ-level of threshold-batch selection (pure-jnp oracle).

    Scores every candidate against the exemplar objective's marginal gains
    under the incoming ``cur_min`` and accepts a *batch* of qualifying
    items in one pass, instead of one argmax per launch.  Returns
    ``(accept, cur_min_out)``:

      accept       — (n,) bool, items committed at this τ-level
      cur_min_out  — (m,) running minimum after folding all accepted rows

    Semantics are **block-sequential** at granularity ``bn`` (the same
    block size the Pallas megakernel tiles at — the two are bit-identical
    per block):

      * a block's gains are computed against the ``cur_min`` produced by
        all *earlier* blocks (within the block, gains are frozen — the
        intra-block staleness is the batching trade the ε-ladder bounds),
      * an item *qualifies* when it is available, its gain ≥ τ, and it is
        singly feasible against the block-entry constraint state,
      * the block accepts the maximal **prefix** of qualifying items whose
        cumulative commitment stays feasible: inclusive cumulative counts /
        weights / per-group counts are checked against ``k`` / ``budget``
        / ``caps``; the first qualifying item that would overflow stops
        acceptance for the whole launch (later blocks accept nothing),
        which keeps the accepted set prefix-feasible by construction,
      * accepted rows fold into ``cur_min`` via the contraction-form
        distance matrix (a masked row-min — no per-item refresh order to
        match, since this kernel has no step-wise counterpart).

    ``tau``, ``used`` (running knapsack weight), ``counts`` (per-group,
    ``(G,)`` int32 — pass shape (1,) when unconstrained), and ``count``
    (items selected so far) are traced scalars so the τ-ladder driver can
    run as one ``lax.while_loop``.  ``budget``/``caps`` may be traced
    (dynamic serve parameters) — every use below is tracer-safe.
    """
    from repro.core.constraints import KNAPSACK_TOL

    n, _ = X.shape
    m = E.shape[0]
    assert (weights is None) == (budget is None), "weights and budget pair up"
    assert (group_ids is None) == (caps is None), "group_ids and caps pair up"
    X = dequantize_rows(X, x_scale, x_zp)
    d2 = _sqdist(X, E, compute_dtype)                 # (n, m), τ-invariant
    if caps is not None:
        caps_arr = jnp.asarray(caps, jnp.int32)
        G = int(caps_arr.shape[0])
        gid = group_ids.astype(jnp.int32)
    used = jnp.asarray(used, jnp.float32)
    count = jnp.asarray(count, jnp.int32)
    cm = cur_min
    stopped = jnp.zeros((), bool)
    inf = jnp.float32(jnp.inf)
    accepts = []
    for b0 in range(0, n, bn):
        b1 = min(b0 + bn, n)
        d2b = d2[b0:b1]
        contrib = jnp.maximum(cm[None, :] - d2b, 0.0)
        if eval_weights is not None:
            contrib = contrib * eval_weights[None, :]
        g = jnp.sum(contrib, axis=-1) / m
        q = mask[b0:b1] & (g >= tau)
        if weights is not None:
            wb = weights[b0:b1]
            q = q & (used + wb <= budget + KNAPSACK_TOL)
        if caps is not None:
            gidb = gid[b0:b1]
            open_any = jnp.zeros_like(q)
            for grp in range(G):
                open_any = open_any | ((gidb == grp)
                                       & (counts[grp] < caps_arr[grp]))
            q = q & open_any
        cumn = prefix_sum(q.astype(jnp.int32))
        violate = (count + cumn) > k
        if weights is not None:
            cumw = prefix_sum(jnp.where(q, wb, 0.0))
            violate = violate | (used + cumw > budget + KNAPSACK_TOL)
        if caps is not None:
            for grp in range(G):
                cg = prefix_sum((q & (gidb == grp)).astype(jnp.int32))
                violate = violate | ((counts[grp] + cg) > caps_arr[grp])
        acc = q & (prefix_sum(violate.astype(jnp.int32)) == 0) & ~stopped
        stopped = stopped | jnp.any(violate & q)
        count = count + jnp.sum(acc.astype(jnp.int32))
        if weights is not None:
            used = used + jnp.sum(jnp.where(acc, wb, 0.0))
        if caps is not None:
            for grp in range(G):
                counts = counts.at[grp].add(
                    jnp.sum((acc & (gidb == grp)).astype(jnp.int32)))
        cm = jnp.minimum(cm, jnp.min(jnp.where(acc[:, None], d2b, inf),
                                     axis=0))
        accepts.append(acc)
    return jnp.concatenate(accepts), cm


def rbf_kernel(X: jax.Array, Y: jax.Array, h: float) -> jax.Array:
    """K[i, j] = exp(-||x_i - y_j||^2 / h^2)  (paper §4.2, h=0.5)."""
    return jnp.exp(-pairwise_sqdist(X, Y) / (h * h))


def flash_attention(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, Hkv, T, D)
    v: jax.Array,  # (B, Hkv, T, D)
    *,
    causal: bool = True,
    scale: float | None = None,
    kv_valid_len: jax.Array | int | None = None,
) -> jax.Array:
    """Reference attention with GQA head-group broadcasting.

    kv_valid_len: only keys with position < kv_valid_len participate (decode
    against a fixed-size, partially filled cache buffer).
    """
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    T = k.shape[2]
    if scale is None:
        scale = 1.0 / (D**0.5)
    G = H // Hkv
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    def on_chunk(q_chunk, q_off):
        """q_chunk: (B, Hkv, G, Sc, D) grouped — no KV head repeat."""
        Sc = q_chunk.shape[3]
        logits = jnp.einsum("bkgsd,bktd->bkgst", q_chunk, kf) * scale
        kpos = jnp.arange(T)[None, :]
        if causal:
            qpos = q_off + jnp.arange(Sc)[:, None] + (T - S)
            logits = jnp.where(kpos <= qpos, logits, -1e30)
        if kv_valid_len is not None:
            logits = jnp.where(kpos < kv_valid_len, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bkgst,bktd->bkgsd", probs, vf)

    qg = q.astype(jnp.float32).reshape(B, Hkv, G, S, D)
    # blocked over queries when the (S, T) logit plane would be large —
    # keeps the lowered module's live memory O(S·chunk) like the TPU kernel
    CHUNK = 1024
    if S > CHUNK and S % CHUNK == 0:
        qc = qg.reshape(B, Hkv, G, S // CHUNK, CHUNK, D).transpose(
            3, 0, 1, 2, 4, 5)
        # recompute probs in backward (flash-attention memory behaviour)
        chunk_fn = jax.checkpoint(on_chunk, prevent_cse=False)
        def body(off, qck):
            return off + CHUNK, chunk_fn(qck, off)
        _, oc = jax.lax.scan(body, jnp.int32(0), qc)
        o = oc.transpose(1, 2, 3, 0, 4, 5).reshape(B, H, S, D)
    else:
        o = on_chunk(qg, 0).reshape(B, H, S, D)
    return o.astype(q.dtype)


def wkv6(
    r: jax.Array,  # (B, H, T, Dk)
    k: jax.Array,  # (B, H, T, Dk)
    v: jax.Array,  # (B, H, T, Dv)
    w: jax.Array,  # (B, H, T, Dk)  decay in (0, 1), data-dependent (RWKV-6 "Finch")
    u: jax.Array,  # (H, Dk)        per-head bonus
) -> jax.Array:
    """RWKV-6 WKV recurrence (sequential oracle).

      y_t = r_t @ (S_{t-1} + diag(u) k_t^T v_t)
      S_t = diag(w_t) S_{t-1} + k_t^T v_t
    """
    B, H, T, Dk = r.shape
    Dv = v.shape[-1]

    def head_scan(r_h, k_h, v_h, w_h, u_h):
        def step(S, inp):
            r_t, k_t, v_t, w_t = inp
            kv = k_t[:, None] * v_t[None, :]                 # (Dk, Dv)
            y = r_t @ (S + u_h[:, None] * kv)                # (Dv,)
            S = w_t[:, None] * S + kv
            return S, y

        S0 = jnp.zeros((Dk, Dv), jnp.float32)
        _, ys = jax.lax.scan(step, S0, (r_h, k_h, v_h, w_h))
        return ys

    fn = jax.vmap(jax.vmap(head_scan, in_axes=(0, 0, 0, 0, 0)),
                  in_axes=(0, 0, 0, 0, None))
    return fn(r.astype(jnp.float32), k.astype(jnp.float32),
              v.astype(jnp.float32), w.astype(jnp.float32),
              u.astype(jnp.float32)).astype(r.dtype)
