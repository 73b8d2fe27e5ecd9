"""Fused exemplar-clustering marginal-gain kernel (Pallas, TPU target).

This is THE oracle hot spot of the paper's experiments (§4.2, §4.4): every
greedy step evaluates, for all candidates x_i in a machine's block,

    gains[i] = (1/m) * Σ_j max(0, cur_min[j] - ||x_i - e_j||²).

Unfused, XLA materialises the (n, m) distance matrix in HBM
(n·m·4 bytes per step — for a 16k-item block against a 16k eval set that is
1 GiB of HBM traffic per greedy step).  The fusion below keeps each (bn, bm)
distance tile in VMEM: the ``-2 X Eᵀ`` contraction runs on the MXU, and the
rank/clamp/row-sum epilogue runs on the VPU before the tile is discarded.
HBM traffic drops from O(n·m) to O((n + m)·d + n) per step — this moves the
memory-roofline term by ~d/4 (see EXPERIMENTS.md §Perf).

Grid: (n/bn, m/bm); the m-axis revisits the same output block and accumulates
(output index map ignores j ⇒ sequential minor axis on TPU).

Padding contract (enforced by ops.py): E rows are zero-padded and cur_min is
zero-padded, so padded eval columns contribute max(0 - ||x||², 0) = 0 exactly.
Padded candidate rows produce garbage gains that the wrapper slices off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT_BYTES
from repro.kernels.ref import contract


def _kernel(x_ref, e_ref, cm_ref, *rest, quantized: bool = False,
            weighted: bool = False):
    it = iter(rest)
    xs_ref = next(it) if quantized else None
    xz_ref = next(it) if quantized else None
    ew_ref = next(it) if weighted else None
    out_ref = next(it)
    j = pl.program_id(1)

    x = x_ref[...].astype(jnp.float32)          # (bn, d) — narrow rows ok
    if quantized:
        # in-kernel dequant (per-row affine): VMEM held the narrow tile,
        # the fp32 mult-add matches ref.dequantize_rows bit-for-bit
        x = x * xs_ref[...] + xz_ref[...]
    e = e_ref[...].astype(jnp.float32)          # (bm, d)
    cm = cm_ref[...].astype(jnp.float32)        # (1, bm)

    x2 = jnp.sum(x * x, axis=-1, keepdims=True)              # (bn, 1)
    e2 = jnp.sum(e * e, axis=-1, keepdims=True).T            # (1, bm)
    # MXU contraction + VPU epilogue, all in VMEM:
    d2 = x2 + e2 - 2.0 * contract(x, e)
    d2 = jnp.maximum(d2, 0.0)
    contrib = jnp.maximum(cm - d2, 0.0)                      # (bn, bm)
    if weighted:
        # query-conditioned relevance reweighting (serve layer): one VPU
        # multiply per tile; zero-padded weight columns stay inert
        contrib = contrib * ew_ref[...].astype(jnp.float32)
    partial = jnp.sum(contrib, axis=-1, keepdims=True)       # (bn, 1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(j != 0)
    def _acc():
        out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("bn", "bm", "interpret"))
def exemplar_gains_pallas(
    X: jax.Array,        # (n, d) candidates — n % bn == 0 (wrapper pads)
    E: jax.Array,        # (m, d) eval set  — m % bm == 0, zero-padded
    cur_min: jax.Array,  # (m,)             — zero-padded
    x_scale: jax.Array | None = None,  # (n,) per-row dequant scale
    x_zp: jax.Array | None = None,     # (n,) per-row dequant zero-point
    eval_weights: jax.Array | None = None,  # (m,) eval reweighting, zero-padded
    *,
    bn: int = 256,
    bm: int = 256,
    interpret: bool = False,
) -> jax.Array:
    n, d = X.shape
    m = E.shape[0]
    assert n % bn == 0 and m % bm == 0, (n, bn, m, bm)
    assert (x_scale is None) == (x_zp is None), "x_scale and x_zp pair up"
    quantized = x_scale is not None
    weighted = eval_weights is not None
    grid = (n // bn, m // bm)

    in_specs = [
        pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
        pl.BlockSpec((bm, d), lambda i, j: (j, 0)),
        pl.BlockSpec((1, bm), lambda i, j: (0, j)),
    ]
    operands = [X, E, cur_min[None, :]]
    if quantized:
        in_specs.append(pl.BlockSpec((bn, 1), lambda i, j: (i, 0)))
        in_specs.append(pl.BlockSpec((bn, 1), lambda i, j: (i, 0)))
        operands.append(x_scale.astype(jnp.float32)[:, None])
        operands.append(x_zp.astype(jnp.float32)[:, None])
    if weighted:
        in_specs.append(pl.BlockSpec((1, bm), lambda i, j: (0, j)))
        operands.append(eval_weights.astype(jnp.float32)[None, :])

    out = pl.pallas_call(
        functools.partial(_kernel, quantized=quantized, weighted=weighted),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands)
    # NOTE: returns the raw sum; ops.py divides by the *unpadded* eval-set size.
    return out[:, 0]
