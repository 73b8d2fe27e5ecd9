"""RWKV-6 ("Finch") WKV recurrence kernel (Pallas, TPU target).

The attention-free hot spot of the rwkv6-1.6b assigned architecture:

    y_t = r_t @ (S_{t-1} + diag(u) k_t^T v_t)
    S_t = diag(w_t) S_{t-1} + k_t^T v_t          (w_t: data-dependent decay)

The recurrence is sequential in t but embarrassingly parallel over
(batch, head).  The (Dk, Dv) state lives in VMEM scratch for the whole
sequence; inputs stream through in time-chunks of ``bt`` so HBM traffic is
exactly one read of r/k/v/w and one write of y (the state never spills).

Grid: (B, H, T/bt) with the time axis sequential (state carried in scratch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr, *, bt: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0, 0].astype(jnp.float32)   # (bt, Dk)
    k = k_ref[0, 0].astype(jnp.float32)   # (bt, Dk)
    v = v_ref[0, 0].astype(jnp.float32)   # (bt, Dv)
    w = w_ref[0, 0].astype(jnp.float32)   # (bt, Dk)
    u = u_ref[0].astype(jnp.float32)      # (Dk,)

    def step(t, S):
        kv = k[t][:, None] * v[t][None, :]               # (Dk, Dv)
        y = r[t][None, :] @ (S + u[:, None] * kv)        # (1, Dv)
        o_ref[0, 0, pl.ds(t, 1), :] = y.astype(o_ref.dtype)
        return w[t][:, None] * S + kv

    s_scr[...] = jax.lax.fori_loop(0, bt, step, s_scr[...])


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def wkv6_pallas(
    r: jax.Array,  # (B, H, T, Dk)
    k: jax.Array,  # (B, H, T, Dk)
    v: jax.Array,  # (B, H, T, Dv)
    w: jax.Array,  # (B, H, T, Dk)
    u: jax.Array,  # (H, Dk)
    *,
    bt: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, T, Dk = r.shape
    Dv = v.shape[-1]
    assert T % bt == 0, (T, bt)

    io_spec = pl.BlockSpec((1, 1, bt, Dk), lambda b, h, c: (b, h, c, 0))
    v_spec = pl.BlockSpec((1, 1, bt, Dv), lambda b, h, c: (b, h, c, 0))

    return pl.pallas_call(
        functools.partial(_kernel, bt=bt),
        grid=(B, H, T // bt),
        in_specs=[io_spec, io_spec, v_spec, io_spec,
                  pl.BlockSpec((1, Dk), lambda b, h, c: (h, 0))],
        out_specs=v_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, Dv), r.dtype),
        scratch_shapes=[pltpu.VMEM((Dk, Dv), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u)
