"""Sharding rules: DP(pod) × FSDP/TP hybrid (data, model) for all archs.

Logical layout (DESIGN.md §6):
  * batch            → ("pod", "data")      pure DP over pods, DP over data
  * d_model weight   → "data"               (FSDP-ish 2D: contraction psum)
  * heads / d_ff     → "model"              (Megatron TP)
  * MoE experts      → "model"              (EP), expert d_ff → "data"
  * vocab            → "model"

All constraints go through :func:`shard`, which (a) no-ops when no ambient
mesh is set (plain CPU tests), and (b) drops axis names that do not divide
the corresponding dimension (small archs degrade to replication instead of
erroring — e.g. whisper-tiny's 6 heads on a 16-wide model axis).
"""
from __future__ import annotations

import math
from typing import Any, Sequence

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

BATCH = ("pod", "data")


def _fit_names(dim: int, names, mesh_shape: dict[str, int]):
    """Largest prefix of `names` that exists in the mesh and divides `dim`."""
    if names is None:
        return None
    names_t = tuple(names) if isinstance(names, (tuple, list)) else (names,)
    names_t = tuple(n for n in names_t if n in mesh_shape)
    while names_t:
        size = math.prod(mesh_shape[n] for n in names_t)
        if size > 0 and dim % size == 0:
            return names_t if len(names_t) > 1 else names_t[0]
        names_t = names_t[:-1]
    return None


def fit_spec(shape: Sequence[int], spec: Sequence[Any],
             mesh_shape: dict[str, int]) -> P:
    assert len(spec) == len(shape), (shape, spec)
    return P(*[_fit_names(d, s, mesh_shape) for d, s in zip(shape, spec)])


def _ambient_mesh_shape() -> dict[str, int] | None:
    """Axis sizes of the ambient mesh, or None when no mesh is set."""
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else dict(am.shape)


def shard(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint with divisibility fallback; no-op w/o mesh."""
    mesh_shape = _ambient_mesh_shape()
    if mesh_shape is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, fit_spec(x.shape, spec, mesh_shape))


# ---------------------------------------------------------------------------
# Parameter placement rules (by leaf path)
# ---------------------------------------------------------------------------

_IN_PROJ = ("wq", "wk", "wv", "w_gate", "w_up", "w_in", "w_r", "w_k", "w_v",
            "w_g", "w_x", "in_proj", "w_dt")
_OUT_PROJ = ("wo", "w_down", "w_out", "out_proj")


def param_spec(path: tuple[str, ...], shape: tuple[int, ...]) -> tuple:
    """Logical spec for a parameter leaf (layer-stacked dims lead)."""
    name = path[-1]
    nd = len(shape)
    if name == "emb":                       # (V, d): vocab over data —
        return ("data", None)               # masked gather + psum(data)
    if name == "head":                      # (d, V): V over model — logits
        return (None, "model")              # born vocab-sharded, no psum
    if nd >= 2 and "experts" in path:       # (L, E, d, ff) / (L, E, ff, d)
        lead = (None,) * (nd - 3)
        if name in _OUT_PROJ:
            return lead + ("model", "data", None)
        return lead + ("model", None, "data")
    if any(name.endswith(s) or name == s for s in _OUT_PROJ) and nd >= 2:
        return (None,) * (nd - 2) + ("model", "data")
    if any(name.endswith(s) or name == s for s in _IN_PROJ) and nd >= 2:
        return (None,) * (nd - 2) + ("data", "model")
    _SMALL = ("ln", "norm", "bias", "scale", "mu", "mu_c", "u", "w0",
              "dt_bias", "A_log", "D", "wkv_ln", "enc_pos", "final_ln",
              "q_norm", "k_norm", "enc_ln", "conv_w")
    if nd >= 2 and shape[-1] >= 1024 and name not in _SMALL and \
            not name.endswith("ln"):        # misc big matrices: be safe
        return (None,) * (nd - 2) + ("data", "model")
    return (None,) * nd                     # norms, biases, small tensors


def param_sharding_tree(params: Any, mesh) -> Any:
    """NamedShardings for a parameter pytree (used for in_shardings)."""
    mesh_shape = dict(mesh.shape)

    def one(path, leaf):
        names = tuple(getattr(p, "key", getattr(p, "name", str(p)))
                      for p in path)
        spec = fit_spec(leaf.shape, param_spec(names, leaf.shape), mesh_shape)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params)


def batch_spec(shape: Sequence[int], mesh) -> NamedSharding:
    """Batch-leading arrays: shard dim 0 over (pod, data)."""
    spec = fit_spec(shape, (BATCH,) + (None,) * (len(shape) - 1),
                    dict(mesh.shape))
    return NamedSharding(mesh, spec)


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
