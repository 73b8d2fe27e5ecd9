"""Distributed execution of one TREE round over a device mesh.

The paper's "machines" map to mesh devices (DESIGN.md §3): machine i's block
T_i is a ``(cap, d)`` slab of a machine-sharded array; running the β-nice
algorithm on every machine in parallel (Algorithm 1, line 9) is a
``shard_map`` over the flattened device mesh with a per-device ``vmap`` when
multiple logical machines share a device.  Collecting partial solutions
(line 13) and re-partitioning is a sharded scatter the XLA partitioner lowers
to collectives.

Fault model: ``dead_mask`` marks machines whose round output is lost
(failure/straggler drop).  Because Algorithm 1 takes a *max* over machine
solutions and Lemma 3.4 degrades gracefully under dropped partitions, the
round remains valid — the dead machines' items are simply pruned.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import algorithms
from repro.engine.telemetry import span

class RoundResult(NamedTuple):
    sol_rows: jax.Array   # (M, k, d)
    sol_mask: jax.Array   # (M, k)
    values: jax.Array     # (M,) f(S_i), -inf where no solution
    oracle_calls: jax.Array  # (M,) int32
    depth: jax.Array      # (M,) int32 — sequential solve depth per machine
    #   (dependent kernel launches; machines run in parallel, so the
    #   round's adaptive depth is the max over machines)


def make_submod_mesh(devices=None) -> Mesh:
    """All devices flattened into one 'machines' axis."""
    devices = jax.devices() if devices is None else devices
    return Mesh(np.asarray(devices), ("machines",))


def _solve_block(obj, T, mask, key, meta=None, *, k: int, alg: str,
                 eps: float, attr_dim: int = 0, constraint=None):
    """Solve one machine block.

    ``T`` is the *carried* block: item feature rows, optionally widened with
    ``attr_dim`` trailing per-item attribute columns (knapsack weights,
    partition ids).  The objective only ever sees the feature slice; the
    constraint only ever sees the attribute slice; the returned solution
    rows keep the full width, so attributes travel with their items into
    the next round's union without any side-channel bookkeeping.

    Quantized round-0 waves instead ship a *narrow* ``(cap, d)`` feature
    block plus a separate fp32 ``meta`` matrix ``[attrs | qmeta]`` (the
    per-row dequant params ride out-of-band, never widening the carried
    rows).  The solve runs on the narrow block (in-kernel dequant / scan
    upcast), and the k *selected* rows are dequantized to fp32 here — so
    rounds t ≥ 1 carry exactly the wide fp32 rows they always have.
    """
    dkw = algorithms.driver_kwargs(alg, key=key, eps=eps)
    if meta is not None:
        attrs = meta[:, :attr_dim] if attr_dim else None
        qmeta = meta[:, attr_dim:]
        res = algorithms.run_algorithm(alg, obj, T, mask, k,
                                       constraint=constraint,
                                       attrs=attrs, qmeta=qmeta, **dkw)
        safe = jnp.maximum(res.sel_idx, 0)
        wide = algorithms._dequant_block(T[safe], qmeta[safe])
        if attr_dim:
            wide = jnp.concatenate([wide, attrs[safe]], axis=1)
        rows = jnp.where(res.sel_mask[:, None], wide, 0.0)
        value = jnp.where(jnp.any(res.sel_mask), res.value, -jnp.inf)
        return rows, res.sel_mask, value, res.oracle_calls, res.depth
    if attr_dim:
        feat, attrs = T[:, :-attr_dim], T[:, -attr_dim:]
    else:
        feat, attrs = T, None
    res = algorithms.run_algorithm(alg, obj, feat, mask, k,
                                   constraint=constraint, attrs=attrs, **dkw)
    safe = jnp.maximum(res.sel_idx, 0)
    rows = jnp.where(res.sel_mask[:, None], T[safe], 0.0)
    any_sel = jnp.any(res.sel_mask)
    value = jnp.where(any_sel, res.value, -jnp.inf)
    return rows, res.sel_mask, value, res.oracle_calls, res.depth


def _round_local(obj, blocks, bmask, keys, dead, meta=None, *, k, alg, eps,
                 attr_dim=0, constraint=None):
    """Per-device slab: vmap the machine solver over local machines."""
    solve = functools.partial(_solve_block, k=k, alg=alg, eps=eps,
                              attr_dim=attr_dim, constraint=constraint)
    if meta is None:
        rows, smask, vals, calls, depth = jax.vmap(
            solve, in_axes=(None, 0, 0, 0))(obj, blocks, bmask, keys)
    else:
        rows, smask, vals, calls, depth = jax.vmap(
            solve, in_axes=(None, 0, 0, 0, 0))(obj, blocks, bmask, keys,
                                               meta)
    alive = ~dead
    smask = smask & alive[:, None]
    vals = jnp.where(alive, vals, -jnp.inf)
    return rows, smask, vals, calls, depth


def run_round(obj, blocks: jax.Array, bmask: jax.Array, keys: jax.Array,
              *, k: int, alg: str = "greedy", eps: float = 0.5,
              dead_mask: jax.Array | None = None,
              mesh: Mesh | None = None, attr_dim: int = 0,
              constraint=None, meta: jax.Array | None = None) -> RoundResult:
    """One round of Algorithm 1 over all M machine blocks.

    blocks: (M, cap, d + attr_dim) items (trailing ``attr_dim`` columns are
    per-item constraint attributes that ride along with the rows),
    bmask: (M, cap) validity, keys: (M,) PRNG keys.  ``constraint`` is a
    hereditary constraint from :mod:`repro.core.constraints` (hashable
    frozen dataclass — closed over, not an operand) that every machine's
    solve respects independently.
    With a mesh, machines are sharded over devices via shard_map; without,
    the same code runs as a plain vmap (single-process testing path —
    semantics identical by construction).

    Quantized round-0 waves pass narrow ``blocks`` plus a separate fp32
    ``meta`` of shape (M, cap, attr_dim + qcols) — see ``_solve_block``.
    """
    M = blocks.shape[0]
    dead = jnp.zeros((M,), bool) if dead_mask is None else dead_mask
    local = functools.partial(_round_local, k=k, alg=alg, eps=eps,
                              attr_dim=attr_dim, constraint=constraint)
    operands = ((obj, blocks, bmask, keys, dead) if meta is None
                else (obj, blocks, bmask, keys, dead, meta))

    if mesh is None:
        out = jax.jit(local)(*operands)
        return RoundResult(*out)

    ndev = mesh.devices.size
    assert M % ndev == 0, f"M={M} must divide over {ndev} devices"
    spec = P("machines")
    in_specs = (P(), spec, spec, spec, spec)
    if meta is not None:
        in_specs = in_specs + (spec,)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=in_specs,
        out_specs=(spec, spec, spec, spec, spec),
        check_vma=False)  # replicated obj feeds a machine-varying scan carry
    return RoundResult(*jax.jit(fn)(*operands))


def dead_wave_result(machines: int, k: int, width: int) -> RoundResult:
    """The fold contribution of machines that never ran.

    When the fault supervisor drops a whole ingestion wave past its retry
    budget, the wave's machines fold exactly like ``dead_mask`` machines —
    value −inf (can never win the best-solution max), solutions masked out
    (contribute nothing to A_{t+1}; the between-round repartition zeroes
    masked rows, so downstream is bit-identical to any other dead-machine
    encoding) — except their oracle calls are zero: unlike a declared
    ``fail_machines`` failure, which models a machine dying *after* doing
    its work, a dropped wave's machines never received their blocks.
    """
    return RoundResult(
        sol_rows=jnp.zeros((machines, k, width), jnp.float32),
        sol_mask=jnp.zeros((machines, k), bool),
        values=jnp.full((machines,), -jnp.inf, jnp.float32),
        oracle_calls=jnp.zeros((machines,), jnp.int32),
        depth=jnp.zeros((machines,), jnp.int32))


@functools.lru_cache(maxsize=None)
def _resharder(spec: NamedSharding):
    return jax.jit(lambda x: x, out_shardings=spec)


def _place(x, spec: NamedSharding):
    """``x`` laid out as ``spec``.  An array already spread over the
    mesh's devices in another layout (the union between rounds, which the
    repartition leaves replicated) is resharded by a program on those
    devices: ``jax.device_put`` would fetch a replicated array to the host
    to slice it."""
    if (isinstance(x, jax.Array) and x.sharding.device_set == spec.device_set
            and not x.sharding.is_equivalent_to(spec, x.ndim)):
        return _resharder(spec)(x)
    return jax.device_put(x, spec)


def shard_round_inputs(mesh: Mesh, blocks, bmask, keys, meta=None):
    """Place round inputs with the machine axis sharded over the mesh.

    Quantized waves pass the out-of-band ``meta`` operand too; the return
    grows to a 4-tuple so it shards under the same machine layout.
    """
    spec = NamedSharding(mesh, P("machines"))
    arrays = (blocks, bmask, keys) + (() if meta is None else (meta,))
    return tuple(_place(a, spec) for a in arrays)


# On a TPU v5e (libtpu 0.0.34) the round program halted the core
# ("on-device check-failure" in its row gather) on a 2.46 GB wave uploaded
# in one host transfer; the same program ran on waves uploaded as 1.23 GB
# and 0.79 GB transfers and on a 2.46 GB wave made on the device.  Single
# transfers of 2^31 - 8 KiB and 2^31 + 4 KiB of other data read back and
# gathered correctly, so transfer size alone does not explain the fault.
# Uploads travel in pieces no larger than the largest transfer seen to run
# and are joined on the device.
LARGEST_GOOD_TRANSFER_BYTES = 1_228_800_000
MAX_TRANSFER_BYTES = 1 << 30


def _upload_to(x: np.ndarray, device) -> jax.Array:
    pieces = -(-x.nbytes // MAX_TRANSFER_BYTES)
    if pieces <= 1:
        return jax.device_put(x, device)
    return jnp.concatenate([jax.device_put(p, device)
                            for p in np.array_split(x, pieces)])


def upload(x, sharding=None) -> jax.Array:
    """``jax.device_put`` of a host array in transfers of at most
    ``MAX_TRANSFER_BYTES`` per device, each device's part joined there."""
    x = np.asarray(x)
    if sharding is None or x.nbytes <= MAX_TRANSFER_BYTES:
        return _upload_to(x, sharding)
    shards = [_upload_to(x[idx], dev) for dev, idx in
              sharding.addressable_devices_indices_map(x.shape).items()]
    return jax.make_array_from_single_device_arrays(x.shape, sharding, shards)


def stage_wave_inputs(mesh: Mesh | None, blocks_np, bmask_np, meta_np=None,
                      tracer=None, wave: int | None = None):
    """Host→device staging of one ingestion wave's gathered buffers.

    The async engine produces waves as host numpy (gather runs on a
    prefetch thread that must not touch JAX); this is the single explicit
    upload boundary where those buffers become device arrays — placed
    with the machine axis sharded over the mesh when one is given, so the
    copy lands directly in the round layout instead of being replicated
    and re-sharded at dispatch.  Once it returns, the host buffers are
    dead and the engine may release their in-flight credit (the
    backpressure accounting in :mod:`repro.engine.scheduler`).

    Each device's share of every buffer is put in one ``wave.put`` span
    (``device`` = its position on the mesh's machine axis), in transfers
    of at most ``MAX_TRANSFER_BYTES``; the span covers issuing the
    transfers, and the caller waits for them to land.

    Quantized waves add the out-of-band ``meta_np`` matrix (attr + dequant
    columns); the return grows to a 3-tuple so narrow feature blocks and
    their fp32 metadata stage under the same sharding.
    """
    arrays = tuple(np.asarray(a) for a in (blocks_np, bmask_np)
                   + (() if meta_np is None else (meta_np,)))
    if mesh is None:
        with span("wave.put", tracer=tracer, wave=wave, device=0):
            return tuple(_upload_to(a, None) for a in arrays)
    spec = NamedSharding(mesh, P("machines"))
    maps = [spec.addressable_devices_indices_map(a.shape) for a in arrays]
    parts: list[list] = [[] for _ in arrays]
    for pos, dev in enumerate(mesh.devices.flat):
        if dev not in maps[0]:
            continue                          # another process's device
        with span("wave.put", tracer=tracer, wave=wave, device=pos):
            for a, m, out in zip(arrays, maps, parts):
                out.append(_upload_to(a[m[dev]], dev))
    return tuple(jax.make_array_from_single_device_arrays(a.shape, spec, p)
                 for a, p in zip(arrays, parts))


def device_bytes(mesh: Mesh | None, arrays) -> list[int]:
    """Bytes of ``arrays`` held on each device, by mesh position."""
    if mesh is None:
        return [sum(a.nbytes for a in arrays)]
    pos = {dev: i for i, dev in enumerate(mesh.devices.flat)}
    out = [0] * len(pos)
    for a in arrays:
        for s in a.addressable_shards:
            out[pos[s.device]] += s.data.nbytes
    return out
