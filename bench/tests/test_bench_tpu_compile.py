"""Compile the cells' round-0 solves for a described TPU v5e, no chip.

The batch cell's wave program (``run_round`` over W = 200 machines of
1,000 x 3,072 fp32 rows against 512 exemplars, k = 50) is lowered for one
described chip as ``core/distributed.run_round`` builds it (machines
sharded over a one-device mesh); the reference's greedy over the uploaded
ground set is compiled at the cell's size too.  Each compile must fit the
chip's 16 GB.

The topology is described in a module-scoped fixture, never at import:
only one process at a time may load libtpu, and every xdist worker imports
this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from bench.lib import manifest, tree_ref

HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any libtpu failure means no TPU
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a cached entry compiled for a described chip cannot be read back
    # without one, so keep the persistent cache out of these compiles
    jax.config.update("jax_enable_compilation_cache", False)
    return t


def _cfg(name):
    return manifest.read_json(f"bench/configs/{name}.json")


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def _wave_program(topo, c, devices: int):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import ExemplarClustering
    from repro.core.distributed import _round_local
    W, mu, d = c["wave_machines"], c["mu"], c["d"]
    mesh = Mesh(topo.devices[:devices], ("machines",))
    spec, rep = NamedSharding(mesh, P("machines")), NamedSharding(mesh, P())
    local = functools.partial(_round_local, k=c["k"], alg=c["algorithm"],
                              eps=0.5)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(),) + (P("machines"),) * 4,
                       out_specs=(P("machines"),) * 5, check_vma=False)
    obj = ExemplarClustering(jax.ShapeDtypeStruct((c["n_eval"], d),
                                                  jnp.float32, sharding=rep))
    args = (obj,
            jax.ShapeDtypeStruct((W, mu, d), jnp.float32, sharding=spec),
            jax.ShapeDtypeStruct((W, mu), jnp.bool_, sharding=spec),
            jax.ShapeDtypeStruct((W, 2), jnp.uint32, sharding=spec),
            jax.ShapeDtypeStruct((W,), jnp.bool_, sharding=spec))
    return jax.jit(fn).lower(*args).compile()


def test_round0_wave_compiles_for_the_chip(topo):
    compiled = _wave_program(topo, _cfg("tiny-images-batch"), devices=1)
    per_device = _device_bytes(compiled)
    assert per_device < HBM, per_device


def test_reference_greedy_compiles_at_cell_size(topo):
    from jax.sharding import SingleDeviceSharding
    c = _cfg("tiny-images-batch")
    one = SingleDeviceSharding(topo.devices[0])
    M = 100
    args = (jax.ShapeDtypeStruct((c["n"], c["d"]), jnp.float32, sharding=one),
            jax.ShapeDtypeStruct((M, c["mu"]), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((c["n_eval"], c["d"]), jnp.float32,
                                 sharding=one))
    compiled = tree_ref.greedy_blocks.lower(*args, k=c["k"]).compile()
    assert _device_bytes(compiled) < HBM
