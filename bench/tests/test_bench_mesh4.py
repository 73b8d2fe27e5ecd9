"""``batch-tiny-mesh4`` without the chip: the cell rehearsed on four CPU
devices (sound, under planted faults, traced), and its programs compiled
for a described TPU v5e 2x2 at the cell's size.

The rehearsal runs in a child process (``bench/tests/rehearse_mesh4.py``):
the test process keeps one CPU device, and only the child sets the device
count.  The compiles run here, under ``test_bench_tpu_compile``'s
module-scoped ``topo`` fixture.
"""
import json
import os
import subprocess
import sys

import pytest

from bench.lib import manifest
from bench.tests.test_bench_tpu_compile import (  # noqa: F401 (fixture)
    HBM, _device_bytes, _wave_program, topo)

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
WAVE_SHARE = 200 * 1000 * 3072 * 4      # one chip's 200 machines, 2.46 GB


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("mesh4")
    out = tmp / "runs.json"
    # the child runs on the CPU: a chip belongs to the one process that
    # opened it, so a child must never reach for it
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    r = subprocess.run([sys.executable, "-m", "bench.tests.rehearse_mesh4",
                        str(tmp), str(out)], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-6000:]
    with open(out) as f:
        return json.load(f)


def test_mesh4_cell_runs_and_is_correct(runs):
    r = runs["sound"]
    res = r["res"]
    assert r["rc"] == 0, r["err"]
    assert res["correct"] is True, r["err"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"batch_rows_per_s", "setup_s"}
    assert res["device"]["count"] == 4
    assert res["checks"]["answer_gap_ref"]["value"] == 0.0
    assert res["checks"]["value_gap_ref"]["value"] == 0.0
    assert "compiles_in_window=0" in r["err"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered", "answer_swapped"])
def test_mesh4_fault_is_not_correct(runs, fault):
    r = runs[fault]
    assert r["rc"] == 0, r["err"]
    assert r["res"]["correct"] is False, r["res"]["checks"]


def test_mesh4_exchange_left_out_is_not_correct(runs):
    """One device's share of the union dropped before round 1: every row
    is still a ground-set row, but the answer is no longer TREE's."""
    r = runs["exchange_left_out"]
    assert r["rc"] == 0, r["err"]
    c = r["res"]["checks"]
    assert r["res"]["correct"] is False, c
    assert c["rows_off_set"]["value"] == 0, c
    assert c["answer_gap_ref"]["value"] > c["answer_gap_ref"]["limit"], c


def test_mesh4_traced_run_reads_collectives_and_puts(runs):
    r = runs["traced"]
    assert r["rc"] == 0 and r["res"]["correct"], r["err"]
    m = r["res"]["metrics"]
    assert m["collective_s.batch"]["value"] > 0
    assert m["wave_put_s.batch"]["value"] > 0
    # one wave.put per device inside each wave's staging
    assert r["stages"] >= 1 and r["puts"] == 4 * r["stages"], r


def test_mesh4_programs_compile_for_v5e_2x2(topo):
    """The round-0 wave at W = 800 over four described chips (200
    machines, 2.46 GB, each), its fold, the round-1 repartition of the
    union and the reference's greedy over the whole 800,000-row ground set
    on one chip: each fits a chip's 16 GB.  Compiled in this process (only
    one process may load the TPU compiler)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from bench.lib import tree_ref
    from repro.core import partition, tree
    c = manifest.read_json("bench/configs/tiny-images-batch-mesh4.json")
    W, mu, d, k = c["wave_machines"], c["mu"], c["d"], c["k"]
    assert W == 800 and c["n"] == W * mu
    wave = _device_bytes(_wave_program(topo, c, devices=4))
    assert WAVE_SHARE < wave < HBM, wave

    mesh = Mesh(topo.devices[:4], ("machines",))
    spec, rep = NamedSharding(mesh, P("machines")), NamedSharding(mesh, P())
    f32, i32, b = jnp.float32, jnp.int32, jnp.bool_

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    # the fold of the wave's solutions, sharded as run_round returns them
    fold = tree._fold_round.lower(
        sds((W, k, d), f32, spec), sds((W, k), b, spec),
        sds((W,), f32, spec), sds((W,), i32, spec), sds((W,), i32, spec),
        sds((k, d), f32, rep), sds((k,), b, rep), sds((), f32, rep),
        sds((), i32, rep), sds((), i32, rep)).compile()
    # the union A_1 (W * k rows) repartitioned onto round 1's machines
    union = partition.repartition_rows.lower(
        sds((W * k, d), f32, spec), sds((W * k,), b, spec),
        sds((2,), jnp.uint32, rep), L=W * k // mu, cap=mu).compile()
    # the reference holds the whole ground set on one chip
    one = SingleDeviceSharding(topo.devices[0])
    ref = tree_ref.greedy_blocks.lower(
        sds((c["n"], d), f32, one), sds((100, mu), i32, one),
        sds((c["n_eval"], d), f32, one), k=k).compile()
    for name, compiled in (("fold", fold), ("repartition", union),
                           ("reference", ref)):
        assert 0 < _device_bytes(compiled) < HBM, name


def test_collective_reader_reads_each_jobs_collectives():
    """``collective_s.batch`` on hand-made planes: collective ops inside
    each ``bench.job``, averaged over devices, then over jobs."""
    import types

    from bench.lib import trace

    def plane(name, lines):
        return types.SimpleNamespace(name=name, lines=[
            types.SimpleNamespace(name=ln, events=[
                types.SimpleNamespace(name=n, start_ns=round(s * 1e9),
                                      duration_ns=round((e - s) * 1e9))
                for n, s, e in evs]) for ln, evs in lines.items()])

    host = {"python": [("bench.window", 0.0, 10.0), ("bench.job", 0.5, 5.0),
                       ("bench.job", 5.5, 9.0)]}
    ops0 = [("%all-gather.1 = f32[8] all-gather(...)", 1.0, 1.4),
            ("%fusion.2 = f32[8] fusion(...)", 2.0, 3.0),
            ("%all-reduce.3 = f32[] all-reduce(...)", 6.0, 6.2)]
    ops1 = [("%all-gather.1 = f32[8] all-gather(...)", 1.0, 1.2)]
    reader = manifest.metric_reader("collective_s.batch")
    tr = trace.Trace([plane("/host:CPU", host),
                      plane("/device:TPU:0", {"XLA Ops": ops0}),
                      plane("/device:TPU:1", {"XLA Ops": ops1})])
    got = reader.read({"trace": tr})
    assert got == pytest.approx(((0.4 + 0.2) / 2 + 0.2 / 2) / 2, rel=1e-6)
    # no device operations, or no trace: nothing to read
    bare = trace.Trace([plane("/host:CPU", host)])
    assert reader.read({"trace": bare}) is None
    assert reader.read({"trace": None}) is None
