"""Multi-device checks run in a subprocess with 8 host devices (the main
pytest process keeps 1 device).  Covers: shard_map TREE round == serial,
failure drop-out on a real mesh, GSPMD train step on a 2x2 debug mesh."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str):
    # children run on the CPU: a chip belongs to the one process that
    # opened it, so a child must never reach for it
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_tree_8dev_equals_serial_and_survives_failures():
    _run("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import ExemplarClustering, TreeConfig, tree_maximize, make_submod_mesh
assert len(jax.devices()) == 8
rng = np.random.default_rng(0)
data = rng.standard_normal((2000, 16)).astype(np.float32)
E = data[rng.choice(2000, 256, replace=False)]
obj = ExemplarClustering(jnp.asarray(E))
cfg = TreeConfig(k=12, capacity=100, seed=3)
trm = tree_maximize(obj, jnp.asarray(data), cfg, mesh=make_submod_mesh())
trs = tree_maximize(obj, jnp.asarray(data), cfg)
assert abs(trm.value - trs.value) < 1e-5, (trm.value, trs.value)
trf = tree_maximize(obj, jnp.asarray(data), cfg, mesh=make_submod_mesh(),
                    fail_machines={0: [0, 1, 2]})
assert trf.value >= 0.8 * trm.value
print("OK")
""")


def test_streamed_tree_on_4_devices_equals_1_device_bit_for_bit():
    # the batch deployment's path on a 4-device mesh: each wave's share
    # lands on its device (one wave.put each), the union stays on the
    # devices between rounds (never fetched to the host), and rounds
    # t >= 1 spread over all four
    _run("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import (ArraySource, ExemplarClustering, TreeConfig,
                        tree_maximize, make_submod_mesh)
from repro.core import partition as part_lib, tree
from repro.engine import MetricsRegistry, Tracer, feed_result_metrics
rng = np.random.default_rng(1)
n, d, mu, k, W = 4800, 16, 100, 10, 16
data = rng.standard_normal((n, d)).astype(np.float32)
E = data[rng.choice(n, 64, replace=False)]
obj = ExemplarClustering(jnp.asarray(E))
devs = jax.devices()[:4]

def cfg(tracer=None):
    return TreeConfig(k=k, capacity=mu, seed=7, engine="pipelined",
                      telemetry=tracer,
                      capacity_bytes=W * mu * d * 4)

unions, rounds = [], []
repartition, dispatch = part_lib.repartition_rows, tree._dispatch_round
def spy_repartition(rows, mask, *a, **kw):
    unions.append(rows)
    return repartition(rows, mask, *a, **kw)
def spy_dispatch(*a, **kw):
    r = dispatch(*a, **kw)
    rounds.append(r.sol_rows)
    return r
part_lib.repartition_rows = spy_repartition
tree._dispatch_round = spy_dispatch
# every device array the run fetches to the host (the CPU backend does not
# enforce jax.transfer_guard, so the fetch itself is watched)
from jax._src import array as array_lib
fetched, value = [], array_lib.ArrayImpl._value
array_lib.ArrayImpl._value = property(
    lambda self: (fetched.append(self.shape), value.fget(self))[1])
tr = Tracer()
r4 = tree_maximize(obj, ArraySource(data), cfg(tr), mesh=make_submod_mesh(devs))
array_lib.ArrayImpl._value = value
part_lib.repartition_rows, tree._dispatch_round = repartition, dispatch
# only round values, counts and the final answer reach the host: the
# union and the blocks of rounds t >= 1 stay on the devices
assert set(fetched) == {(), (k, d), (k,)}, fetched
r1 = tree_maximize(obj, ArraySource(data), cfg(), mesh=make_submod_mesh(devs[:1]))

assert r4.machines_per_round == r1.machines_per_round == [48, 5, 1]
np.testing.assert_array_equal(r4.sel_rows, r1.sel_rows)
np.testing.assert_array_equal(r4.sel_mask, r1.sel_mask)
assert r4.value == r1.value and r4.oracle_calls == r1.oracle_calls
assert r4.round_values == r1.round_values

# between rounds the union is a device array spread over the four devices
assert len(unions) == 2
for u in unions:
    assert isinstance(u, jax.Array) and u.sharding.device_set == set(devs)
    assert len({s.device for s in u.addressable_shards}) == 4
# every round t >= 1 solves a mesh-padded machine axis split four ways
assert [r.shape[0] for r in rounds] == [8, 4]
for r in rounds:
    per = {s.device: s.data.shape[0] for s in r.addressable_shards}
    assert set(per) == set(devs) and set(per.values()) == {r.shape[0] // 4}

es = r4.engine_stats
share = W // 4 * mu * (d * 4 + 1)             # fp32 rows + bool mask
assert es.mesh_devices == 4 and es.waves == 3
assert es.stage_bytes == [[share] * 4] * 3, es.stage_bytes
puts = tr.spans(cat="wave", name="put")
assert sorted((e.args["wave"], e.args["device"]) for e in puts) == [
    (w, i) for w in range(3) for i in range(4)]
stages = tr.spans(cat="wave", name="stage")
assert all(any(s.t0 <= p.t0 and p.t1 <= s.t1 for s in stages)
           for p in puts)
reg = MetricsRegistry()
feed_result_metrics(reg, r4)
snap = reg.snapshot()
assert snap["gauges"]["mesh.devices"] == 4
for i in range(4):
    assert snap["counters"][f"engine.stage_bytes{{device={i}}}"] == 3 * share
assert r1.engine_stats.mesh_devices == 1
assert r1.engine_stats.stage_bytes == [[4 * share]] * 3
print("OK")
""")


def test_gspmd_train_step_2x2_matches_single_device():
    _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.launch.mesh import make_debug_mesh
from repro import sharding as shd
from repro.train import optimizer as opt_lib, train_step as ts_lib
from repro.data.pipeline import DataConfig, SyntheticLM

cfg = get_config("qwen3-8b").reduced()
opt_cfg = opt_lib.OptConfig(lr=1e-3, moment_dtype="float32")
state = ts_lib.init_train_state(cfg, opt_cfg, jax.random.PRNGKey(0))
step = ts_lib.make_train_step(cfg, opt_cfg)
batch = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                               global_batch=4, seed=0)).batch(0)
# single device
s1, m1 = jax.jit(step)(jax.tree.map(lambda x: x, state), batch)

mesh = make_debug_mesh(2, 2)
with jax.set_mesh(mesh):
    shardings = shd.param_sharding_tree(state, mesh)
    state_sh = jax.device_put(state, shardings)
    tok_sh = jax.device_put(batch["tokens"],
                            shd.batch_spec(batch["tokens"].shape, mesh))
    s2, m2 = jax.jit(step)(state_sh, {"tokens": tok_sh})
np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=2e-3)
g1 = float(m1["grad_norm"]); g2 = float(m2["grad_norm"])
np.testing.assert_allclose(g1, g2, rtol=2e-2)
print("OK", g1, g2)
""")


def test_serve_decode_2x2_matches_single_device():
    _run("""
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.mesh import make_debug_mesh
from repro.models import get_model

cfg = get_config("gemma-2b").reduced()
m = get_model(cfg)
params = m.init_params(cfg, jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, cfg.vocab_size)
cache = m.init_cache(cfg, 4, 16)
lp1, c1 = m.prefill(params, cfg, toks, cache)
mesh = make_debug_mesh(2, 2)
with jax.set_mesh(mesh):
    lp2, c2 = jax.jit(lambda p, t, c: m.prefill(p, cfg, t, c))(params, toks, cache)
np.testing.assert_allclose(np.asarray(lp1, np.float32),
                           np.asarray(lp2, np.float32), rtol=6e-2, atol=6e-2)
print("OK")
""")
