"""Streaming ground-set engine: wave-scheduled round-0 ingestion must be
bit-identical to the all-resident driver, with device footprint bounded by
W·μ candidate rows (the paper's fixed-capacity premise)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (ArraySource, ChunkedSource, ExemplarClustering,
                        TreeConfig, WeightedCoverage, tree_maximize)
from repro.core import tree as tree_lib
from repro.data.sources import ShardedSource, synthetic_sharded_source


def _setup(n=601, d=8, ne=128, seed=0):
    r = np.random.default_rng(seed)
    data = r.standard_normal((n, d)).astype(np.float32)
    E = data[r.choice(n, ne, replace=False)]
    return data, ExemplarClustering(jnp.asarray(E))


def _assert_identical(a, b):
    np.testing.assert_array_equal(a.sel_rows, b.sel_rows)
    np.testing.assert_array_equal(a.sel_mask, b.sel_mask)
    assert a.value == b.value                      # bit-identical, no rtol
    assert a.oracle_calls == b.oracle_calls
    assert a.rounds == b.rounds
    assert a.machines_per_round == b.machines_per_round
    assert a.round_values == b.round_values


@pytest.mark.parametrize("wave", [1, 3, 7])
def test_wave_sizes_bit_identical_to_resident(wave):
    data, obj = _setup()
    cfg = TreeConfig(k=8, capacity=60, seed=3)
    resident = tree_maximize(obj, jnp.asarray(data), cfg)
    streamed = tree_maximize(obj, ArraySource(jnp.asarray(data)), cfg,
                             wave_machines=wave)
    _assert_identical(resident, streamed)
    assert streamed.ingest is not None and resident.ingest is None
    assert streamed.ingest.peak_wave_rows <= wave * cfg.capacity


@pytest.mark.parametrize("make_source", [
    lambda d: ChunkedSource.from_array(d, 97),
    lambda d: ShardedSource.from_arrays([d[s:s + 130]
                                         for s in range(0, len(d), 130)]),
], ids=["chunked", "sharded"])
def test_source_kinds_bit_identical(make_source):
    data, obj = _setup(seed=1)
    cfg = TreeConfig(k=8, capacity=60, seed=5)
    resident = tree_maximize(obj, jnp.asarray(data), cfg)
    streamed = tree_maximize(obj, make_source(data), cfg, wave_machines=4)
    _assert_identical(resident, streamed)


@pytest.mark.parametrize("alg", ["greedy", "threshold_greedy"])
@pytest.mark.parametrize("objective", ["exemplar", "coverage"])
def test_objectives_algorithms_matrix(alg, objective):
    if objective == "exemplar":
        data, obj = _setup(n=450, seed=2)
    else:
        r = np.random.default_rng(7)
        data = (r.random((450, 24)) < 0.25).astype(np.float32)
        obj = WeightedCoverage(jnp.asarray(r.random(24).astype(np.float32)))
    cfg = TreeConfig(k=6, capacity=50, seed=4, algorithm=alg, eps=0.3)
    resident = tree_maximize(obj, jnp.asarray(data), cfg)
    streamed = tree_maximize(obj, ChunkedSource.from_array(data, 64), cfg,
                             wave_machines=3)
    _assert_identical(resident, streamed)


def test_stochastic_greedy_streaming_identity():
    data, obj = _setup(seed=8)
    cfg = TreeConfig(k=8, capacity=60, seed=6, algorithm="stochastic_greedy",
                     eps=0.2)
    resident = tree_maximize(obj, jnp.asarray(data), cfg)
    streamed = tree_maximize(obj, ArraySource(data), cfg, wave_machines=2)
    _assert_identical(resident, streamed)


def test_failure_injection_streaming_identity():
    data, obj = _setup(seed=9)
    cfg = TreeConfig(k=8, capacity=60, seed=7)
    resident = tree_maximize(obj, jnp.asarray(data), cfg,
                             fail_machines={0: [0, 2], 1: [1]})
    streamed = tree_maximize(obj, ChunkedSource.from_array(data, 128), cfg,
                             wave_machines=2, fail_machines={0: [0, 2], 1: [1]})
    _assert_identical(resident, streamed)


@pytest.mark.parametrize("engine", ["sync", "pipelined"])
def test_footprint_guard_wave_never_exceeds_W_mu(monkeypatch, engine):
    """The ingestion waves must never materialize more than W·μ candidate
    rows on device — checked at the actual round-dispatch boundary, under
    both wave engines (pipelining overlaps *host* gathers; it must not
    widen the device-resident window)."""
    data, obj = _setup(n=900, seed=3)
    mu, W = 60, 2
    cfg = TreeConfig(k=8, capacity=mu, seed=1, engine=engine)
    shapes = []
    real_run_round = tree_lib.run_round

    def spy(obj_, blocks, bmask, keys, **kw):
        shapes.append(tuple(blocks.shape))
        return real_run_round(obj_, blocks, bmask, keys, **kw)

    monkeypatch.setattr(tree_lib, "run_round", spy)
    res = tree_maximize(obj, ChunkedSource.from_array(data, 128), cfg,
                        wave_machines=W)
    n_waves = res.ingest.waves
    ingest_shapes = shapes[:n_waves]          # round-0 wave dispatches
    assert ingest_shapes, "no ingestion waves recorded"
    for M, cap, d in ingest_shapes:
        assert M * cap <= W * mu, (M, cap)
    # every dispatch (any round) stays far below the resident ground set
    assert max(M * cap for M, cap, _ in shapes) < len(data)
    assert res.ingest.peak_wave_rows == max(M * cap for M, cap, _ in ingest_shapes)
    assert res.ingest.peak_wave_bytes == res.ingest.peak_wave_rows * data.shape[1] * 4


def test_footprint_guard_capacity_bytes(monkeypatch):
    """Weighted-μ capacity: a device-byte budget must bound every wave's
    dispatched bytes at the round-dispatch boundary (width = d + a)."""
    data, obj = _setup(n=900, seed=3)
    mu, d = 60, data.shape[1]
    budget = 3 * mu * d * 4
    shapes = []
    real_run_round = tree_lib.run_round

    def spy(obj_, blocks, bmask, keys, **kw):
        shapes.append(tuple(blocks.shape))
        return real_run_round(obj_, blocks, bmask, keys, **kw)

    monkeypatch.setattr(tree_lib, "run_round", spy)
    res = tree_maximize(obj, ChunkedSource.from_array(data, 128),
                        TreeConfig(k=8, capacity=mu, seed=1,
                                   capacity_bytes=budget))
    for M, cap, width in shapes[:res.ingest.waves]:
        assert M * cap * width * 4 <= budget, (M, cap, width)
    assert res.ingest.peak_wave_bytes <= budget


def test_synthetic_sharded_source_streams_and_matches_materialized():
    src = synthetic_sharded_source(n=700, d=6, shard_rows=150, seed=5)
    assert src.n == 700 and src.d == 6
    full = src.materialize()
    assert full.shape == (700, 6)
    idx = np.asarray([0, 149, 150, 699, 3])
    np.testing.assert_array_equal(src.gather(idx), full[idx])
    obj = ExemplarClustering(jnp.asarray(full[:96]))
    cfg = TreeConfig(k=5, capacity=70, seed=2)
    resident = tree_maximize(obj, jnp.asarray(full), cfg)
    streamed = tree_maximize(obj, src, cfg, wave_machines=3)
    _assert_identical(resident, streamed)


def test_mesh_streaming_identity():
    data, obj = _setup(seed=4)
    from repro.core import make_submod_mesh
    mesh = make_submod_mesh()
    cfg = TreeConfig(k=8, capacity=60, seed=2)
    resident = tree_maximize(obj, jnp.asarray(data), cfg, mesh=mesh)
    streamed = tree_maximize(obj, ChunkedSource.from_array(data, 100), cfg,
                             mesh=mesh, wave_machines=mesh.devices.size)
    _assert_identical(resident, streamed)


@pytest.mark.parametrize("use_mesh", [False, True], ids=["plain", "mesh"])
def test_waves_uploaded_in_pieces_identical(monkeypatch, use_mesh):
    # waves larger than one transfer are uploaded in pieces and joined on
    # the device; the rows, sharding and result must not change
    from repro.core import distributed, make_submod_mesh
    data, obj = _setup(seed=6)
    mesh = make_submod_mesh() if use_mesh else None
    cfg = TreeConfig(k=8, capacity=60, seed=2)
    whole = tree_maximize(obj, ArraySource(data), cfg, mesh=mesh,
                          wave_machines=4)
    monkeypatch.setattr(distributed, "MAX_TRANSFER_BYTES", 1000)
    sizes = []
    put = jax.device_put

    def spy(x, *a, **kw):
        sizes.append(np.asarray(x).nbytes)
        return put(x, *a, **kw)

    monkeypatch.setattr(distributed.jax, "device_put", spy)
    x = data[:40]
    sharding = None if mesh is None else jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("machines"))
    up = distributed.upload(x, sharding)
    np.testing.assert_array_equal(np.asarray(up), x)
    assert len(sizes) > 1 and max(sizes) <= 1000, sizes
    if sharding is not None:
        assert up.sharding == sharding
    pieced = tree_maximize(obj, ArraySource(data), cfg, mesh=mesh,
                           wave_machines=4)
    _assert_identical(whole, pieced)


def test_upload_guard_within_largest_good_transfer():
    # the piece size may not grow past the largest host transfer the round
    # program was seen to run on (see distributed.MAX_TRANSFER_BYTES)
    from repro.core import distributed
    assert (0 < distributed.MAX_TRANSFER_BYTES
            <= distributed.LARGEST_GOOD_TRANSFER_BYTES)


def test_host_rounds_rejects_sources():
    data, obj = _setup()
    with pytest.raises(ValueError):
        tree_maximize(obj, ArraySource(data), TreeConfig(k=8, capacity=60),
                      host_rounds=True)


def test_single_machine_ground_set_streams():
    """μ ≥ n: one machine, one wave, still exact."""
    data, obj = _setup(n=80, ne=48)
    cfg = TreeConfig(k=8, capacity=100, seed=0)
    resident = tree_maximize(obj, jnp.asarray(data), cfg)
    streamed = tree_maximize(obj, ChunkedSource.from_array(data, 33), cfg)
    _assert_identical(resident, streamed)
    assert streamed.rounds == 1 and streamed.ingest.waves == 1


# ---------------------------------------------------------------------------
# Feistel slot permutation: O(1)-state round-0 virtual locations
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 2, 7, 64, 1000, 4097])
def test_feistel_is_a_bijection(n):
    from repro.core.permute import FeistelPermutation
    perm = FeistelPermutation.from_key(jax.random.PRNGKey(n), n)
    vals = perm.materialize()
    np.testing.assert_array_equal(np.sort(vals), np.arange(n))


def test_feistel_slices_match_materialized_permutation():
    """The cross-check path: evaluating the cipher per wave-slice must
    reproduce the fully materialized permutation (same seed), so the O(1)
    -state scheme can replace the O(n) host buffer without changing a bit."""
    from repro.core.permute import FeistelPermutation, feistel_slot_items
    n_slots, n_items = 1200, 1100
    perm = FeistelPermutation.from_key(jax.random.PRNGKey(5), n_slots)
    full = feistel_slot_items(perm, n_items,
                              np.arange(n_slots, dtype=np.int64))
    pieces = [feistel_slot_items(perm, n_items,
                                 np.arange(s, min(s + 180, n_slots),
                                           dtype=np.int64))
              for s in range(0, n_slots, 180)]
    np.testing.assert_array_equal(np.concatenate(pieces), full)
    # determinism per seed, distinct across seeds
    perm2 = FeistelPermutation.from_key(jax.random.PRNGKey(5), n_slots)
    np.testing.assert_array_equal(perm2.materialize(), perm.materialize())
    perm3 = FeistelPermutation.from_key(jax.random.PRNGKey(6), n_slots)
    assert not np.array_equal(perm3.materialize(), perm.materialize())


def test_feistel_streaming_bit_identical_to_resident():
    """Under permutation="feistel" the streaming waves evaluate the cipher
    per slice while the resident reference materializes it — outputs must
    match bit for bit (the materialized path is the cross-check)."""
    data, obj = _setup(seed=12)
    cfg = TreeConfig(k=8, capacity=60, seed=3, permutation="feistel")
    resident = tree_maximize(obj, jnp.asarray(data), cfg)
    streamed = tree_maximize(obj, ChunkedSource.from_array(data, 97), cfg,
                             wave_machines=3)
    _assert_identical(resident, streamed)
    # the scheme actually changed the round-0 partition vs dense
    dense = tree_maximize(obj, jnp.asarray(data),
                          TreeConfig(k=8, capacity=60, seed=3))
    assert dense.round_values != resident.round_values or \
        dense.value != resident.value or \
        not np.array_equal(dense.sel_rows, resident.sel_rows)


def test_feistel_host_rounds_matches_device():
    data, obj = _setup(n=400, seed=13)
    cfg = TreeConfig(k=8, capacity=60, seed=1, permutation="feistel")
    dev = tree_maximize(obj, jnp.asarray(data), cfg)
    host = tree_maximize(obj, jnp.asarray(data), cfg, host_rounds=True)
    _assert_identical(dev, host)


def test_invalid_permutation_rejected():
    with pytest.raises(AssertionError):
        TreeConfig(k=4, capacity=40, permutation="riffle")


# ---------------------------------------------------------------------------
# attributed sources: (rows, attrs) pairs through the wave machinery
# ---------------------------------------------------------------------------


def test_attributed_sources_roundtrip_attrs():
    from repro.data.sources import ShardedSource
    data = np.random.default_rng(3).standard_normal((260, 5)).astype(np.float32)
    attrs = np.random.default_rng(4).uniform(0, 1, (260, 2)).astype(np.float32)
    idx = np.asarray([0, 7, 130, 259, 31])
    for src in (ArraySource(data, attrs=attrs),
                ChunkedSource.from_array(data, 64, attrs=attrs),
                ShardedSource.from_arrays(
                    [data[s:s + 90] for s in range(0, 260, 90)],
                    attrs=[attrs[s:s + 90] for s in range(0, 260, 90)])):
        assert src.a == 2
        np.testing.assert_array_equal(src.gather(idx), data[idx])
        np.testing.assert_array_equal(src.gather_attrs(idx), attrs[idx])
        np.testing.assert_array_equal(src.materialize_attrs(), attrs)
        rows2, attrs2 = src.gather_with_attrs(idx)   # single-pass combined
        np.testing.assert_array_equal(rows2, data[idx])
        np.testing.assert_array_equal(attrs2, attrs[idx])


def test_unattributed_source_has_zero_width_attrs():
    data = np.zeros((40, 3), np.float32)
    src = ChunkedSource.from_array(data, 16)
    assert src.a == 0
    assert src.gather_attrs(np.arange(5)).shape == (5, 0)
