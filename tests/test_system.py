"""End-to-end behaviour tests for the paper's system.

The paper's headline empirical claims (§4.3, Table 3), scaled to CPU:
  1. TREE with severely limited capacity (down to 2k) stays within ~1% of
     centralized GREEDY on clustered data.
  2. RANDOM is far worse.
  3. Approximation quality is insensitive to capacity across a sweep.
Plus: the full LM path — submodular data selection → train a small LM →
loss drops; and serve path generates tokens.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (ExemplarClustering, TreeConfig, centralized_greedy,
                        random_subset, randgreedi, tree_maximize)
from repro.data import datasets
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.data.selection import SelectionConfig, select_coreset
from repro.serve.serve_step import greedy_generate
from repro.train import optimizer as opt_lib
from repro.train import train_step as ts_lib


def _obj(data, ne=512, seed=0):
    r = np.random.default_rng(seed)
    E = data[r.choice(len(data), min(ne, len(data)), replace=False)]
    return ExemplarClustering(jnp.asarray(E))


def test_tree_close_to_centralized_even_at_2k():
    """Paper Fig 2: TREE copes with extremely limited capacity (2k)."""
    data = datasets.csn(n=4000, d=17)
    k = 20
    obj = _obj(data)
    dj = jnp.asarray(data)
    cg = centralized_greedy(obj, dj, k)
    tree = tree_maximize(obj, dj, TreeConfig(k=k, capacity=2 * k, seed=0))
    ratio = tree.value / float(cg.value)
    assert ratio > 0.95, ratio
    assert tree.rounds >= 3  # capacity 2k genuinely forces multiple rounds


def test_relative_error_under_1pct_table3_regime():
    """Paper Table 3: ≤~1% relative error at μ ∈ {200, 400, 800}."""
    data = datasets.parkinsons()
    k = 50
    obj = _obj(data, ne=512)
    dj = jnp.asarray(data)
    cg = float(centralized_greedy(obj, dj, k).value)
    for mu in (200, 400, 800):
        tree = tree_maximize(obj, dj, TreeConfig(k=k, capacity=mu, seed=0))
        rel_err = (cg - tree.value) / cg * 100
        assert rel_err < 2.0, (mu, rel_err)


def test_random_much_worse_than_tree():
    data = datasets.csn(n=4000, d=17)
    k = 20
    obj = _obj(data)
    dj = jnp.asarray(data)
    tree = tree_maximize(obj, dj, TreeConfig(k=k, capacity=100, seed=0))
    rnd = random_subset(obj, dj, k, jax.random.PRNGKey(0))
    assert tree.value > 1.1 * float(rnd.value)


def test_tree_matches_randgreedi_when_capacity_sufficient():
    """Paper: with μ ≥ √(nk) TREE reduces to the two-round regime."""
    data = datasets.parkinsons(n=2000)
    k = 10
    obj = _obj(data)
    dj = jnp.asarray(data)
    mu = int(np.ceil(np.sqrt(2000 * k)))
    tree = tree_maximize(obj, dj, TreeConfig(k=k, capacity=mu, seed=3))
    rg = randgreedi(obj, dj, k, int(np.ceil(2000 / mu)), jax.random.PRNGKey(3))
    assert abs(tree.value - float(rg.value)) / float(rg.value) < 0.05


def test_end_to_end_select_then_train():
    """The production path: distributed selection feeds LM training."""
    rng = np.random.default_rng(1)
    pool = jnp.asarray(rng.standard_normal((600, 32)).astype(np.float32))
    idx, _ = select_coreset(pool, SelectionConfig(k=8, capacity=64,
                                                  n_eval=128, seed=1))
    assert len(idx) == 8

    cfg = get_config("gemma-2b").reduced()
    opt_cfg = opt_lib.OptConfig(lr=3e-3, warmup_steps=2, total_steps=40,
                                moment_dtype="float32")
    state = ts_lib.init_train_state(cfg, opt_cfg, jax.random.PRNGKey(0))
    step = jax.jit(ts_lib.make_train_step(cfg, opt_cfg))
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                  global_batch=8, seed=2))
    first = last = None
    for i in range(15):
        state, m = step(state, data.batch(i % 3))
        first = float(m["loss"]) if first is None else first
        last = float(m["loss"])
    assert last < first


def test_serve_generates():
    cfg = get_config("qwen3-8b").reduced()
    from repro.models import get_model
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    out = greedy_generate(cfg, params, prompt, n_new=5)
    assert out.shape == (2, 5)
    assert bool(jnp.all((out >= 0) & (out < cfg.padded_vocab)))


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    import os
    from repro.launch.compile_cache import CHECKOUT, enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == os.path.join(CHECKOUT, ".jax_cache")
        assert os.path.isfile(os.path.join(CHECKOUT, "chip_smoke.py"))
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_leaves_the_variable_to_jax(monkeypatch, tmp_path):
    from repro.launch.compile_cache import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    prev = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev
