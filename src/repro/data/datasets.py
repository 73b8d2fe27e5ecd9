"""Deterministic synthetic analogs of the paper's datasets (§4.1).

The originals (CSN, Tiny Images, Parkinsons, Yahoo Webscope R6A) are not
redistributable/offline; these generators match (n, d) and the qualitative
structure (clustered point clouds with outliers) so the paper's *relative*
claims — error w.r.t. centralized greedy vs. capacity — are reproducible.
Absolute objective values differ by construction; see EXPERIMENTS.md.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

TINY_CHUNK_ROWS = 8_192      # rows per generation task in :func:`tiny`


def _clusters(rng, n, d, n_clusters, spread=0.25, outlier_frac=0.02):
    centers = rng.standard_normal((n_clusters, d)).astype(np.float32)
    assign = rng.integers(0, n_clusters, n)
    X = centers[assign] + spread * rng.standard_normal((n, d)).astype(np.float32)
    n_out = int(outlier_frac * n)
    X[:n_out] = 3.0 * rng.standard_normal((n_out, d)).astype(np.float32)
    return X


def parkinsons(n=5_800, d=22, seed=0):
    """Biomedical voice measurements analog; normalized rows (paper §4.1)."""
    X = _clusters(np.random.default_rng(seed), n, d, 12)
    X -= X.mean(0)
    X /= np.maximum(np.linalg.norm(X, axis=1, keepdims=True), 1e-9)
    return X


def webscope(n=100_000, d=6, seed=1):
    """Yahoo! R6A user-visit features analog (d=6)."""
    return _clusters(np.random.default_rng(seed), n, d, 30, spread=0.4)


def csn(n=20_000, d=17, seed=2):
    """Community Seismic Network accelerometer features analog."""
    return _clusters(np.random.default_rng(seed), n, d, 20, spread=0.3)


def tiny(n=10_000, d=3_072, seed=3, n_clusters=50):
    """Tiny Images analog; zero-mean unit-norm rows (paper §4.1).

    Built in place in fp32, ``TINY_CHUNK_ROWS`` rows per task on a thread
    pool (chunk i draws from its own ``(seed, i)`` stream), so the paper's
    n = 10⁶ × 3,072 set (12.3 GB) needs little more host memory than
    itself and is made in seconds, not minutes.
    """
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_clusters, d), dtype=np.float32)
    assign = rng.integers(0, n_clusters, n)
    n_out = int(0.02 * n)                     # outliers, as in _clusters
    X = np.empty((n, d), np.float32)
    chunk_rows = TINY_CHUNK_ROWS
    starts = range(0, n, chunk_rows)

    def fill(s):
        blk = X[s:s + chunk_rows]
        np.random.default_rng((seed, s // chunk_rows)).standard_normal(
            dtype=np.float32, out=blk)
        o = min(max(n_out - s, 0), len(blk))  # this chunk's outlier rows
        blk[:o] *= 3.0
        blk[o:] *= 0.5
        blk[o:] += centers[assign[s + o:s + len(blk)]]
        return blk.sum(0, dtype=np.float64)

    def normalize(s, mean):
        blk = X[s:s + chunk_rows]
        blk -= mean
        blk /= np.maximum(np.sqrt(np.einsum("ij,ij->i", blk, blk))[:, None],
                          1e-9)

    with ThreadPoolExecutor(os.cpu_count()) as pool:
        mean = (sum(pool.map(fill, starts)) / n).astype(np.float32)
        list(pool.map(lambda s: normalize(s, mean), starts))
    return X


def large_scale(n=200_000, d=64, seed=4):
    """Stand-in for the 1M Tiny / 45M Webscope large-scale runs, sized for
    this CPU container; capacity ratios (0.05%, 0.1%) are preserved."""
    return _clusters(np.random.default_rng(seed), n, d, 100, spread=0.4)


REGISTRY = {
    "parkinsons": parkinsons,
    "webscope-100k": webscope,
    "csn-20k": csn,
    "tiny-10k": tiny,
    "large-scale": large_scale,
}
