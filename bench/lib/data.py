"""Ground sets made from the seed (a copy of the Tiny Images analog).

Same construction as the program's ``datasets.tiny`` at the time this
benchmark was written: 50 Gaussian clusters plus 2% outliers, centred and
scaled to unit norm, d = 3,072 (paper §4.1).  Kept here so a change to the
program's generator cannot change what the benchmark measures.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 8_192


def tiny(n: int, d: int, seed: int, n_clusters: int = 50) -> np.ndarray:
    """(n, d) fp32 unit-norm clustered rows; chunk i draws from (seed, i)."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_clusters, d), dtype=np.float32)
    assign = rng.integers(0, n_clusters, n)
    n_out = int(0.02 * n)
    X = np.empty((n, d), np.float32)
    starts = range(0, n, CHUNK_ROWS)

    def fill(s):
        blk = X[s:s + CHUNK_ROWS]
        np.random.default_rng((seed, s // CHUNK_ROWS)).standard_normal(
            dtype=np.float32, out=blk)
        o = min(max(n_out - s, 0), len(blk))
        blk[:o] *= 3.0
        blk[o:] *= 0.5
        blk[o:] += centers[assign[s + o:s + len(blk)]]
        return blk.sum(0, dtype=np.float64)

    def normalize(s, mean):
        blk = X[s:s + CHUNK_ROWS]
        blk -= mean
        blk /= np.maximum(np.sqrt(np.einsum("ij,ij->i", blk, blk))[:, None],
                          1e-9)

    with ThreadPoolExecutor(min(os.cpu_count() or 1, 16)) as pool:
        mean = (sum(pool.map(fill, starts)) / n).astype(np.float32)
        list(pool.map(lambda s: normalize(s, mean), starts))
    return X


def eval_set(data: np.ndarray, n_eval: int, seed: int) -> np.ndarray:
    """|E| rows of the ground set drawn without replacement from the seed."""
    pick = np.random.default_rng((seed, 0xE7A1)).choice(
        len(data), n_eval, replace=False)
    return np.ascontiguousarray(data[np.sort(pick)])
