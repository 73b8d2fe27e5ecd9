"""Independent NumPy fp64 re-score of a coreset under the exemplar objective.

``f(S) = mean_e w_e ||e||^2 - mean_e w_e min(||e||^2, min_{s in S} ||e - s||^2)``
(paper §4.2, with the phantom exemplar e0 = 0; the weights ``w`` are all 1
unless a served request brings its own).
"""
from __future__ import annotations

import numpy as np


def exemplar_value(E, rows, mask=None, weights=None) -> float:
    E = np.asarray(E, np.float64)
    S = np.asarray(rows, np.float64)
    if mask is not None:
        S = S[np.asarray(mask, bool)]
    w = (np.ones(len(E)) if weights is None
         else np.asarray(weights, np.float64))
    e0 = np.sum(E * E, axis=1)
    if len(S) == 0:
        return 0.0
    d2 = e0[:, None] - 2.0 * E @ S.T + np.sum(S * S, axis=1)[None, :]
    cur = np.minimum(e0, d2.min(axis=1))
    return float(np.mean(w * e0) - np.mean(w * cur))
