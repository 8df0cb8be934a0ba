"""Modularity (the paper's Eq. 1), accumulated on the host in float64.

Q = sum_c [ sigma_c / 2m - (Sigma_c / 2m)^2 ], with both directions of
every edge stored, so the intra-community weight counts each edge twice
as 2*sigma_c does. Float64 because float32 sums of a giant community stall
once they pass 2^24.
"""
from __future__ import annotations

import numpy as np


def modularity(offsets: np.ndarray, indices: np.ndarray, weights: np.ndarray,
               labels: np.ndarray) -> float:
    n = len(offsets) - 1
    src = np.repeat(np.arange(n), np.diff(offsets))
    w = weights.astype(np.float64)
    two_m = w.sum()
    intra2 = w[labels[src] == labels[indices]].sum()
    k_i = np.bincount(src, weights=w, minlength=n)
    sigma_tot = np.bincount(labels, weights=k_i, minlength=n)
    return float(intra2 / two_m - np.sum((sigma_tot / two_m) ** 2))
