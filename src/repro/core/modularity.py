"""Community quality metrics: modularity (paper Eq. 1) and NMI."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.graphs.csr import CSRGraph


def modularity(graph: CSRGraph, labels: jnp.ndarray,
               edge_src: jnp.ndarray | None = None) -> float:
    """Q = sum_c [ sigma_c / 2m - (Sigma_c / 2m)^2 ]  (paper Eq. 1).

    sigma_c counts both directions of every intra-community edge, matching
    2*sigma_c of the undirected formulation — our CSR stores both directions.
    Accumulated on the host in float64: float32 sums of a giant community
    stall once they pass 2^24 (a graph500-22 run read Q = -0.78 that way).
    """
    n = graph.n_nodes
    src = np.asarray(graph.sources() if edge_src is None else edge_src)
    lab = np.asarray(labels)
    w = np.asarray(graph.weights, dtype=np.float64)
    two_m = w.sum()  # = 2m (both directions stored)
    intra2 = w[lab[src] == lab[np.asarray(graph.indices)]].sum()
    k_i = np.bincount(src, weights=w, minlength=n)          # weighted degree
    sigma_tot = np.bincount(lab, weights=k_i, minlength=n)  # Sigma_c
    return float(intra2 / two_m - np.sum((sigma_tot / two_m) ** 2))


def nmi(labels_a: np.ndarray, labels_b: np.ndarray) -> float:
    """Normalized mutual information between two disjoint partitions."""
    a = np.asarray(labels_a)
    b = np.asarray(labels_b)
    n = len(a)
    _, ai = np.unique(a, return_inverse=True)
    _, bi = np.unique(b, return_inverse=True)
    na, nb = ai.max() + 1, bi.max() + 1
    cont = np.zeros((na, nb), dtype=np.float64)
    np.add.at(cont, (ai, bi), 1.0)
    pa = cont.sum(1) / n
    pb = cont.sum(0) / n
    pab = cont / n
    with np.errstate(divide="ignore", invalid="ignore"):
        mi = np.nansum(pab * np.log(pab / (pa[:, None] * pb[None, :])))
        ha = -np.nansum(pa * np.log(pa))
        hb = -np.nansum(pb * np.log(pb))
    denom = np.sqrt(ha * hb)
    return float(mi / denom) if denom > 0 else 1.0


def community_sizes(labels: np.ndarray) -> np.ndarray:
    """Sorted community sizes (descending)."""
    _, counts = np.unique(np.asarray(labels), return_counts=True)
    return np.sort(counts)[::-1]
