"""k-mer graph stand-in: long chains with occasional short branches.

A de Bruijn-style k-mer graph (the paper's kmer_A2a/kmer_V1r, SuiteSparse
GenBank group) is mostly long unbranched paths of overlapping k-mers with
a few branch points, mean degree about 2.1. This draws one chain over all
vertices plus ``branch_prob * n`` branch edges from a uniform vertex to
one 2 to 49 steps further down, as the program's own ``chain_kmer`` does.
``benchlib.graphs`` permutes the vertex ids from the run's seed, as a
k-mer id (a hash of the sequence) carries no position along the chain.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict, rng: np.random.Generator):
    """Returns ([E, 2] int64 edge list, vertex count, None: every edge
    weighs 1, as the data set is a pattern matrix)."""
    n = int(params["n_vertices"])
    chain = np.stack([np.arange(n - 1, dtype=np.int64),
                      np.arange(1, n, dtype=np.int64)], axis=1)
    n_branch = int(n * float(params["branch_prob"]))
    b_src = rng.integers(0, n, n_branch)
    b_dst = np.minimum(b_src + rng.integers(2, 50, n_branch), n - 1)
    return np.concatenate([chain, np.stack([b_src, b_dst], axis=1)]), n, None
