"""Graph500 Kronecker (R-MAT) edge generator.

The Graph500 specification's generator: ``edge_factor * 2**scale`` edges,
each placed by ``scale`` quadrant draws with probabilities A, B, C and
D = 1 - A - B - C. The draws follow the same loop as the program's own
``rmat`` generator. With ``weighted``, each drawn edge then gets a weight
uniform in [0, 1), as the specification's kernel 3 (single-source shortest
paths) draws them. Graph500 then permutes the vertex ids, which spreads
the hubs over the id space; ``benchlib.graphs`` does that for every
family, from the run's seed.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict, rng: np.random.Generator):
    """Returns ([E, 2] int64 edge list, vertex count, [E] float64 weights
    or None for a weight of 1 each)."""
    scale, edge_factor = int(params["scale"]), int(params["edge_factor"])
    a, b, c = float(params["a"]), float(params["b"]), float(params["c"])
    n = 1 << scale
    m = n * edge_factor
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    ab, abc = a + b, a + b + c
    for bit in range(scale):
        r = rng.random(m)
        right = r >= ab
        src |= right.astype(np.int64) << bit
        dst |= ((~right & (r >= a)) | (right & (r >= abc))).astype(np.int64) << bit
    weights = rng.random(m) if params.get("weighted") else None
    return np.stack([src, dst], axis=1), n, weights
