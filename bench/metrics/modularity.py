"""Modularity Q of the last solve's labels (the benchmark's own float64
host copy of the paper's Eq. 1)."""
from benchlib.modularity import modularity


def read(r):
    g = r.graph
    return modularity(g.offsets, g.indices, g.weights, r.solves[-1].labels)
