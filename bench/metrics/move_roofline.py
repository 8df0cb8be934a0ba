"""Share (%) of the HBM roofline that the mover reaches: the least bytes
any implementation moves per iteration, over the chip's HBM bandwidth,
over the mover's device time per iteration (its XLA module, the one that
runs the Pallas kernels).

The bytes depend on the graph alone: each directed edge slot reads its
neighbour id, the neighbour's label and its weight (12 B), and each vertex
reads and writes its label (8 B): ``12 |E| + 8 |V|``. The mover does no
arithmetic worth a compute bound, so bandwidth bounds it."""


def min_bytes(n_nodes: int, n_edges: int) -> int:
    return 12 * n_edges + 8 * n_nodes


def read(r):
    if r.trace is None or not r.peaks:
        return None
    s = r.trace.module_s_with("tpu_custom_call") / r.solves[0].iterations
    if s <= 0:
        return None
    least = min_bytes(r.graph.n_nodes, r.graph.n_edges) / r.peaks["hbm_bytes_per_s"]
    return 100.0 * least / s
