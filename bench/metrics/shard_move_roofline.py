"""Share (%) of the HBM roofline that one chip's shard mover reaches: the
least bytes any implementation moves per iteration on one of the P chips
(the trace's device planes), over the chip's HBM bandwidth, over that
chip's device time per iteration of the sharded step (the XLA module
that runs the Pallas kernels, averaged over the chips; it holds the
exchange too).

The bytes depend on the graph alone, split evenly over the chips: each
directed edge slot reads its neighbour id, the neighbour's label and its
weight (12 B), and each vertex reads and writes its label (8 B):
``(12 |E| + 8 |V|) / P``. The mover does no arithmetic worth a compute
bound, so bandwidth bounds it."""


def min_bytes(n_nodes: int, n_edges: int, chips: int) -> float:
    return (12 * n_edges + 8 * n_nodes) / chips


def read(r):
    if r.trace is None or not r.peaks or not r.trace.devices:
        return None
    s = r.trace.module_s_with("tpu_custom_call") / r.solves[0].iterations
    if s <= 0:
        return None
    least = min_bytes(r.graph.n_nodes, r.graph.n_edges,
                      len(r.trace.devices)) / r.peaks["hbm_bytes_per_s"]
    return 100.0 * least / s
