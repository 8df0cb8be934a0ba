"""Device milliseconds per iteration of the jitted mover ``lpa_move``
(neighbour-label gather, fold rounds, selection): the XLA module that runs
the Pallas fold kernels (``tpu_custom_call``), over the traced solve's
iterations."""


def read(r):
    if r.trace is None:
        return None
    s = r.trace.module_s_with("tpu_custom_call")
    return 1e3 * s / r.solves[0].iterations if s > 0 else None
