"""Device milliseconds per iteration of the jitted ``mark_frontier``
(one segment-max over every edge): its XLA module in the trace."""


def read(r):
    if r.trace is None:
        return None
    s = r.trace.module_s("jit_mark_frontier")
    return 1e3 * s / r.solves[0].iterations if s > 0 else None
