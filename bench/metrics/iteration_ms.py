"""Milliseconds per LPA iteration over the whole window: its host-clock
length over the iterations that its solves ran, each solve being
``lpa(graph, config, ws=ws)`` until its labels are on the host."""


def read(r):
    iters = sum(s.iterations for s in r.solves)
    return 1e3 * r.window_s / iters if iters and r.trace is None else None
