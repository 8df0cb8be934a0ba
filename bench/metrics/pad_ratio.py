"""Entry slots of the streamed plan's windows, all rounds, over the graph's
directed edge slots (``streamed_window_slots(ws.stream_plan) / |E|``):
how much padding the plan layout carries into HBM."""


def read(r):
    return r.window_slots / r.graph.n_edges if r.window_slots else None
