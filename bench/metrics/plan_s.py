"""Host seconds of ``build_workspace(graph, config)`` until its device
arrays are ready: the plan build a user pays once per graph."""


def read(r):
    return r.plan_s
