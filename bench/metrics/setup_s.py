"""Seconds from the process's start to the window: reading the graph and
renumbering it, placing it on the device, the plan build and one warm
solve, which compiles or loads every program (host clock). Generating a
graph the cache lacks is the benchmark's own cost and is left out."""


def read(r):
    return r.setup_s
