"""Device milliseconds per iteration of the label exchange: the
collective ops of the sharded step (the hub ``all-gather``, the halo
``all-to-all``, the moved count's ``all-reduce``, each with its
``-start``/``-done`` halves where the compiler splits it), averaged over
the chips, over the traced solve's iterations.

A device op's name in the trace is its HLO instruction, as in
``all-to-all.1 = s32[4,70081]{...} all-to-all(...)``, so the pattern
matches the opcode: a space, the collective's name, an optional
``-start`` or ``-done``, and the opening parenthesis. A fusion that
reads a collective's result names it only as an operand and does not
match. Only ops that run inside the sharded step count: the XLA module
that runs the Pallas kernels (``tpu_custom_call``), as
``shard_move_roofline`` reads it. The all-reduce of the program that
gathers the labels back to vertex order once a solve is not the
exchange and is left out."""

import bisect
import collections
import re

PATTERN = r" (all-to-all|all-gather|all-reduce)(-start|-done)?\("
STEP_OP = "tpu_custom_call"


def step_windows(summary) -> dict:
    """Per device plane, the sorted (start, end) ns of the modules that
    ran a ``tpu_custom_call`` op."""
    kernel_starts = collections.defaultdict(list)
    for o in summary.ops:
        if STEP_OP in o.name:
            kernel_starts[o.plane].append(o.start_ns)
    for v in kernel_starts.values():
        v.sort()
    out = collections.defaultdict(list)
    for m in summary.modules:
        v = kernel_starts.get(m.plane, [])
        i = bisect.bisect_left(v, m.start_ns)
        if i < len(v) and v[i] < m.end_ns:
            out[m.plane].append((m.start_ns, m.end_ns))
    for v in out.values():
        v.sort()
    return out


def exchange_s(summary) -> float:
    """Device seconds of the step's collectives, averaged over the
    devices traced."""
    rx = re.compile(PATTERN)
    windows = step_windows(summary)
    total = 0.0
    for o in summary.ops:
        if not rx.search(o.name):
            continue
        w = windows.get(o.plane, [])
        i = bisect.bisect_right(w, (o.start_ns, float("inf"))) - 1
        if i >= 0 and o.start_ns < w[i][1]:
            total += o.dur_ns
    return total * 1e-9 / max(len(summary.devices), 1)


def read(r):
    if r.trace is None:
        return None
    s = exchange_s(r.trace)
    return 1e3 * s / r.solves[0].iterations if s > 0 else None
