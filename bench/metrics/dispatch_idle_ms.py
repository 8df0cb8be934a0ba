"""Device-idle milliseconds per iteration inside the host loop's
``lpa.dispatch.*`` spans (``repro.core.obs``: the calls of the jitted
mover and ``mark_frontier`` and the small programs that feed them, with
any re-trace or cache load): each idle instant of the traced solve
charged to the innermost ``lpa.*`` span covering it."""
from benchlib import spans


def read(r):
    if r.trace is None:
        return None
    s = spans.idle_in(r.trace, "lpa.dispatch.")
    return None if s is None else 1e3 * s / r.solves[0].iterations
