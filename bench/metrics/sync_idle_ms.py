"""Device-idle milliseconds per iteration inside the host loop's
``lpa.sync.*`` spans (``repro.core.obs``: each device-to-host read) and
its own code between them (``lpa.iteration`` innermost): each idle
instant of the traced solve charged to the innermost ``lpa.*`` span
covering it."""
from benchlib import spans


def read(r):
    if r.trace is None:
        return None
    s = spans.idle_in(r.trace, "lpa.sync.", "lpa.iteration")
    return None if s is None else 1e3 * s / r.solves[0].iterations
