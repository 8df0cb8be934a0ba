"""Device-to-host reads per iteration of the traced solve: its
``lpa.sync.*`` spans, which ``repro.core.obs.sync`` opens once for each
read it counts into ``LPAResult.host_syncs``."""
from benchlib import spans


def read(r):
    if r.trace is None:
        return None
    n = spans.sync_count(r.trace)
    return n / r.solves[0].iterations if n else None
