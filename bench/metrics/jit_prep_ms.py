"""Host milliseconds per solve that JAX spent inside the traced solve
tracing, lowering, compiling or loading programs from the persistent
cache (its compile-phase monitoring events): work ``lpa()`` repeats on
every call because it builds its jitted mover afresh."""


def read(r):
    if r.trace is None:
        return None
    return 1e3 * r.jit_prep_s / len(r.solves)
