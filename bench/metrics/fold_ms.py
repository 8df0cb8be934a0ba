"""Device milliseconds per iteration of the Pallas fold kernels (the
streamed fold rounds and the last round's fold with selection): the
trace's ``tpu_custom_call`` ops, over the traced solve's iterations."""


def read(r):
    if r.trace is None:
        return None
    s = r.trace.op_s("tpu_custom_call")
    return 1e3 * s / r.solves[0].iterations if s > 0 else None
