"""Device time of one H-matrix apply, in ms: the busy union of the chip
inside each ``bench.apply`` span of the traced window, per span."""


def read(run):
    if run.trace is None:
        return None
    s = run.trace.device_s_per_span("bench.apply")
    return None if s is None else 1e3 * s
