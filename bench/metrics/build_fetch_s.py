"""Mean ``hmatrix.build.fetch`` span (the plan's metadata to the host and
the plan's assembly), in s; the device time inside it.  Reads the scoped
reduction (``bench/scopes.py``)."""
from bench import scopes

SPAN = "hmatrix.build.fetch"


def read(run):
    r = scopes.scoped(run)
    s = None if r is None else r.program_s(SPAN)
    if s is None:
        return None
    return s, {"device_s": r.program_device_ns[SPAN] / len(r.program[SPAN])
               / 1e9}
