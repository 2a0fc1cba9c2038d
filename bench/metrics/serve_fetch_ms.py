"""Mean ``hmatrix.serve.fetch`` span (one panel's blocking fetch, which
waits for the device too), in ms.  Reads the scoped reduction
(``bench/scopes.py``)."""
from bench import scopes


def read(run):
    r = scopes.scoped(run)
    s = None if r is None else r.program_s("hmatrix.serve.fetch")
    return None if s is None else 1e3 * s
