"""Device time of one build's ACA launches, in s: the operations under
``hmatrix.build.aca.L{level}`` per ``bench.build`` span; per level.
Reads the scoped reduction (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    r = scopes.scoped(run)
    if r is None or not r.spans.get("bench.build"):
        return None
    levels = r.family("hmatrix.build.aca.")
    if not levels:
        return None
    n = len(r.spans["bench.build"])
    extra = {s.rsplit(".", 1)[-1]: r.scope_s(s) / n for s in levels}
    return sum(extra.values()), extra
