"""Device time of one H-matrix apply's low-rank levels, in ms: the
operations under ``hmatrix.apply/lowrank.L{level}`` per ``bench.apply``
span; per level, and per gather, contract and scatter.  Reads the scoped
reduction (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    r = scopes.scoped(run)
    if r is None or not r.spans.get("bench.apply"):
        return None
    levels = r.family("hmatrix.apply/lowrank.")
    if not levels:
        return None
    scale = 1e3 / len(r.spans["bench.apply"])
    extra = {s.rsplit(".", 1)[-1]: scale * r.scope_s(s) for s in levels}
    extra.update({p: scale * t for p, t in r.parts_s(
        levels, ("gather", "contract", "scatter")).items()})
    return scale * sum(r.scope_s(s) for s in levels), extra
