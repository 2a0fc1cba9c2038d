"""Device time of one H-matrix apply's dense leaves, in ms: the
operations under ``hmatrix.apply/dense`` per ``bench.apply`` span; per
gather, kernel, contract and scatter.  Reads the scoped reduction
(``bench/scopes.py``)."""
from bench import scopes

SCOPE = "hmatrix.apply/dense"


def read(run):
    r = scopes.scoped(run)
    if r is None or not r.spans.get("bench.apply") or not r.scope_s(SCOPE):
        return None
    scale = 1e3 / len(r.spans["bench.apply"])
    parts = r.parts_s([SCOPE], ("gather", "kernel", "contract", "scatter"))
    return scale * r.scope_s(SCOPE), {p: scale * t for p, t in parts.items()}
