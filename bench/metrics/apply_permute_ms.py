"""Device time of one H-matrix apply's permutations into and out of tree
order, in ms: the operations under ``hmatrix.apply/permute_in`` and
``permute_out`` per ``bench.apply`` span; each.  Reads the scoped
reduction (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    r = scopes.scoped(run)
    if r is None or not r.spans.get("bench.apply"):
        return None
    scale = 1e3 / len(r.spans["bench.apply"])
    each = {p: scale * r.scope_s(f"hmatrix.apply/{p}")
            for p in ("permute_in", "permute_out")}
    if not any(each.values()):
        return None
    return sum(each.values()), each
