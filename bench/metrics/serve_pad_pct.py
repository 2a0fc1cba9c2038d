"""Share of the launched panels' columns that are padding, in %:
100 (1 - Σ ``requests`` / Σ ``width``) over the ``hmatrix.serve.launch``
spans; the launches and their mean width.  Reads the scoped reduction
(``bench/scopes.py``)."""
from bench import scopes


def read(run):
    r = scopes.scoped(run)
    got = [] if r is None else r.program_args("hmatrix.serve.launch")
    width = sum(a["width"] for a in got)
    if not width:
        return None
    return (100.0 * (1.0 - sum(a["requests"] for a in got) / width),
            {"launches": len(got), "mean_width": width / len(got)})
