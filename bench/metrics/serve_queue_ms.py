"""Mean wait of a served request from its enqueue to its panel's launch,
in ms: Σ ``queued_ms_sum`` / Σ ``requests`` over the
``hmatrix.serve.launch`` spans; the longest (``max``).  Reads the scoped
reduction (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    r = scopes.scoped(run)
    got = [] if r is None else r.program_args("hmatrix.serve.launch")
    requests = sum(a["requests"] for a in got)
    if not requests:
        return None
    return (sum(a["queued_ms_sum"] for a in got) / requests,
            {"max": max(a["queued_ms_max"] for a in got)})
