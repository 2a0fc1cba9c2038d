"""Share of one apply's least time (work from the plan's shapes at the
traffic's panel width, ``bench/work.py``) in its measured device time, in
%, with the bound (compute or memory) that sets the least time."""
from bench import work


def read(run):
    if run.trace is None or run.shapes is None:
        return None
    got = work.roofline_pct(work.apply_work(run.shapes, run.cols),
                            run.trace.device_s_per_span("bench.apply"),
                            run.device_kind)
    return None if got is None else (got[0], {"bound": got[1]})
