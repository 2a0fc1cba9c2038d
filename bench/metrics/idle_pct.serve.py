"""Idle share of the chip over the traced window of a cell whose loop
runs ``bench.serve`` spans, in %: 1 - busy union / window."""


def read(run):
    if run.trace is None or "bench.serve" not in run.trace.spans:
        return None
    return run.trace.idle_pct()
