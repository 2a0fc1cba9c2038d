"""Seconds of the build's factor stage (``BuildReport.factors_s``: one
batched ACA launch per level group, waited on), mean over the window's
builds."""


def read(run):
    s = run.counters.get("build_factors_s")
    return sum(s) / len(s) if s else None
