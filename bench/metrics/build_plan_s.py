"""Seconds of the build's structural stage (``BuildReport.plan_s``: the
fused plan program and the fetch of its metadata), mean over the window's
builds."""


def read(run):
    s = run.counters.get("build_plan_s")
    return sum(s) / len(s) if s else None
