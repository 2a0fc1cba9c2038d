"""Mean width of the panels the serving runtime launched for the tenant
(its ``stats()["launched_widths"]``, the last 1024 launches), in columns."""


def read(run):
    w = run.counters.get("launched_widths")
    return float(sum(w)) / len(w) if w else None
