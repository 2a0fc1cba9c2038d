"""Share of the window's launches, in %, whose width is the tenant's
largest bucket (its ``max_batch``; ``stats()["launched_widths"]``): how
far the cell stays where every panel launches full-width."""


def read(run):
    w, top = run.counters.get("launched_widths"), run.counters.get("max_batch")
    if not w or not top:
        return None
    return 100.0 * sum(x == top for x in w) / len(w)
