"""Share of answered queries the router sent through the graph (in- or
post-filtering) rather than to pre-filtering."""


def read(run):
    if not run.answered:
        return None
    graph = sum(a.stats.mechanism in ("in", "post") for a in run.answered)
    return 100.0 * graph / len(run.answered)
