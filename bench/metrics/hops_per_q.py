"""Mean hops of the graph-routed queries (``RequestStats.hops``)."""


def read(run):
    hops = [a.stats.hops for a in run.answered
            if a.stats.mechanism in ("in", "post")]
    return sum(hops) / len(hops) if hops else None
