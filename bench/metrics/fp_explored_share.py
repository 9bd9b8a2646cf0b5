"""Share of the records the graph-routed queries explored that failed
exact verification: the bloom superset's false positives."""


def read(run):
    g = [a.stats for a in run.answered if a.stats.mechanism in ("in", "post")]
    explored = sum(s.explored for s in g)
    return 100.0 * sum(s.fp_explored for s in g) / explored if explored \
        else None
