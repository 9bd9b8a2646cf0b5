"""Device microseconds of the ``hop_fused`` kernel per query, over the
engine batches that ran wholly inside the traced window."""


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.batches_inside()
    queries = sum(b.requests for b in run.window.batches
                  if b.number in spans)
    t = run.trace.kernel_s("hop_fused", within=spans.values())
    return t / queries * 1e6 if t > 0 and queries else None
