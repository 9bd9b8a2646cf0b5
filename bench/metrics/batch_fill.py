"""Requests per engine batch the server executed in the window (the
benchmark's span around each ``Index.search_batch`` call)."""


def read(run):
    b = run.window.batches
    return sum(x.requests for x in b) / len(b) if b else None
