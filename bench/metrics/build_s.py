"""Seconds of ``Index.build`` in set-up, to ``block_until_ready``."""


def read(run):
    return run.setup.build_s
