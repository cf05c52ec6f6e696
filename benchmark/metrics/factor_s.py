"""Median seconds of `factorize(plan=...)` ending in
block_until_ready, over the window's steps."""


def read(run):
    return run.spans.median("bench.factorize")
