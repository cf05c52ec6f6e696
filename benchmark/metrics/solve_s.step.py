"""Median seconds of `solve(lu, b)` (triangular sweeps and
refinement, answer on the host), over the window's steps."""


def read(run):
    return run.spans.median("bench.solve")
