"""Median wall of one step: the steadier statistic beside `step_s`,
which is the window's time over its steps."""

import statistics


def read(run):
    v = run.readings.get("step_walls")
    return statistics.median(v) if v else None
