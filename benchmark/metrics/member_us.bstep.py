"""Microseconds of a step per member of the batch: the median wall of
one step (`step_median_s`'s statistic) over the members it factors
and solves: what one system costs a caller.  None where the run is no
batch."""

import statistics


def read(run):
    walls = run.readings.get("step_walls")
    members = run.readings.get("batch_members")
    if not walls or not members:
        return None
    return 1e6 * statistics.median(walls) / members
