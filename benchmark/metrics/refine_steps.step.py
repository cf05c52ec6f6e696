"""Median refinement steps per solve (`Stats.refine_steps`)."""

import statistics


def read(run):
    v = run.readings.get("refine_steps")
    return statistics.median(v) if v else None
