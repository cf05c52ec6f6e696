"""Mean share of a dispatched batch's columns that held a live
request (`serve.batch_occupancy` histogram of the service)."""

from harness import histogram


def read(run):
    mean = histogram(run, "serve.batch_occupancy", "mean")
    return None if mean is None else 100.0 * mean
