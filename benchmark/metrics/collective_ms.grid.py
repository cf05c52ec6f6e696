"""Milliseconds of collective operations (all-reduce, all-gather,
collective-permute, all-to-all, reduce-scatter) on the first device,
per traced step."""


def read(run):
    red = run.readings.get("trace")
    steps = run.readings.get("traced_steps")
    if not red or red["collective_s"] is None or not steps:
        return None
    return 1e3 * red["collective_s"] / steps
