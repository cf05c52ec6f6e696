"""Busy seconds of the first device inside the programs that run
during a `slu.solve.sweep` span, per traced step
(`sweep_device_s.step`) or per `slu.serve.batch`
(`sweep_device_s.serve`): the sweeps on the device's clock, without
the host's share of a solve."""

import progspans


def read(run):
    red = progspans.reduction(run)
    if not red or not red["units"] \
            or red["unit_sweep_device_s"] is None:
        return None
    return red["unit_sweep_device_s"] / red["units"]
