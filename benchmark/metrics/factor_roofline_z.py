"""A COMPLEX numeric factorization's share of the chip's roofline: as
`factor_roofline` (the least time the chip could take for the fronts'
work over the device time of the programs launched inside the
`bench.factorize` spans of the traced window), with the flops and
bytes of roofline_z.py: a complex multiply-add is 8 real operations,
and the bytes are at the complex itemsize.  Which of the two bounds
it rides `notes`."""

import numpy as np

import roofline
import roofline_z


def read(run):
    red = run.readings.get("trace")
    fronts = run.readings.get("fronts")
    steps = run.readings.get("traced_steps")
    if not red or not fronts or not steps or run.peaks is None:
        return None
    device_s = red["span_device_s"].get("bench.factorize")
    if not device_s:
        return None
    flops = roofline_z.factor_flops(fronts["w"], fronts["r"])
    nbytes = roofline_z.factor_bytes(
        fronts["w"], fronts["r"], fronts["nnz"],
        np.dtype(run.config["options"]["factor_dtype"]).itemsize)
    share, bound = roofline.roofline_share(
        flops / len(run.devices), nbytes / len(run.devices),
        device_s / steps, run.peaks)
    run.notes["factor_roofline_z"] = {
        "bound": bound, "flops": flops, "bytes": nbytes,
        "device_s_per_factorization": device_s / steps}
    return share
