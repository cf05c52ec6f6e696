"""The numeric factorization's share of the chip's roofline: the least
time the chip could take for the fronts' work (roofline.py) over the
device time of the programs launched inside the `bench.factorize`
spans of the traced window.  On a grid, the least time is divided by
the chips."""

import numpy as np

import roofline


def read(run):
    red = run.readings.get("trace")
    fronts = run.readings.get("fronts")
    steps = run.readings.get("traced_steps")
    if not red or not fronts or not steps or run.peaks is None:
        return None
    device_s = red["span_device_s"].get("bench.factorize")
    if not device_s:
        return None
    flops = roofline.factor_flops(fronts["w"], fronts["r"])
    nbytes = roofline.factor_bytes(
        fronts["w"], fronts["r"], fronts["nnz"],
        np.dtype(run.config["options"]["factor_dtype"]).itemsize)
    share, bound = roofline.roofline_share(
        flops / len(run.devices), nbytes / len(run.devices),
        device_s / steps, run.peaks)
    run.notes["factor_roofline"] = {
        "bound": bound, "flops": flops, "bytes": nbytes,
        "device_s_per_factorization": device_s / steps}
    return share
