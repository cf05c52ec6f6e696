"""The batched factor program's share of the chip's roofline (%): the
least time the chip could take for B members' fronts
(roofline_batch.py: B times one member's flops and bytes) over the
device time of the programs launched inside the `bench.factorize`
spans of the traced window (the factor program, and the pack program
`batch_factorize` dispatches behind it: its bytes are the program's
choice and are not counted as work, so it lowers the share and
nothing raises it).  None where the run is no batch (no
`batch_members` reading), or the trace holds no such program."""

import numpy as np

import roofline
import roofline_batch


def read(run):
    red = run.readings.get("trace")
    fronts = run.readings.get("fronts")
    steps = run.readings.get("traced_steps")
    members = run.readings.get("batch_members")
    if not red or not fronts or not steps or not members \
            or run.peaks is None:
        return None
    device_s = red["span_device_s"].get("bench.factorize")
    if not device_s:
        return None
    flops = roofline_batch.batch_factor_flops(
        fronts["w"], fronts["r"], members)
    nbytes = roofline_batch.batch_factor_bytes(
        fronts["w"], fronts["r"], fronts["nnz"],
        np.dtype(run.config["options"]["factor_dtype"]).itemsize,
        members)
    share, bound = roofline.roofline_share(
        flops, nbytes, device_s / steps, run.peaks)
    run.notes["batch_factor_roofline"] = {
        "bound": bound, "flops": flops, "bytes": nbytes,
        "members": members,
        "device_s_per_factorization": device_s / steps}
    return share
