"""The Pallas panel LU's share of the chip's roofline (%): the least
time the chip could take for the buckets it is handed
(`roofline_pallas_lu.py`: their flops at the peak, their fronts read
once and written once at the memory's rate, whichever is larger) over
the device seconds a factorization spends under `slu.pallas_lu`.  The
buckets' shapes are the program's own record of what it dispatched
(`last_factor["pallas_shapes"]`).  None where the trace names no such
scope or the ring no such shapes."""

import numpy as np

import harness
import roofline
import roofline_pallas_lu


def read(run):
    steps = run.readings.get("traced_steps")
    if not steps or getattr(run, "peaks", None) is None:
        return None
    s = harness.metric_reader("pallas_lu_share").kernel_seconds(run)
    rec = harness.metric_reader("staged_segments.step").route(run)
    shapes = rec and rec.get("pallas_shapes")
    if not s or not s[0] or not shapes:
        return None
    flops = roofline_pallas_lu.panel_lu_flops(shapes)
    nbytes = roofline_pallas_lu.panel_lu_bytes(
        shapes, np.dtype(run.config["options"]["factor_dtype"]).itemsize)
    share, bound = roofline.roofline_share(flops, nbytes, s[0] / steps,
                                           run.peaks)
    run.notes["pallas_lu_roofline"] = {
        "bound": bound, "flops": flops, "bytes": nbytes,
        "shapes": shapes, "device_s_per_factorization": s[0] / steps}
    return share
