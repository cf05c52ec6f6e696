"""The pair kernels' share of the chip's peak (%): the least time for
the fronts' flops in complex arithmetic (roofline_z.factor_flops over
the peak) over the device seconds a factorization spends under
`slu.partial_lu`, `slu.tri_inverse` and `slu.schur`
(`dense_front_share`'s `dense_seconds`), which in a pair-lowered
program are the kernels of ops/pair_lu.  The flops are the fronts'
useful count, so bucket padding and the float32 passes at precision
highest lower the share and nothing raises it."""

import harness
import roofline_z


def read(run):
    fronts = run.readings.get("fronts")
    steps = run.readings.get("traced_steps")
    if not fronts or not steps or run.peaks is None:
        return None
    s = harness.metric_reader("dense_front_share").dense_seconds(run)
    if not s or not s[0]:
        return None
    flops = roofline_z.factor_flops(fronts["w"], fronts["r"])
    least_s = flops / len(run.devices) / run.peaks["flops_per_s"]
    run.notes["pair_front_roofline"] = {
        "flops": flops, "dense_s_per_factorization": s[0] / steps}
    return 100.0 * least_s / (s[0] / steps)
