"""Share (%) of the traced window that the flusher thread spends in
`slu.serve.wait`: nothing to feed the device, or lingering for a
batch to fill."""

import progspans


def read(run):
    red = progspans.reduction(run)
    rec = red and red["host_s"].get("slu.serve.wait")
    window = run.readings.get("trace_window_s")
    return 100.0 * rec[0] / window if rec and window else None
