"""Host seconds in `slu.refine.residual` (the residual and its
backward error, `models/refine.iterative_refine`), per traced step
(`residual_s.step`) or per `slu.serve.batch` (`residual_s.serve`)."""

import progspans


def read(run):
    return progspans.unit_seconds(run, "slu.refine.residual")
