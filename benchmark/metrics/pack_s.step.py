"""Host seconds a traced step spends in `slu.solve.pack`: the miss
path of `ops/trisolve.get_packs`, which slices a new factorization's
flats into per-group panels before its first solve."""

import progspans


def read(run):
    return progspans.unit_seconds(run, "slu.solve.pack")
