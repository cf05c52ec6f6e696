"""Host seconds a traced step spends in `slu.batch.stage`: the host's
share of a batched refactorization before any dispatch, inside
`slu.FACT` (the cast of the caller's (B, nnz) values to the factor
dtype and their hand-over to the device; the scaling is the factor
program's own prologue).  A program without the span gives None."""

import progspans


def read(run):
    return progspans.unit_seconds(run, "slu.batch.stage")
