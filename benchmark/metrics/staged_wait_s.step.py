"""Host seconds a traced step spends in `slu.fact.wait`: the one
blocking read of a staged factorization's counters (`int(tiny)`,
`int(nzero)`), during which the chip works through the segments still
queued.  A program without the span, and a factorization that is one
program, give None."""

import progspans


def read(run):
    return progspans.unit_seconds(run, "slu.fact.wait")
