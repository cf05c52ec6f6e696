"""Host seconds a traced step spends in `slu.fact.dispatch`: the loop
of donated-buffer dispatches of a staged factorization, one program a
segment (`ops/batched._staged_factor_run`), from the first dispatch to
the last, before the blocking read of the counters.  A program without
the span, and a factorization that is one program, give None."""

import progspans


def read(run):
    return progspans.unit_seconds(run, "slu.fact.dispatch")
