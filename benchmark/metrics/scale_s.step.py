"""Host seconds a traced step spends in `slu.fact.scale`: the host's
share of a refactorization before any dispatch, inside `slu.FACT`
(`plan.scaled_values`: Dr·A·Dc in the plan's order, and the cast of
it to the factor dtype).  A program without the span gives None."""

import progspans


def read(run):
    return progspans.unit_seconds(run, "slu.fact.scale")
