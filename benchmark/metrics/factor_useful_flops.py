"""Useful over executed flops of a factorization (%): the plan's
count over what the schedule runs at its bucket shapes, padding slots
included (`Stats.factor_flops`, `Stats.factor_flops_executed`, read
from the last factorization's record in the program's health ring;
both counts go to the line's notes).  A program without the counters
gives None, and so does a rehearsal: its fronts are the rehearsal
matrix's, not the cell's."""


def read(run):
    if run.rehearse:
        return None
    return useful_share(run)


def useful_share(run):
    last = run.slu.obs.HEALTH.snapshot().get("last_factor") or {}
    flops = last.get("flops")
    if not flops or not flops.get("executed"):
        return None
    run.notes["factor_flops"] = dict(flops)
    return 100.0 * flops["useful"] / flops["executed"]
