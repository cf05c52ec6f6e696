"""Share (%) of the window's triangular sweeps whose operand was in
the factor's precision (x0's sweep and every refinement correction's;
complex of the factor's width counts: realness is the system's).
Read from the program's health ring, whose record of a refined solve
counts its sweeps by operand dtype (`Stats.sweeps`): the newest
records, one a step of the window and of the traced steps after it, as
far as the ring holds them (64).  The counts go to the line's notes,
and with them the newest record's berr trajectory (berr before each
pass and after the last: what the stopping rule saw).
A program without the counter gives None, and so does a rehearsal,
which prints no number of the program's."""

import numpy as np


def read(run):
    if run.rehearse:
        return None
    return share(run)


def share(run):
    recent = run.slu.obs.HEALTH.snapshot().get("recent_solves")
    if not recent:
        return None
    steps = len(run.readings.get("refine_steps") or ()) or len(recent)
    by = {}
    for rec in recent[-steps:]:
        if "sweeps" not in rec:
            return None
        for name, n in rec["sweeps"].items():
            by[name] = by.get(name, 0) + n
    total = sum(by.values())
    if not total:
        return None
    run.notes["sweeps_by_dtype"] = dict(by)
    run.notes["berr_trajectory"] = list(
        recent[-1].get("berr_trajectory", ()))
    fdt = np.dtype(run.config["options"]["factor_dtype"])
    mine = {fdt.name, np.promote_types(fdt, np.complex64).name}
    return 100.0 * sum(n for name, n in by.items() if name in mine) / total
