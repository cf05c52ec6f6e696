"""Share (%) of the window's factorizations and triangular sweeps that
ran in the pair lowering (real and imaginary planes, no complex op in
the program) on the accelerator: 100 is the expected reading of a
complex cell on a TPU; a run whose complex programs were placed on the
host CPU reads 0.  Read from the program's health ring, whose record
of each factorization and of each refined solve says how it was
lowered and where (`complex_lowering`: "pair", "native", or "cpu" for
a gated placement; `Stats.complex_lowering`): the newest records, one
of each a step of the window and of the traced steps after it, as far
as the ring holds them (64); a solve counts once for each of its
sweeps.  The counts go to the line's notes.  A program without the
field gives None, and so does a rehearsal, which prints no number of
the program's."""


def read(run):
    if run.rehearse:
        return None
    return share(run)


def share(run):
    snap = run.slu.obs.HEALTH.snapshot()
    factors = snap.get("factor_events") or []
    solves = snap.get("recent_solves") or []
    steps = len(run.readings.get("refine_steps") or ())
    if steps:
        factors, solves = factors[-steps:], solves[-steps:]
    by = {}
    for rec, weight in ([(r, 1) for r in factors]
                        + [(r, sum((r.get("sweeps") or {}).values()))
                           for r in solves]):
        if "complex_lowering" not in rec:
            return None
        how = rec["complex_lowering"] or "none"
        by[how] = by.get(how, 0) + weight
    total = sum(by.values())
    if not total:
        return None
    run.notes["complex_lowering"] = dict(by)
    on_chip = run.device["platform"] != "cpu"
    return 100.0 * (by.get("pair", 0) if on_chip else 0) / total
