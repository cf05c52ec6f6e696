"""Share (%) of the window's factorizations whose solve mirror (the
packed panels, `jit_slu_pack`) was dispatched by the factorization
itself, on the factor program's output futures and before its blocking
read, so that the host hands out the pack's buffers while the chip
factors: 100 is the expected reading of a one-chip step cell under the
merged sweep, 0 where every first solve still packs.  Read from the
program's health ring, whose record of each factorization says where
its pack was dispatched (`pack`: "at_factor", "at_solve", or "none"
for one that never packed, as on the mesh; `Stats.packs`): the newest
records, one a step of the window and of the traced steps after it, as
far as the ring holds them (64).  The counts go to the line's notes.
A program without the field gives None, and so does a rehearsal, which
prints no number of the program's."""


def read(run):
    if run.rehearse:
        return None
    return share(run)


def share(run):
    events = run.slu.obs.HEALTH.snapshot().get("factor_events")
    if not events:
        return None
    steps = len(run.readings.get("refine_steps") or ()) or len(events)
    by = {}
    for rec in events[-steps:]:
        if "pack" not in rec:
            return None
        by[rec["pack"]] = by.get(rec["pack"], 0) + 1
    run.notes["packs"] = dict(by)
    return 100.0 * by.get("at_factor", 0) / sum(by.values())
