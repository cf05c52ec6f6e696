"""Factor programs the newest factorization dispatched: the segments
of the staged route (109 on `lap3d_k48.step`), 1 where the
factorization is one program.  Read from the program's health ring,
whose record of each factorization says which route it took
(`dispatch`: "staged" or "program", `segments`, `groups`,
`pallas_buckets`, `pallas_shapes`; `Stats.dispatch`); the record and
the newest solve's `sweep_segments` go to the line's notes.  A program
without the fields gives None, and so does a rehearsal, which prints
no number of the program's."""

KEYS = ("dispatch", "segments", "groups", "pallas_buckets",
        "pallas_shapes")


def read(run):
    if run.rehearse:
        return None
    return segments(run)


def route(run, snap=None):
    """The newest factorization's route, or None where the ring's
    record does not say."""
    if snap is None:
        snap = run.slu.obs.HEALTH.snapshot()
    last = snap.get("last_factor") or {}
    if "dispatch" not in last or "segments" not in last:
        return None
    return {k: last.get(k) for k in KEYS}


def segments(run):
    snap = run.slu.obs.HEALTH.snapshot()
    rec = route(run, snap)
    if rec is None:
        return None
    solve = snap.get("last_solve") or {}
    run.notes["route"] = dict(rec,
                              sweep_segments=solve.get("sweep_segments"))
    return float(rec["segments"])
