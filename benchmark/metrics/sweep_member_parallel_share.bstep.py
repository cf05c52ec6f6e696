"""Share (%) of the window's batched sweeps that were dispatched on
the member-parallel arm (`jax.vmap` over the members: one batched dot
a group) and not as a scan of one member after another.  Read from the
program's health ring, whose record of a batched solve names its arm
(`sweep_arm`: `vmap` or `scan`; `Stats.dispatch["batch_sweep_arm"]`)
and counts its sweeps by operand dtype: the newest records, one a step
of the window and of the traced steps after it, as far as the ring
holds them (64).  A record that is no batched solve's (no `members`)
counts nothing.  A program without the counter gives None, and so does
a rehearsal, which prints no number of the program's."""


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
        if not rec.get("members") or not rec.get("sweep_arm"):
            continue
        n = sum((rec.get("sweeps") or {}).values())
        by[rec["sweep_arm"]] = by.get(rec["sweep_arm"], 0) + n
    total = sum(by.values())
    if not total:
        return None
    run.notes["batch_sweeps_by_arm"] = dict(by)
    return 100.0 * by.get("vmap", 0) / total
