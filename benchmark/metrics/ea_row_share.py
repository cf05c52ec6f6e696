"""Share (%) of the padded extend-add elements outside the block lane
that ride the row lane (whole rows moved, no index per entry) and not
the element lane, in the last factorization's record in the program's
health ring (`Stats.ea_elements`; the counts go to the line's notes).
A program without the counter gives None, and so does a rehearsal:
its fronts are the rehearsal matrix's, not the cell's."""


def read(run):
    if run.rehearse:
        return None
    last = run.slu.obs.HEALTH.snapshot().get("last_factor") or {}
    lanes = last.get("extend_add")
    if not lanes:
        return None
    run.notes["ea_elements"] = {k: dict(v) for k, v in lanes.items()}
    row = lanes["row"]["padded"]
    both = row + lanes["element"]["padded"]
    return 100.0 * row / both if both else None
