"""Share (%) of the matrix's rows that the plan's static-pivoting
permutation moves: 100 * `rows_moved` / `n` of the plan's GESP facts
(`Stats.gesp`), read from the last factorization's record in the
program's health ring; the whole record (equed, the scales' ranges,
the structurally zero diagonals) goes to the line's notes.  0 where
the permutation is the identity, about 66 on a saddle point whose
pressure rows each trade places with a velocity row.  A program
without the counter gives None, and so does a rehearsal: its plan is
the rehearsal matrix's, not the cell's."""


def read(run):
    if run.rehearse:
        return None
    return share(run)


def share(run):
    last = run.slu.obs.HEALTH.snapshot().get("last_factor") or {}
    gesp = last.get("gesp")
    if not gesp or not gesp.get("n"):
        return None
    run.notes["gesp"] = dict(gesp)
    return 100.0 * gesp["rows_moved"] / gesp["n"]
