"""Programs of set-up that the persistent cache did not serve: rows of
the program's start-up ledger (`benchmark/startup.py`) whose `cache` is
`miss` (asked for, built) or `off` (no cache directory, or the cache
disabled).  0 is the expected reading of a warm start; a row that was
traced and never compiled counts for nothing.  The notes carry their
names with counts and seconds, the forty largest (`cold_programs`),
and the names of every program born after set-up, in the window or the
traced steps (`window_programs`: `window_compiles.*` counts them and
cannot name them).  None in a rehearsal and for a program without the
ledger."""

import startup


def read(run):
    cut = startup.setup_ledger(run)
    if cut is None:
        return None
    led, _lo, hi = cut
    cold = [p for p in led["programs"] if p["cache"] in ("miss", "off")]
    run.notes["cold_programs"] = {
        n: {"count": c, "seconds": s}
        for n, (c, s) in list(startup.by_name(cold).items())[:40]}
    late = startup.ledger(run, since=hi)["programs"]
    run.notes["window_programs"] = [p["name"] for p in late][:40]
    return len(cold)
