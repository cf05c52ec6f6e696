"""Seconds of set-up in which some thread traced a program to a jaxpr
or lowered one to StableHLO: the union of every new program's `trace`
and `lower` intervals in the program's start-up ledger
(`benchmark/startup.py`).  It is what a warm start pays again whatever
the persistent cache holds.  The notes carry the ten largest programs
of set-up by trace + lower + compile + load seconds, each with its
split, its `cache` and its `watched` label (`startup_programs`); of the
unwatched ones (set-up's eager operations, the bucket grid) the count,
the sums and the five largest names (`startup_small`); and the
ledger's header with its own cost (`startup_header`).  None in a
rehearsal and for a program without the ledger."""

import startup


def read(run):
    cut = startup.setup_ledger(run)
    if cut is None:
        return None
    led, lo, hi = cut
    progs = led["programs"]
    top = sorted(progs, key=startup.seconds, reverse=True)[:10]
    run.notes["startup_programs"] = [startup.brief(p) for p in top]
    small = [p for p in progs if p["watched"] is None]
    names = startup.by_name(small)
    run.notes["startup_small"] = dict(
        {k + "_s": sum(p[k + "_s"] for p in small)
         for k in startup.KINDS},
        count=len(small),
        largest={n: {"count": c, "seconds": s}
                 for n, (c, s) in list(names.items())[:5]})
    run.notes["startup_header"] = dict(led["header"],
                                       programs=len(progs),
                                       folded=led["folded"])
    return startup.union_s(startup.spans(progs, ("trace", "lower")),
                           lo, hi)
