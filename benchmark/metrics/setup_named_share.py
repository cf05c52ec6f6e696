"""Share (%) of `setup_s` that the program's start-up ledger names
(`benchmark/startup.py`): the union, cut to set-up, of the phases'
intervals (the plan's, `SCHEDULE`, `PREFACTOR`) and of every new
program's trace, lower, compile and load intervals, over `setup_s`.
What it leaves is the benchmark's side of set-up (imports, data from
the seed, the reference's factorization) and warm-up's executions; in
a served cell `PREFACTOR` spans the service's whole warm-up,
executions included.  The notes carry the seconds by part
(`setup_by_part`: `plan.<PHASE>`, `schedule`, `prefactor`, and
`trace`, `lower`, `compile`, `load` each as a union), and beside them,
for the reader of the line only, the benchmark's own span totals, the
collector's pass that ends set-up and `setup_s` itself (a traced line
carries no end-to-end metric).  None in a rehearsal and for a
program without the ledger."""

import startup


def read(run):
    cut = startup.setup_ledger(run)
    if cut is None:
        return None
    led, lo, hi = cut
    parts: dict = {}
    named = startup.spans(led["programs"])
    for ph in led["phases"]:
        name = ("plan." + ph["name"]
                if ph["name"] in startup.PLAN_PHASES
                else ph["name"].lower())
        parts[name] = parts.get(name, 0.0) + ph["seconds"]
        named.append((ph["t0"], ph["t0"] + ph["seconds"]))
    for kind in startup.KINDS:
        parts[kind] = startup.union_s(
            startup.spans(led["programs"], (kind,)), lo, hi)
    for span in ("bench.native_library", "bench.plan",
                 "bench.prefactor", "bench.warmup"):
        total = run.spans.total(span)
        if total is not None:
            parts[span] = total
    if "setup_gc_collect_s" in run.notes:
        parts["setup_gc_collect_s"] = run.notes["setup_gc_collect_s"]
    # a traced line carries no end-to-end metric: what the share is of
    parts["setup_s"] = hi - lo
    run.notes["setup_by_part"] = parts
    return 100.0 * startup.union_s(named, lo, hi) / (hi - lo)
