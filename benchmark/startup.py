"""Reductions of the program's start-up ledger
(`obs.COMPILE_WATCH.ledger()`, superlu_dist_tpu/obs/compile_watch.py)
over set-up's interval, for the four readers under `metrics/` that
move `setup_s`: `trace_lower_s`, `cache_load_s`, `cold_programs`,
`setup_named_share`.

The ledger has one row a program that was new to the process (name,
`watched` label or None, `t0`, `cache`, and `spans`: the (kind, start,
end) intervals of its tracing, lowering, compiling or loading from the
persistent cache) and one record a phase of the plan, the schedule and
`prefactor` (name, `t0`, seconds), all on `time.perf_counter()`, the
harness's clock.  Seconds here are unions of intervals cut to set-up:
the traces nested in a program lie inside its own, and a watched row
holds what compiled while it was traced."""

KINDS = ("trace", "lower", "compile", "load")
PLAN_PHASES = ("GATHER", "EQUIL", "ROWPERM", "COLPERM", "ETREE",
               "SYMBFACT", "DIST")


def union_s(intervals, lo=None, hi=None) -> float:
    """Seconds covered by (start, end) intervals cut to [lo, hi],
    overlaps counted once."""
    total, edge = 0.0, float("-inf")
    for a, b in sorted(intervals):
        a = a if lo is None else max(a, lo)
        b = b if hi is None else min(b, hi)
        if b > max(a, edge):
            total += b - max(a, edge)
            edge = b
    return total


def setup_interval(run):
    """Set-up on the harness's clock, or None where the run has not
    timed one (the tests' own drive of a kind)."""
    setup_s = run.readings.get("setup_s")
    if setup_s is None:
        return None
    return run.t_start, run.t_start + setup_s


def ledger(run, since=None, until=None):
    """The program's ledger cut by `t0`, or None: a rehearsal prints
    no number of the program's, and a program from before the ledger
    (the parent of the PR that brought it) has none."""
    if run.rehearse:
        return None
    watch = getattr(run.slu.obs, "COMPILE_WATCH", None)
    read = getattr(watch, "ledger", None)
    return read(since=since, until=until) if read else None


def setup_ledger(run):
    """(ledger over set-up, start, end), or None."""
    cut = setup_interval(run)
    led = ledger(run, *cut) if cut else None
    return (led, *cut) if led else None


def spans(programs, kinds=KINDS):
    return [(a, b) for p in programs for k, a, b in p["spans"]
            if k in kinds]


def seconds(p) -> float:
    return sum(p[k + "_s"] for k in KINDS)


def brief(p) -> dict:
    """A program's row as the notes carry it."""
    out = {"name": p["name"], "watched": p["watched"],
           "cache": p["cache"]}
    out.update({k + "_s": p[k + "_s"] for k in KINDS})
    if "first_call_other_s" in p:
        out["first_call_other_s"] = p["first_call_other_s"]
    return out


def by_name(programs) -> dict:
    """name -> [count, seconds], the largest seconds first."""
    out: dict = {}
    for p in programs:
        rec = out.setdefault(p["name"], [0, 0.0])
        rec[0] += 1
        rec[1] += seconds(p)
    return dict(sorted(out.items(), key=lambda kv: -kv[1][1]))
