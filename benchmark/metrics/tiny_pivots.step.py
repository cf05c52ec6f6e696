"""The largest count of tiny pivots that any factorization of the
window replaced (`tiny_pivots` of the health ring's factor records:
the newest, one a step of the window and of the traced steps after
it, as far as the ring holds them, 64).  0 expected: a replaced pivot
is a perturbed factor that refinement has to carry, and the first
thing to look at where `correct` fails.  The count of factorizations
looked at goes to the line's notes.  A program without the ring gives
None, and so does a rehearsal, which prints no number of the
program's."""


def read(run):
    if run.rehearse:
        return None
    return largest(run)


def largest(run):
    events = run.slu.obs.HEALTH.snapshot().get("factor_events")
    if not events:
        return None
    steps = len(run.readings.get("refine_steps") or ()) or len(events)
    mine = events[-steps:]
    if any("tiny_pivots" not in e for e in mine):
        return None
    run.notes["tiny_pivots_factorizations"] = len(mine)
    return float(max(e["tiny_pivots"] for e in mine))
