"""Host seconds a traced step spends in the plane codec of pair
storage at the mesh entry points: `slu.pair.encode` (a complex value
set's and a right-hand side's real and imaginary planes, made on the
host because a complex-to-real extraction inside the program would be
a complex operation) and `slu.pair.decode` (the answer's planes back
to complex), `parallel/factor_dist`.  Every sweep of a refined solve
encodes and decodes once, so this grows with `refine_steps.step`.  A
program without the spans (a real system, one device, the parent of
the PR that brought them) gives None."""

import progspans

SPANS = ("slu.pair.encode", "slu.pair.decode")


def read(run):
    found = {s: progspans.unit_seconds(run, s) for s in SPANS}
    found = {s: v for s, v in found.items() if v is not None}
    if not found:
        return None
    run.notes["pair_codec_s.step"] = found
    return sum(found.values())
