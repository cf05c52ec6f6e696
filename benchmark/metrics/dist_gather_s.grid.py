"""Device seconds a mesh factorization spends under its collectives'
scopes a traced step, first device: `slu.dist.gather` (a level's
update slab gathered to every device), `slu.coop.psum` and
`slu.coop.gather` (the cooperative tree-top LU's panel reductions and,
in the legacy replicated scheme, its recombination gather), from the
scopes of the programs run inside `bench.factorize`.  It counts the
collectives and what XLA fuses with them under the scope (a psum's
adds), each for the time it holds the device's operation line; what
runs beside an asynchronous collective in flight is the other
scopes'.  `collective_ms.grid` counts the collectives of the whole
step by instruction; this one says which of the factorization's they
are.

progspans.scope_of keeps a scope's name as far as its second dot
(`slu.dist`, `slu.coop`): either form is read.  None where the trace
names neither (one device, or a program without the scopes)."""

import progspans

FAMILIES = ("slu.dist", "slu.coop")


def seconds_by_scope(run):
    """{scope: device seconds of the traced window} of the
    collectives' scopes, or None where the trace names no scope."""
    red = progspans.reduction(run)
    scopes = red and red["factor_scopes"]
    if not scopes:
        return None
    return {k: v for k, v in scopes.items()
            if any(k == f or k.startswith(f + ".") for f in FAMILIES)}


def read(run):
    mine = seconds_by_scope(run)
    steps = run.readings.get("traced_steps")
    if not mine or not steps:
        return None
    run.notes["dist_gather_s.grid"] = {
        k: v / steps for k, v in sorted(mine.items())}
    return sum(mine.values()) / steps
