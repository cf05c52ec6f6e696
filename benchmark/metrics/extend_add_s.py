"""Device seconds a factorization spends under `slu.extend_add` (the
children's updates read from the slab and added into their parents'
fronts, every lane), from the scopes of the programs run inside
`bench.factorize` in the traced window.  On a mesh: the first
device's.  None where the trace names no scope."""

import progspans

SCOPE = "slu.extend_add"


def read(run):
    red = progspans.reduction(run)
    scopes = red and red["factor_scopes"]
    steps = run.readings.get("traced_steps")
    if not scopes or not steps or SCOPE not in scopes:
        return None
    return scopes[SCOPE] / steps
