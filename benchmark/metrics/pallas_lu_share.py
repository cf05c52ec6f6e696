"""Share (%) of the device seconds of the programs run inside
`bench.factorize` that lie under `slu.pallas_lu`: the Pallas panel LU
(`ops/pallas_lu.py`), the one kernel that is on by default, on the
buckets the staged route hands it.  Its scope lies inside
`slu.partial_lu`; a scope's seconds are its innermost name's, so these
are not also `slu.partial_lu`'s.  None where the trace names no such
scope: a program without it, or a cell whose factorization never
reaches the kernel."""

import progspans

SCOPE = "slu.pallas_lu"


def kernel_seconds(run):
    """(seconds under the kernel's scope, seconds of all scopes) of
    the traced factor programs, or None."""
    red = progspans.reduction(run)
    scopes = red and red["factor_scopes"]
    if not scopes or SCOPE not in scopes:
        return None
    return scopes[SCOPE], sum(scopes.values())


def read(run):
    s = kernel_seconds(run)
    return 100.0 * s[0] / s[1] if s and s[1] else None
