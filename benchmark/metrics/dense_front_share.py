"""Share (%) of the device seconds of the programs run inside
`bench.factorize` that lie under the dense kernels' scopes: the
partial LU of the pivot block, the triangular inverses and the Schur
update.  The rest is assembly, extend-add, stores and what carries no
scope."""

import progspans

DENSE = ("slu.partial_lu", "slu.tri_inverse", "slu.schur")


def dense_seconds(run):
    """(seconds under the dense scopes, seconds of all scopes) of the
    traced factor programs, or None where the trace names no scope."""
    red = progspans.reduction(run)
    scopes = red and red["factor_scopes"]
    if not scopes or set(scopes) == {progspans.UNNAMED}:
        return None
    return (sum(scopes.get(k, 0.0) for k in DENSE),
            sum(scopes.values()))


def read(run):
    s = dense_seconds(run)
    return 100.0 * s[0] / s[1] if s else None
