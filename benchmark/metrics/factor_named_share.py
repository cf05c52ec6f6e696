"""Share (%) of the device seconds of the programs run inside
`bench.factorize` that lie in operations carrying a `slu.` kernel
scope; seconds by scope go to the line's notes."""

import progspans


def read(run):
    red = progspans.reduction(run)
    scopes = red and red["factor_scopes"]
    if not scopes or set(scopes) == {progspans.UNNAMED}:
        return None
    run.notes["factor_scopes"] = dict(sorted(scopes.items(),
                                             key=lambda kv: -kv[1]))
    total = sum(scopes.values())
    return 100.0 * (total - scopes.get(progspans.UNNAMED, 0.0)) / total
