"""Share (%) of the first device's idle seconds that lie under a
`slu.*` span of the program; the ten largest [span, seconds], each
span named for the idle time no span inside it covers, go to the
line's notes."""

import progspans


def read(run):
    red = progspans.reduction(run)
    if not red or not red["host_s"] or not red["idle"]["idle_s"]:
        return None
    idle = red["idle"]
    run.notes["idle_by_span"] = idle["by_span"][:10]
    return 100.0 * idle["attributed_s"] / idle["idle_s"]
