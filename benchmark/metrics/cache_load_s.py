"""Seconds of set-up spent reading programs out of jax's persistent
compilation cache: the sum of `load_s` over the programs of set-up in
the program's start-up ledger (`benchmark/startup.py`), `load_s` being
the backend-compile span of a program the cache served.  It is the
part of `compile_s` (every backend-compile span, served or built) that
is not compiling, so it never exceeds it.  None in a rehearsal and for
a program without the ledger."""

import startup


def read(run):
    cut = startup.setup_ledger(run)
    if cut is None:
        return None
    return sum(p["load_s"] for p in cut[0]["programs"])
