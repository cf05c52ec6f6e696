"""Deterministic fault injection for the serve stack.

Nothing in a solver exercises its failure paths by accident: a
factorization that works never raises, factors that converge are never
NaN, a flusher thread that's healthy never dies.  This module is the
only way the repo breaks itself ON PURPOSE — a seeded, spec-driven
chaos layer whose injection sites are compiled into the serve code
(`factor_cache`, `batcher`, `store`) but cost one module-global `is
None` check when off, so production paths pay nothing.

Spec grammar (`SLU_CHAOS` or `install(spec)`):

    site=prob[:param][,site=prob[:param]]...

        factor_raise=0.3          30% of factorizations raise ChaosError
        factor_nan=0.3            30% of factorizations return NaN factors
        store_flip=1              every store read gets one bit flipped
        flusher_raise=0.05        5% of flusher batches kill the flusher
        latency=0.2:0.005         20% of dispatches sleep 5 ms
        store_latency=0.3:0.02    30% of store reads/writes sleep 20 ms
                                  (a slow shared warm tier / object store)
        lease_steal=0.1           10% of fleet lease-freshness checks
                                  treat a FRESH lease as expired — forces
                                  the steal path without killing a leader
        replica_kill=1:2.0        arm a self-SIGKILL 2 s after the site
                                  first fires (DRILL-ONLY: the process
                                  dies the way `kill -9` kills it — no
                                  handlers, no cleanup)
        refactor_raise=0.3        30% of BACKGROUND refactorizations
                                  raise (the stream pipeline worker's
                                  own failure site; the foreground
                                  factor path keeps factor_raise)
        refactor_slow=0.5:0.1     50% of background refactorizations
                                  sleep 100 ms first (a long factor
                                  the stale-serving path must ride)
        swap_kill=1               synchronous self-SIGKILL inside the
                                  resident-swap publish window —
                                  after the durable store holds the
                                  new generation, before the
                                  in-memory assignment (DRILL-ONLY:
                                  the mid-swap crash the warm-restart
                                  gate proves safe)
        near_singular=1:0.5       skew incoming STREAM value sets
                                  toward rank deficiency (param =
                                  skew strength s in [0,1): values
                                  blend (1-s)·v + s·mean(v), exactly
                                  singular at s=1) — the drift fault
                                  the rcond-drift cadence trigger and
                                  the condition policy must catch

Determinism: each site owns a `random.Random` seeded from
(`SLU_CHAOS_SEED`, site name), so the same spec+seed replays the same
failure sequence regardless of which other sites fire — the property
that makes a chaos regression debuggable.  Per-site fired counters
ride `fired()`.

Sites are NAMED here (SITES) and validated at install: a typo'd site
in a spec is an error, not silence.
"""

from __future__ import annotations

import hashlib
import random
import threading
import time

from .. import flags

SITES = ("factor_raise", "factor_nan", "store_flip", "flusher_raise",
         "latency", "store_latency", "lease_steal", "replica_kill",
         "refactor_raise", "refactor_slow", "swap_kill",
         "near_singular")


def _stable_seed(seed: int, *legs) -> int:
    """Process-independent integer seed from (seed, legs)."""
    h = hashlib.sha256(
        ("\x00".join([str(seed)] + [str(x) for x in legs])).encode())
    return int.from_bytes(h.digest()[:8], "big")


class ChaosError(RuntimeError):
    """An injected failure (never raised by real solver code): test
    assertions and loadgen accounting can tell engineered faults from
    genuine bugs."""


class ChaosPolicy:
    """Parsed spec + per-site seeded RNGs and fired counters."""

    def __init__(self, spec: str, seed: int = 0) -> None:
        self.spec = spec
        self.seed = seed
        self._lock = threading.Lock()
        self._prob: dict[str, float] = {}
        self._param: dict[str, float] = {}
        self._rng: dict[str, random.Random] = {}
        self._fired: dict[str, int] = {}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            name, _, rest = part.partition("=")
            name = name.strip()
            if name not in SITES:
                raise ValueError(
                    f"unknown chaos site {name!r}; expected one of "
                    f"{SITES}")
            probs, _, param = rest.partition(":")
            self._prob[name] = float(probs) if probs else 1.0
            if param:
                self._param[name] = float(param)
            # site-local stream: firing order at one site never
            # perturbs another site's sequence.  Seeded via a STABLE
            # hash — str.__hash__ is PYTHONHASHSEED-randomized and
            # would silently break cross-process replay
            self._rng[name] = random.Random(_stable_seed(seed, name))
            self._fired[name] = 0

    def should(self, site: str) -> bool:
        """One draw at `site`; counts a firing when it trips."""
        with self._lock:
            p = self._prob.get(site)
            if p is None:
                return False
            if self._rng[site].random() >= p:
                return False
            self._fired[site] += 1
            return True

    def param(self, site: str, default: float = 0.0) -> float:
        return self._param.get(site, default)

    def fired(self) -> dict:
        with self._lock:
            return dict(self._fired)


# the process-wide policy; None = chaos off (the only cost real code
# ever pays is this pointer check)
_POLICY: ChaosPolicy | None = None


def install(spec: str, seed: int | None = None) -> ChaosPolicy:
    global _POLICY
    if seed is None:
        seed = flags.env_int("SLU_CHAOS_SEED", 0)
    _POLICY = ChaosPolicy(spec, seed=seed)
    return _POLICY


def install_from_env() -> ChaosPolicy | None:
    spec = flags.env_str("SLU_CHAOS").strip()
    return install(spec) if spec else None


def uninstall() -> None:
    global _POLICY
    _POLICY = None


def active() -> ChaosPolicy | None:
    return _POLICY


# -- injection-site helpers (all no-ops when chaos is off) -----------

def should(site: str) -> bool:
    p = _POLICY
    return p is not None and p.should(site)


def maybe_raise(site: str, msg: str) -> None:
    if should(site):
        raise ChaosError(f"[chaos:{site}] {msg}")


def maybe_sleep(site: str, default_s: float = 0.005) -> None:
    p = _POLICY
    if p is not None and p.should(site):
        time.sleep(p.param(site, default_s))


def maybe_flip_bit(site: str, data: bytes) -> bytes:
    """Flip one deterministic bit of `data` when `site` fires — the
    persisted-entry-corruption fault the store's checksum must catch."""
    p = _POLICY
    if p is None or not data or not p.should(site):
        return data
    rng = random.Random(_stable_seed(p.seed, site, len(data)))
    i = rng.randrange(len(data))
    out = bytearray(data)
    out[i] ^= 1 << rng.randrange(8)
    return bytes(out)


def maybe_replica_kill(site: str = "replica_kill") -> bool:
    """DRILL-ONLY self-`kill -9`: when `site` fires, arm a daemon
    timer that SIGKILLs THIS process after the site's param seconds
    (default: immediately).  SIGKILL is deliberate — no atexit, no
    finally blocks, no flusher drain: the fleet drill needs the
    ugliest replica death there is, the one the lease TTL and the
    survivors' failover must absorb.  Returns whether the kill was
    armed (the drill logs it; nothing sane ever checks the return
    after the delay).  One pointer check when chaos is off; inert
    unless the spec names the site."""
    p = _POLICY
    if p is None or not p.should(site):
        return False
    import os
    import signal
    delay = p.param(site, 0.0)

    def _die() -> None:
        if delay > 0:
            time.sleep(delay)
        os.kill(os.getpid(), signal.SIGKILL)

    threading.Thread(target=_die, name="chaos-replica-kill",
                     daemon=True).start()
    return True


def maybe_sigkill(site: str = "swap_kill") -> None:
    """DRILL-ONLY synchronous self-`kill -9` AT the call site: when
    `site` fires the process dies on this very line — no delay, no
    handlers, no cleanup.  The stream pipeline plants it between a
    generation's durable publication and its in-memory swap
    (stream/pipeline.py), so the drift drill crashes a replica at the
    worst instant of the hand-off and proves the restart boots warm
    from whichever generation the store last published.  One pointer
    check when chaos is off; inert unless the spec names the site."""
    p = _POLICY
    if p is None or not p.should(site):
        return
    import os
    import signal
    os.kill(os.getpid(), signal.SIGKILL)


def maybe_skew_singular(site: str, a):
    """Deterministically skew a value set toward rank deficiency when
    `site` fires: v' = (1-s)·v + s·mean(v) blends every stored entry
    toward the constant vector (a rank-1 value pattern — exactly
    singular at s=1), with s = the site's param (default 0.5).  The
    PATTERN is untouched, so the skewed matrix stays in the same
    stream.  Returns the input object unchanged when the site does not
    fire (one pointer check when chaos is off), else a NEW matrix of
    the same type — callers must rekey off the return value."""
    if not should(site):
        return a
    import dataclasses as _dc

    import numpy as np
    p = _POLICY
    s = min(max(p.param(site, 0.5), 0.0), 1.0)
    v = np.asarray(a.data)
    skewed = (1.0 - s) * v + s * v.mean()
    return _dc.replace(a, data=skewed.astype(v.dtype))


def maybe_poison_factors(site: str, lu) -> None:
    """Overwrite the factorization's numeric factors with NaN when
    `site` fires — the silently-wrong-answer fault the serve layer's
    finite-validation gate (FactorPoisoned) must contain.  Mutates the
    handle in place (host panels) or swaps device flats."""
    if not should(site):
        return
    import numpy as np
    if lu.backend == "host":
        for side in (lu.host_lu.L, lu.host_lu.U,
                     lu.host_lu.Linv, lu.host_lu.Uinv):
            for p in side:
                p[...] = np.nan
        return
    import jax.numpy as jnp
    d = lu.device_lu
    if hasattr(d, "panels"):
        d.panels = [tuple(jnp.full_like(a, jnp.nan) for a in p)
                    for p in d.panels]
        return
    for f in ("L_flat", "U_flat", "Li_flat", "Ui_flat"):
        setattr(d, f, jnp.full_like(getattr(d, f), jnp.nan))
