"""What today's chip does differently, and what the package does
about it.

Complex on the TPU.  Probed on a TPU v5e under jax 0.9.0 / libtpu
0.0.34 (PR 23, `tools/complex_probe.py`, one process per probe):

  * a tiny NATIVE complex128 program (one 48x48 partial_lu + one GEMM)
    does not compile: the TPU compiler's 64-bit rewriter hits a fatal
    check — "Unsupported CVT X64 expansion from s64[] to c128[]" — and
    ABORTS THE PROCESS (signal 6).  Not a hang, not an exception.
  * the same program in complex64 compiles and runs (3 s).
  * the complex128 math on stacked real/imag planes (`ops/pair_lu`,
    an all-real program) compiles in ~20 s and matches numpy to 2e-15.

So a complex factor/solve on a TPU backend is still placed on the
host CPU backend (`complex_device_gate`) — an abort would take the
caller's process with it — but no longer silently: a placement
raises a `ComplexPlacementWarning` naming the dtype and where it
went, and every gated phase records the placement on its `Stats`
(`Stats.placement`).  `SLU_COMPLEX_PAIR=1` runs complex on
the chip through the pair lowering (single device only);
`SLU_COMPLEX_TPU=1` lifts the gate for native complex, for whoever
repairs the lowering.  ROADMAP R3/D4 own the real fix: one complex
path that runs on the chip.

Amalgamation.  `apply_accel_amalg_defaults` is the other thing here:
entry points that resolved an accelerator env-default tau/cap to the
values measured best on a TPU; a library caller of gssvx does not.
"""

from __future__ import annotations

import contextlib
import os
import warnings

import numpy as np

from .. import flags


class ComplexPlacementWarning(UserWarning):
    """A complex program was placed on the host CPU backend although
    the default backend is a TPU (module docstring)."""


def complex_pair_enabled() -> bool:
    """Real-pair complex lowering (ops/pair_lu +
    batched._factor_group_impl_pair): the single-device complex
    factor/solve runs on stacked real/imag planes, so the compiled
    program contains NO complex ops and the native-complex compile
    abort is never reached.  SLU_COMPLEX_PAIR=1 opts in; the raw pair
    kernel compiles and runs on today's chip (module docstring), the
    full pair solve has not been run there (ROADMAP R3)."""
    return flags.env_str("SLU_COMPLEX_PAIR", "0") == "1"


def complex_needs_cpu(dtype, pair_capable: bool = True) -> bool:
    """True when `dtype` is complex and the default backend is a TPU
    (see module docstring).  Pair mode lifts the gate — its programs
    are all-real, so the native-complex compile is never attempted —
    but only for callers that actually implement pair storage; a path
    that still builds native-complex programs (the fused one-program
    solver) passes pair_capable=False so the lift cannot route it
    into the compile abort."""
    if not np.issubdtype(np.dtype(dtype), np.complexfloating):
        return False
    if flags.env_str("SLU_COMPLEX_TPU", "0") == "1":
        return False
    if pair_capable and complex_pair_enabled():
        return False
    import jax
    return jax.default_backend() == "tpu"


def apply_accel_amalg_defaults() -> None:
    """Env-default the supernode-amalgamation knobs to the values
    measured best on TPU, for callers that have already resolved an
    accelerator backend.  User-set env always wins.

    Measured 2026-08-01 on v5e (pre-round chip record, not
    re-measured; n=27k, steady-state
    wall of the fused solve — compare `best`, not GFLOP/s, since
    amalgamation grows flops by construction):

        tau=100%/cap=512 (library default)   0.952 s
        tau=100%/cap=1024                    0.885 s
        tau=200%/cap=1024                    0.841 s
        tau=400%/cap=1024                    0.815 s   (-14%)

    The TPU run is latency-bound (MFU ~0.01%): merging supernodes
    removes whole sequential level-batch steps and the MXU absorbs
    the extra flops for free, so aggressive merging keeps winning
    through the measured ladder.  On CPU the same trade LOSES
    (round-4 measurement at n=27k) — flops are not free there — so
    these defaults apply only on accelerator-resolved paths and the
    library default stays CPU-safe.

    A library caller of gssvx does NOT get these: only entry points
    that resolved an accelerator call this (pddrive).  chip_smoke.py says which settings it ran with."""
    for k, v in (("SUPERLU_AMALG_TAU_PCT", "400"),
                 ("SUPERLU_AMALG_CAP", "1024")):
        os.environ.setdefault(k, v)


def complex_mesh_blocked(dtype, mesh) -> bool:
    """True when a complex `dtype` is about to compile onto a mesh
    containing TPU devices (and the override is not set).  Deliberately
    independent of jax.default_backend(): a TPU mesh built while the
    default backend is CPU would hit the same compile abort, so the
    mesh's own devices are the predicate."""
    if not np.issubdtype(np.dtype(dtype), np.complexfloating):
        return False
    if flags.env_str("SLU_COMPLEX_TPU", "0") == "1":
        return False
    return any(d.platform == "tpu"
               for d in np.asarray(mesh.devices).flat)


@contextlib.contextmanager
def complex_device_gate(*dtypes, pair_capable: bool = True,
                        stats=None, phase: str = ""):
    """Context manager: place jitted programs on the host CPU backend
    when any of `dtypes` trips complex_needs_cpu; no-op otherwise.
    Yields True when the gate engaged.  An engaged gate is never
    silent: it warns (ComplexPlacementWarning; Python shows a
    repeated warning once per call site), and records
    `phase -> "cpu"` on `stats.placement` when the caller hands its
    Stats.  pair_capable=False for callers whose programs cannot use
    pair storage (see complex_needs_cpu)."""
    gated = [np.dtype(dt).name for dt in dtypes
             if complex_needs_cpu(dt, pair_capable=pair_capable)]
    if not gated:
        yield False
        return
    import jax
    warnings.warn(
        f"superlu_dist_tpu: {gated[0]} programs are placed on the "
        "host CPU backend, not on the TPU: native complex does not "
        "compile on this chip (utils/platform.py).  "
        "SLU_COMPLEX_PAIR=1 runs complex on the TPU through the "
        "real-pair lowering.", ComplexPlacementWarning, stacklevel=3)
    if stats is not None:
        stats.placement[phase or "complex"] = "cpu"
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        yield True
