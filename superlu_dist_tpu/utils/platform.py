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

So which lowering a complex program takes is ONE rule on what the
code observes, `complex_lowering`: on a TPU default backend a complex
factor dtype runs ON THE TPU through the pair lowering (real and
imaginary planes, no complex op in the program: `ops/pair_lu`,
`ops/batched._factor_group_impl_pair`, the sweeps' real-view codec),
on every path that implements it — `factorize`, `factorize(plan=...)`
and `solve` on one device, one-program and staged, and on a process
grid (`grid=`: `parallel/factor_dist`, whose flats are then (2, N)
planes sharded over the mesh, with the cooperative tree-top LU of
`ops/coop_sharded` in pair arithmetic); on any other backend it stays
native.  A mesh is judged by its own devices, not by the default
backend.  No environment variable is needed (PR 32: PETSc ex11's
Helmholtz system, n=65,536, runs so in the benchmark's cell
`helm2d_n512.zstep`; PR 44: the same system on the 2x2 grid,
`helm2d_grid2x2.zstep`).  Each factorization and solve says which
lowering it took on `Stats.complex_lowering`.

What still leaves the chip, loudly: a path that cannot store pairs (a
caller of `complex_device_gate(pair_capable=False)`; a handle whose
factors are natively stored) is placed on the host CPU backend — an
abort would take the caller's process with it — with a
`ComplexPlacementWarning` naming the dtype and where it went, and
`Stats.placement` / `Stats.complex_lowering` record "cpu".  What has no
pair storage at all and would compile native complex onto TPU devices
refuses instead: the fused mesh solver (`ops/batched.make_fused_solver`
with `mesh=`) and the legacy replicated cooperative LU
(SLU_COOP_SHARDED=0) raise NotImplementedError there.

`SLU_COMPLEX_PAIR=1` is a TEST HOOK: it forces the pair lowering on
a backend that would run native (XLA:CPU, where tier-1 runs), and
decides nothing on a TPU.  `SLU_COMPLEX_TPU=1` runs NATIVE complex on
the TPU (no pair, no gate, on one device and on a mesh), for whoever
repairs the native lowering.  ROADMAP D4 owns deleting the paths this
rule no longer reaches.

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


def _is_complex(dtype) -> bool:
    return np.issubdtype(np.dtype(dtype), np.complexfloating)


def complex_lowering(dtype, mesh=None) -> str:
    """THE rule: how a program of this factor dtype is lowered on the
    paths that implement both lowerings (module docstring).  "pair":
    stacked real/imaginary planes, an all-real program — what a
    complex dtype takes on a TPU, one device and a mesh alike;
    "native": the dtype's own arithmetic — every real dtype, and
    complex on any other backend.  Where the program runs is the
    default backend, or, for a program shard_map'd over `mesh`, the
    mesh's own devices (a TPU mesh built while the default backend is
    the CPU compiles for the TPU all the same).  Two environment
    overrides, neither an option of the program: SLU_COMPLEX_PAIR=1
    forces pair wherever it is asked (the tests' hook on XLA:CPU),
    SLU_COMPLEX_TPU=1 keeps a TPU native."""
    if not _is_complex(dtype):
        return "native"
    if flags.env_str("SLU_COMPLEX_PAIR", "0") == "1":
        return "pair"
    if flags.env_str("SLU_COMPLEX_TPU", "0") == "1":
        return "native"
    if mesh is not None:
        on_tpu = any(d.platform == "tpu"
                     for d in np.asarray(mesh.devices).flat)
    else:
        import jax
        on_tpu = jax.default_backend() == "tpu"
    return "pair" if on_tpu else "native"


def complex_needs_cpu(dtype, pair_capable: bool = True) -> bool:
    """True when a program of `dtype` has to leave the chip: `dtype`
    is complex, the default backend is a TPU, and the caller cannot
    take the pair lowering `complex_lowering` gives it there —
    pair_capable=False is a path that still builds native-complex
    programs (no pair storage of its own, or a handle whose factors
    are natively stored).  SLU_COMPLEX_TPU=1 lifts the gate."""
    if not _is_complex(dtype):
        return False
    if flags.env_str("SLU_COMPLEX_TPU", "0") == "1":
        return False
    if pair_capable and complex_lowering(dtype) == "pair":
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


@contextlib.contextmanager
def complex_device_gate(*dtypes, pair_capable: bool = True,
                        stats=None, phase: str = "", mesh=None):
    """Context manager: place jitted programs on the host CPU backend
    when any of `dtypes` trips complex_needs_cpu; no-op otherwise.
    Yields True when the gate engaged.  An engaged gate is never
    silent: it warns (ComplexPlacementWarning; Python shows a
    repeated warning once per call site), and records
    `phase -> "cpu"` on `stats.placement` when the caller hands its
    Stats.  pair_capable=False for callers whose programs cannot use
    pair storage (see complex_needs_cpu).

    Whenever one of `dtypes` is complex the phase's lowering goes on
    `stats.complex_lowering`: "cpu" for a gated placement, "pair"
    where the caller can take it and `complex_lowering` gives it,
    else "native"."""
    cplx = [np.dtype(dt) for dt in dtypes if _is_complex(dt)]
    gated = [dt.name for dt in cplx
             if complex_needs_cpu(dt, pair_capable=pair_capable)]
    if cplx and stats is not None:
        stats.complex_lowering[phase or "complex"] = (
            "cpu" if gated
            else complex_lowering(cplx[0], mesh) if pair_capable
            else "native")
    if not gated:
        yield False
        return
    import jax
    warnings.warn(
        f"superlu_dist_tpu: {gated[0]} programs are placed on the "
        "host CPU backend, not on the TPU: native complex does not "
        "compile on this chip, and this path cannot take the "
        "real-pair lowering that runs complex there "
        "(utils/platform.py).", ComplexPlacementWarning, stacklevel=3)
    if stats is not None:
        stats.placement[phase or "complex"] = "cpu"
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        yield True
