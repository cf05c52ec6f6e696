"""Pallas TPU kernel: batched partial LU of front panels in VMEM.

The XLA formulation (ops/dense_lu.py) carries the front through a
fori_loop in HBM — every column step is a separate fused kernel with an
HBM round-trip.  This kernel keeps the whole (mb × mb) front VMEM-
resident for the entire wb-column elimination (the analog of the
reference keeping the panel in GPU shared memory across
Local_Dgstrf2's column loop, SRC/pdgstrf2.c:404), so the per-column
cost is pure VPU work:

    column k:  extract col/row k by iota-mask reduction (no dynamic
               lane slicing), tiny-pivot replace, scale below-diagonal,
               masked rank-1 outer-product update of the trailing block

Gating: off by default until validated on real hardware; enable with
SLU_TPU_PALLAS=1 (force, any platform via interpret on CPU) — see
`enabled()`.  The factorization computed agrees with
ops/dense_lu.partial_lu to rounding (the two use different but
algebraically equivalent block formulations; tests/test_pallas.py
compares them elementwise under a small tolerance).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import flags

# the kernel keeps its operands plus an output copy VMEM-resident
# (~16 MB/core on v5e); beyond this the XLA path keeps the bucket
VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def mosaic_dtype(dtype) -> bool:
    """Real sub-64-bit dtypes only: Mosaic lowers neither complex nor
    64-bit (the kernel traces under `jax.enable_x64(False)` for the
    same reason — weak Python scalars must enter the jaxpr at 32
    bit)."""
    dtype = np.dtype(dtype)
    return dtype.kind != "c" and dtype.itemsize < 8


def interpret_default() -> bool:
    """Interpret mode exists for the CPU test suite, which runs the
    same kernel body through the Pallas interpreter.  On a TPU
    backend this is False: the kernel there always goes through
    Mosaic, and a compile failure surfaces as the compiler's error."""
    return jax.default_backend() != "tpu"


def enabled(dtype) -> bool:
    """Use the Pallas kernel everywhere?  SLU_TPU_PALLAS=1 forces on
    (interpret mode off-TPU), =0/unset leaves the global routing off.

    Default OFF — resolved by hardware measurement, not hope
    (pre-round chip record, not re-measured; TPU v5e, amortized
    in-jit timing): the XLA fori_loop formulation is ~2x faster at every
    bucket shape ≥ (wb=16, mb=32) (e.g. 44 vs 20 GFLOP/s at 512²) and
    both paths sit at true-f32 accuracy vs the f64 ground truth
    (~5e-7) under the package's "highest" matmul precision.  The
    kernel wins only the µs-scale (8, 16) bucket (1.3x), which never
    dominates a schedule — but IS the population the level-merged
    factor segments coalesce; `merged_eligible` promotes exactly that
    regime.  What that regime costs in a cell is read since PR 41
    (`lap3d_k48.step`, PERF.md section 5): 5.5 ms of a factorization's
    0.351 s on the device, 1.6 %.  Complex dtypes always use the XLA
    path (no complex in Mosaic)."""
    return (mosaic_dtype(dtype)
            and flags.env_str("SLU_TPU_PALLAS", "0").strip() == "1")


def merged_eligible(wb: int, mb: int, dtype) -> bool:
    """Merged-factor-segment promotion (ISSUE 12): inside a merged
    staged factor segment (ops/batched.get_factor_segments) the
    panel-LU kernel engages BY DEFAULT for the µs-scale buckets a
    pre-round chip record priced it ahead on — wb ≤ 8, mb ≤ 16, the
    (8, 16)-class population that level merging coalesces — on real
    TPU hardware only (kernels are resolved by measurement; interpret
    mode would merely slow the CPU rehearsal, and the bitwise fp64
    A/B never reaches here because f64 is structurally ineligible).
    One cell runs this arm since PR 41, `lap3d_k48.step`: its one
    eligible bucket, 4,096 leaf slots of (16, 8), takes 5.5 ms a
    factorization under `slu.pallas_lu`, 0.18 % of the roofline for
    the bytes it moves (my chip runs, PR 41; PERF.md section 5).  The
    XLA arm on the same bucket has not been read beside it: the arm is
    pinned by the benchmark's `pallas_lu_roofline`; a `benchmark`
    issue releases it (ROADMAP D-a).
    SLU_TPU_PALLAS=0 restores the XLA path; =1 forces the kernel for
    every usable bucket (the historical A/B arm)."""
    if not mosaic_dtype(dtype) or not usable(mb, dtype):
        return False
    flag = flags.env_str("SLU_TPU_PALLAS", "auto").strip().lower()
    if flag in ("0", "false", "off"):
        return False
    if flag == "1":
        return True
    return jax.default_backend() == "tpu" and wb <= 8 and mb <= 16


def usable(mb: int, dtype) -> bool:
    """Does one (mb × mb) front, input + output copy, fit the
    kernel's VMEM working set?"""
    return 2 * mb * mb * np.dtype(dtype).itemsize <= VMEM_BUDGET_BYTES


def _tiny_replace_sel(piv, thresh, dtype):
    """GESP tiny-pivot replacement, Mosaic-safe formulation: same
    semantics as dense_lu._tiny_replace (|piv| < thresh →
    sign(piv)·thresh; thresh == 0 disables and flags exact zeros) but
    written as copysign-via-select + maximum and where-selected int32
    counters.  The original's nested scalar-where chain combined with
    bool→int32 counter casts trips a Mosaic layout-inference bug
    ("failed to legalize func.return") when traced inside a fori_loop
    on real hardware; this arithmetic form lowers cleanly."""
    apiv = jnp.abs(piv)
    one = jnp.ones((), jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    sgn = jnp.where(piv >= 0, jnp.ones((), dtype), -jnp.ones((), dtype))
    newpiv = sgn * jnp.maximum(apiv, thresh)
    is_tiny = apiv < thresh
    was_tiny = jnp.where(is_tiny, one, zero)
    was_zero = jnp.where((apiv == 0) & jnp.logical_not(is_tiny),
                         one, zero)
    return newpiv, was_tiny, was_zero


def _pick_nb(wb: int, nb_max: int = 32) -> int:
    """Largest panel block ≤ nb_max dividing wb (wb buckets live on
    the {2^k, 1.5·2^k} grid, so a divisor ≤ 32 always exists)."""
    if wb <= nb_max:
        return wb
    for d in (32, 24, 16, 12, 8, 4, 2, 1):
        if d <= nb_max and wb % d == 0:
            return d
    return 1


def _unit_lower_inverse_newton(L, nb: int):
    """inv(unit-lower L), exact Newton iteration — delegates to the
    shared dense_lu helper (plain jnp ops, Mosaic-compatible; Mosaic
    has no triangular_solve)."""
    from .dense_lu import _newton_tri_inverse
    return _newton_tri_inverse(L, lower=True, unit=True)


def _lu_kernel_blocked(thresh_ref, F_ref, out_ref, tiny_ref, nzero_ref,
                       *, wb: int, mb: int, nb: int):
    """Blocked right-looking partial LU of one front, VMEM-resident.

    Per nb-wide block: rank-1 panel elimination restricted to the
    (mb, nb) panel, unit-lower inverse of the diagonal block (Newton,
    MXU), U12 = L11⁻¹·A12 and trailing GEMM F22 −= L21·U12 both on
    the MXU.  (dense_lu.partial_lu uses a different but algebraically
    equivalent split — diagonal-block elimination + two triangular
    solves; results agree to rounding.)  The kb loop is
    Python-unrolled (static slices); only the nb rank-1 steps per
    block run as a fori_loop on the (mb, nb) panel, so VPU work is
    O(wb·mb·nb) instead of the whole-front O(wb·mb²).

    The front lives in out_ref for the whole elimination and every
    block update is a STATIC ref-slice store: Mosaic has no
    dynamic_update_slice lowering, but static VMEM slice loads/stores
    are native.  On real hardware every slice boundary (multiples of
    nb) must be tile-aligned — lane offsets in multiples of 128 —
    or Mosaic's backend aborts; the caller picks nb accordingly and
    falls back to the column kernel when no aligned nb divides wb."""
    out_ref[0] = F_ref[0]
    dtype = F_ref.dtype
    thresh = thresh_ref[0, 0].astype(dtype)
    rows_m = jax.lax.broadcasted_iota(jnp.int32, (mb, 1), 0)
    cols_nb = jax.lax.broadcasted_iota(jnp.int32, (1, nb), 1)
    tiny = jnp.zeros((), jnp.int32)
    nzero = jnp.zeros((), jnp.int32)

    for k0 in range(0, wb, nb):
        panel = out_ref[0, :, k0:k0 + nb]               # (mb, nb)

        def t_step(t, carry, k0=k0):
            panel, tiny, nzero = carry
            k = k0 + t
            is_t = cols_nb == t                         # (1, nb)
            ck = jnp.sum(jnp.where(is_t, panel, 0), axis=1,
                         keepdims=True)                 # (mb, 1)
            piv = jnp.sum(jnp.where(rows_m == k, ck, 0))
            piv, was_tiny, was_zero = _tiny_replace_sel(piv, thresh,
                                                        dtype)
            below = rows_m > k
            scaled = jnp.where(below, ck / piv, ck)
            newcol = jnp.where(rows_m == k, piv, scaled)
            panel = jnp.where(is_t, newcol, panel)
            rk = jnp.sum(jnp.where(rows_m == k, panel, 0), axis=0,
                         keepdims=True)                 # (1, nb)
            # broadcast multiply (exact), not a rank-1 matmul at the
            # ambient (possibly bf16) matmul precision
            upd = jnp.where(below, scaled, 0) * jnp.where(
                cols_nb > t, rk, 0)
            panel = panel - upd
            return panel, tiny + was_tiny, nzero + was_zero

        # int32 bounds: Python-int bounds become an int64 induction
        # variable under jax_enable_x64, which Mosaic cannot lower
        panel, tiny, nzero = jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(nb), t_step, (panel, tiny, nzero))
        out_ref[0, :, k0:k0 + nb] = panel
        rest = mb - k0 - nb
        if rest > 0:
            Inv = _unit_lower_inverse_newton(
                panel[k0:k0 + nb, :], nb)
            U12 = Inv @ out_ref[0, k0:k0 + nb, k0 + nb:]  # (nb, rest)
            L21 = panel[k0 + nb:, :]                      # (rest, nb)
            out_ref[0, k0:k0 + nb, k0 + nb:] = U12
            out_ref[0, k0 + nb:, k0 + nb:] = (
                out_ref[0, k0 + nb:, k0 + nb:] - L21 @ U12)

    i = pl.program_id(0)
    tiny_ref[0, i] = tiny
    nzero_ref[0, i] = nzero


def _lu_kernel(thresh_ref, F_ref, out_ref, tiny_ref, nzero_ref, *,
               wb: int, mb: int):
    F = F_ref[0]
    dtype = F.dtype
    thresh = thresh_ref[0, 0].astype(dtype)
    rows = jax.lax.broadcasted_iota(jnp.int32, (mb, mb), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (mb, mb), 1)
    # narrow iotas built at shape (no value slicing: Mosaic cannot
    # legalize width-1 lane extracts of vreg values)
    rows_c = jax.lax.broadcasted_iota(jnp.int32, (mb, 1), 0)
    cols_r = jax.lax.broadcasted_iota(jnp.int32, (1, mb), 1)

    def col_step(k, carry):
        F, tiny, nzero = carry
        is_k_col = cols == k
        is_k_row = rows == k
        # column/row k via mask-reduce (dynamic lane slicing is slow)
        ck = jnp.sum(jnp.where(is_k_col, F, 0), axis=1, keepdims=True)
        piv = jnp.sum(jnp.where(is_k_col & is_k_row, F, 0))
        piv, was_tiny, was_zero = _tiny_replace_sel(piv, thresh, dtype)
        below = rows_c > k
        scaled = jnp.where(below, ck / piv, ck)
        newcol = jnp.where(rows_c == k, piv, scaled)
        F = jnp.where(is_k_col, newcol, F)
        rk = jnp.sum(jnp.where(is_k_row, F, 0), axis=0, keepdims=True)
        upd = jnp.where(below, scaled, 0) * jnp.where(
            cols_r > k, rk, 0)
        F = F - upd
        return F, tiny + was_tiny, nzero + was_zero

    zero = jnp.zeros((), jnp.int32)
    F, tiny, nzero = jax.lax.fori_loop(
        jnp.int32(0), jnp.int32(wb), col_step, (F, zero, zero))
    i = pl.program_id(0)
    out_ref[0] = F
    tiny_ref[0, i] = tiny
    nzero_ref[0, i] = nzero


def partial_lu_batch_pallas(F, thresh, *, wb: int,
                            interpret: bool | None = None):
    """Drop-in for dense_lu.partial_lu_batch: F (N, mb, mb) ->
    (F', tiny_total, nzero_total)."""
    N, mb, _ = F.shape
    if interpret is None:
        interpret = interpret_default()
    thresh_arr = jnp.asarray(thresh, dtype=F.dtype).reshape(1, 1)
    # blocked kernel (MXU TRSM/GEMM per nb-wide panel) where its slice
    # boundaries are expressible: any nb in interpret mode, 128-aligned
    # nb on real hardware (Mosaic aborts on unaligned VMEM slice
    # stores).  SLU_TPU_PALLAS_COLUMN=1 forces the per-column rank-1
    # kernel for A/B comparison.
    if interpret:
        nb = _pick_nb(wb)
    else:
        nb = next((d for d in (256, 128) if wb % d == 0), 0)
    if (flags.env_str("SLU_TPU_PALLAS_COLUMN", "0") == "1"
            or nb == 0 or mb % 8 != 0):
        kern = functools.partial(_lu_kernel, wb=wb, mb=mb)
    else:
        kern = functools.partial(_lu_kernel_blocked, wb=wb, mb=mb, nb=nb)
    # Mosaic's lowering visitors recurse through the unrolled block
    # chain.  Under jit this call only binds the primitive — lowering
    # runs at compile time, after we return — so the raised limit must
    # persist (restoring it here would reinstate the RecursionError at
    # the deferred compile).
    import sys
    if sys.getrecursionlimit() < 20000:
        # process-global on purpose (see comment above); reached only
        # when a Pallas kernel is actually being built, and logged once
        # so the side effect is discoverable
        import warnings
        warnings.warn(
            "superlu_dist_tpu.ops.pallas_lu: raising "
            f"sys.setrecursionlimit({sys.getrecursionlimit()} -> 20000) "
            "for deferred Mosaic lowering of the unrolled block chain",
            stacklevel=2)
        sys.setrecursionlimit(20000)
    # a scope of the kernel's own, inside the caller's
    # `slu.partial_lu`: a device trace then says what the kernel costs
    # (benchmark/metrics/pallas_lu_share.py, pallas_lu_roofline.py)
    with jax.enable_x64(False), jax.named_scope("slu.pallas_lu"):
        out, tiny, nzero = _pallas_lu_call(kern, N, mb, F.dtype,
                                           interpret)(thresh_arr, F)
    return out, jnp.sum(tiny), jnp.sum(nzero)


def _pallas_lu_call(kern, N, mb, dtype, interpret):
    return pl.pallas_call(
        kern,
        grid=(N,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, mb, mb), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, mb, mb), lambda i: (i, 0, 0)),
            # whole-array SMEM blocks (indexed by program_id inside the
            # kernel): Mosaic's tile check rejects a (1, 1) block over
            # an (N, 1) array even in SMEM — block dims must equal the
            # array's, which (1, N) satisfies
            pl.BlockSpec((1, N), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, N), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, mb, mb), dtype),
            jax.ShapeDtypeStruct((1, N), jnp.int32),
            jax.ShapeDtypeStruct((1, N), jnp.int32),
        ],
        interpret=interpret,
    )
