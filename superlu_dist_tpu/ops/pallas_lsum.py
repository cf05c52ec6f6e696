"""Pallas TPU kernel: fused lsum panel-solve + update.

The merged trisolve (ops/trisolve.py) reduces every forward group
step to `y = Li·xb` followed by `upd = L21·y` — the lsum dataflow of
the reference's dedicated device trisolve kernels
(dlsum_fmod_inv_gpu_mrhs, SRC/pdgstrs_lsum_cuda.cu:1002): solve the
supernode panel, produce the off-diagonal contribution, in one
kernel.  XLA executes the two einsums as separate HLO ops with `y`
round-tripping through HBM between them; at nrhs=1 the round trip
costs more than the math.  This kernel fuses them: one grid step per
front holds Li, L21, xb, y and upd in VMEM and runs both contractions
back-to-back on the MXU — y never leaves the chip.

Gating: `SLU_TRISOLVE_PALLAS=1` only (default OFF — to be priced on
the chip before any default flips, the pallas_scatter discipline).
f32/bf16 real only: f64 has no Mosaic lowering (pallas_lu precedent)
and complex/pair lanes keep the XLA einsum fallback
(`trisolve._fwd_member` — the dense fallback is the default path, not
an afterthought).  Interpret mode runs the same kernel on CPU for the
correctness oracle (tests/test_trisolve.py);
chip_smoke.py's `pallas_kernels` phase certifies the Mosaic compile
on real hardware, next to the scatter and panel-LU kernels.

Precision: both dots run HIGHEST (multi-pass f32) — the same pin
`_hi_prec` applies to the XLA einsums, so arm-to-arm differences stay
in the f32 rounding class, not a precision-mode delta.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl

from .. import flags
from .pallas_common import (VMEM_BUDGET_BYTES, interpret_default,
                            mosaic_dtype)


def enabled(dtype) -> bool:
    """Route merged forward steps through the fused lsum kernel?
    SLU_TRISOLVE_PALLAS=1 only; real f32/bf16 only."""
    return (mosaic_dtype(dtype)
            and flags.env_str("SLU_TRISOLVE_PALLAS", "0") == "1")


def usable(trim: int, wb: int, rb: int, nrhs: int, dtype) -> bool:
    if trim <= 0 or rb <= 0:
        return False
    it = np.dtype(dtype).itemsize
    need = (wb * wb + rb * wb + wb * nrhs * 2
            + 2 * rb * nrhs) * it
    return need <= VMEM_BUDGET_BYTES


def _lsum_kernel(Li_ref, L21_ref, xb_ref, y_ref, upd_ref):
    """One front per grid step: y = Li·xb then upd = L21·y, both on
    the MXU, y staying in VMEM between them."""
    Li = Li_ref[0]                                # (wb, wb)
    L21 = L21_ref[0]                              # (rb, wb)
    xb = xb_ref[0]                                # (wb, R)
    y = jax.lax.dot_general(
        Li, xb, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    upd = jax.lax.dot_general(
        L21, y, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)
    upd_ref[0] = upd.astype(upd_ref.dtype)


def lsum_panel(Li_p, L21_p, xb, *, interpret: bool | None = None):
    """(y, upd) for one group's front batch: Li_p (t, wb, wb), L21_p
    (t, rb, wb), xb (t, wb, R) -> y (t, wb, R), upd (t, rb, R)."""
    t, wb, _ = Li_p.shape
    rb = L21_p.shape[1]
    R = xb.shape[2]
    if interpret is None:
        interpret = interpret_default()
    kern = _lsum_kernel
    with jax.enable_x64(False):
        y, upd = pl.pallas_call(
            kern,
            grid=(t,),
            in_specs=[
                pl.BlockSpec((1, wb, wb), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, rb, wb), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, wb, R), lambda i: (i, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, wb, R), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, rb, R), lambda i: (i, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((t, wb, R), xb.dtype),
                jax.ShapeDtypeStruct((t, rb, R), xb.dtype),
            ],
            interpret=interpret,
        )(Li_p, L21_p, xb)
    return y, upd


def fwd_member(state, g, gs, pack, idx):
    """trisolve._fwd_member with the two panel contractions fused
    into one Pallas call.  Gather/chain/dense-write stay in XLA
    (dense data movement is what XLA is good at); only the
    panel-solve + update math enters the kernel."""
    from .trisolve import chain_subtract
    B, UPD, Y = state
    b_idx, u_gidx, _ = idx
    Li_p, L21_p, _, _ = pack
    xb = chain_subtract(B[b_idx], UPD, u_gidx, gs.J)
    y, upd = lsum_panel(Li_p, L21_p[:, :gs.rtrim, :], xb)
    Y = jax.lax.dynamic_update_slice(
        Y, y.reshape(-1, y.shape[-1]), (gs.y_off, 0))
    UPD = jax.lax.dynamic_update_slice(
        UPD, upd.reshape(-1, upd.shape[-1]), (gs.u_off, 0))
    return B, UPD, Y


@functools.lru_cache(maxsize=1)
def _oracle():
    """Reference einsum pair for the smoke/oracle checks."""

    def ref(Li_p, L21_p, xb):
        y = jnp.einsum("nvw,nwr->nvr", Li_p, xb,
                       precision=jax.lax.Precision.HIGHEST)
        upd = jnp.einsum("nsw,nwr->nsr", L21_p, y,
                         precision=jax.lax.Precision.HIGHEST)
        return y, upd

    return jax.jit(ref)
