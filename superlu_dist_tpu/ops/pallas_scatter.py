"""Pallas TPU kernel: tiled extend-add scatter engine.

The reference solves exactly this problem with a device scatter
kernel (`Scatter`/`dScatter`, SRC/dsuperlu_gpu.cu:115-143): child
Schur-update blocks land in parent fronts through an index map, and
letting the generic runtime serialize those indexed writes is the
difference between HBM-rate and broken throughput.  The round-5
profile measured XLA's element scatter fusions at 50–200 MB/s on v5e
(pre-round chip record, not re-measured) — the TPU has no native scatter datapath, so
the fusion loops lane-by-lane.

This kernel re-expresses the scatter as MXU work, the datapath the
chip actually has: for one child update block U (rc_b × tc_b) with
destination positions pr/pc, the scatter IS the one-hot expansion

    delta_front += S_rᵀ · U · S_c,     S_r[k, p] = (p == pr[k])

two dense matmuls per child, accumulated into the child's parent
front tile held in VMEM across consecutive children (the schedule
builder emits records front-sorted, so each front tile is resident
exactly once).  Sentinel positions (mb / ncols, the padding drop
convention) one-hot to all-zero rows and vanish — the mode="drop"
arithmetic for free.  The kernel emits a DELTA array (zeros where no
child lands, thanks to the donated-zeros aliasing) which the caller
adds to the assembled front batch.

Gating: `SLU_TPU_PALLAS_SCATTER=1` only (default OFF — an A/B arm
no chip run has priced yet; chip_smoke.py certifies that Mosaic
compiles it and that it matches the oracle; interpret mode runs the
same kernel on CPU for the correctness oracle in
tests/test_ea_blocks.py).  f32/bf16 only: f64 has no Mosaic lowering
(pallas_lu precedent) and complex never reaches here (pair mode
splits planes before the extend-add).

Precision note: the one-hot factors are exactly representable, but
the value operand crosses the MXU, so products carry f32-matmul
(HIGHEST, multi-pass) rounding instead of being exact adds —
identical error class to every other f32 matmul in the factor, and
the f64 refinement loop owns the residual either way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import flags
from .pallas_common import (VMEM_BUDGET_BYTES, interpret_default,
                            mosaic_dtype)


def enabled(dtype) -> bool:
    """Use the Pallas scatter engine?  SLU_TPU_PALLAS_SCATTER=1 only —
    OFF by default: no chip run has priced it against the XLA element
    scatter yet (the pallas_lu lesson: kernels are resolved by
    measurement, not hope)."""
    return (mosaic_dtype(dtype)
            and flags.env_str("SLU_TPU_PALLAS_SCATTER", "0") == "1")


def usable(mb: int, ncols: int, rc_b: int, tc_b: int, dtype) -> bool:
    it = np.dtype(dtype).itemsize
    need = (2 * mb * ncols + rc_b * tc_b
            + rc_b * mb + tc_b * ncols) * it
    return need <= VMEM_BUDGET_BYTES


def _scatter_kernel(fb_ref, upd_ref, pr_ref, pc_ref, base_ref,
                    out_ref, *, mb: int, ncols: int):
    """One child per grid step: one-hot expand the (rc_b, tc_b) block
    into its (mb, ncols) front tile.  out block index = fb[i] (scalar
    prefetch), so consecutive same-front children accumulate in VMEM;
    the first child of each front ASSIGNS (the VMEM tile is undefined
    on arrival — out blocks are write-only)."""
    i = pl.program_id(0)
    prev = fb_ref[jnp.maximum(i - 1, 0)]
    first = jnp.logical_or(i == 0, fb_ref[i] != prev)
    upd = upd_ref[0]                              # (rc_b, tc_b)
    pr = pr_ref[0]                                # (1, rc_b) row
    pc = pc_ref[0]                                # (tc_b, 1) column
    rc_b, tc_b = upd.shape
    # S_rᵀ (mb, rc_b), S_c (tc_b, ncols), each built in the
    # orientation its matmul consumes (no transposed operand for
    # Mosaic to lower): sentinel pos == mb/ncols matches no iota
    # entry -> all-zero column/row -> dropped
    rows = jax.lax.broadcasted_iota(jnp.int32, (mb, rc_b), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tc_b, ncols), 1)
    S_rT = (rows == pr).astype(upd.dtype)
    S_c = (cols == pc).astype(upd.dtype)
    mid = jnp.dot(upd, S_c, precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32)   # (rc_b, ncols)
    contrib = jnp.dot(S_rT, mid, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32
                      ).astype(out_ref.dtype)           # (mb, ncols)

    del base_ref   # aliased zeros: only its unvisited blocks matter

    @pl.when(first)
    def _():
        out_ref[0] = contrib

    @pl.when(jnp.logical_not(first))
    def _():
        out_ref[0] = out_ref[0] + contrib


def scatter_add_delta(upd, pr, pc, fb, *, mb: int, ncols: int,
                      n_pad: int, interpret: bool | None = None):
    """Extend-add delta of one element bucket: `upd` (K, rc_b, tc_b)
    gathered child blocks, `pr`/`pc` (K, rc_b)/(K, tc_b) int32
    destination positions (sentinel mb/ncols drops), `fb` (K,) int32
    front ids, NON-DECREASING (the schedule builder's front order and
    its K-padding db convention guarantee this).  Returns an
    (n_pad, mb, ncols) delta: the caller's `F + delta` replaces the
    serialized element scatter."""
    K = upd.shape[0]
    if interpret is None:
        interpret = interpret_default()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(K,),
        in_specs=[
            pl.BlockSpec((1,) + upd.shape[1:], lambda i, fb: (i, 0, 0)),
            # positions ride 3D — rows as (K, 1, rc_b), columns as
            # (K, tc_b, 1): Mosaic wants a block's last two dims
            # tile-aligned OR equal to the array's, which a (1, rc_b)
            # block of a (K, rc_b) array is not
            pl.BlockSpec((1, 1, pr.shape[1]), lambda i, fb: (i, 0, 0)),
            pl.BlockSpec((1, pc.shape[1], 1), lambda i, fb: (i, 0, 0)),
            pl.BlockSpec((1, mb, ncols), lambda i, fb: (fb[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, mb, ncols),
                               lambda i, fb: (fb[i], 0, 0)),
    )
    kern = functools.partial(_scatter_kernel, mb=mb, ncols=ncols)
    with jax.enable_x64(False):
        delta = pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_pad, mb, ncols),
                                           upd.dtype),
            # donate a zeros array into the output so front tiles no
            # child visits stay exactly zero (out blocks are only
            # written at visited indices)
            input_output_aliases={4: 0},
            interpret=interpret,
        )(fb, upd, pr[:, None, :], pc[:, :, None],
          jnp.zeros((n_pad, mb, ncols), upd.dtype))
    return delta
