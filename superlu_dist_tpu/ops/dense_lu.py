"""Dense blocked partial LU without pivoting (device kernel).

The panel-factorization kernel of the TPU build — the analog of
pdgstrf2_trsm/Local_Dgstrf2 (SRC/pdgstrf2.c:26-98,404) fused with the
U-row TRSM (pdgstrs2_omp) and the Schur update, expressed as a blocked
right-looking LU of the front's two panels (the leading wb columns and
the leading wb rows), then the trailing update once:

    for each NB-wide column block:
        unblocked rank-1 panel factorization (tiny-pivot replacement,
        the GESP sqrt(eps)·‖A‖ rule of SRC/pdgstrf2.c)
        TRSM for the U block row (unit-lower solve)
        masked rank-NB GEMM update of the two panels
    Schur complement  F22 − L21·U12, one K = wb GEMM (runs on the MXU)

Everything is static-shaped: `wb` (padded pivot width) and the front
size come from the bucket plan, loop bounds are Python ints, and
row/column masks replace dynamic-size slices so XLA sees one fused
GEMM per panel and block step.  Identity padding in columns [w, wb)
makes the padded factorization equal the true one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


# unroll chunk of the diagonal-block elimination chain
_DIAG_UNROLL = 8


def _newton_tri_inverse(T, *, lower: bool, unit: bool):
    """inv(T) for batched (…, k, k) triangular T via Newton iteration
    X ← X(2I − TX).  For triangular T the error I − TX is nilpotent
    (strictly triangular after the diagonal seed), so the iteration is
    EXACT after ⌈log2 k⌉ steps — and every step is an MXU matmul,
    unlike lax.linalg.triangular_solve which TPU lowers to a
    sequential column sweep."""
    k = T.shape[-1]
    dtype = T.dtype
    eye = jnp.eye(k, dtype=dtype)
    rows = jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    keep = rows > cols if lower else rows < cols
    N = jnp.where(keep, T, 0)                   # strict part
    if unit:
        X = eye - N                             # exact for k ≤ 2
        A = eye + N
    else:
        d = jnp.expand_dims(
            jnp.diagonal(T, axis1=-2, axis2=-1), -1)  # (..., k, 1)
        # T = D(I + D⁻¹N) [lower: row scaling]  or (I + ND⁻¹)D [upper]
        # handled uniformly by scaling N's rows by 1/d for lower and
        # N's rows by 1/d for upper too (N strictly upper: row i of
        # D⁻¹T has N[i,:]/d[i]) — both cases are D⁻¹T = I + D⁻¹N.
        Nn = N / d
        X = eye - Nn
        A = eye + Nn
    steps = max(0, (k - 1).bit_length() - 1)
    # fori_loop, not Python unroll: the two dots per step are the whole
    # body, so unrolling only multiplies program size (compile time)
    # without enabling any fusion
    if steps > 0:
        # int32 bounds: under jax_enable_x64 Python-int bounds make the
        # induction variable int64, which Mosaic cannot lower when this
        # helper is traced inside the Pallas kernel (its 64->32 scalar
        # convert self-recurses)
        X = jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(steps),
            lambda _, X: X @ (2 * eye - A @ X), X)
    if not unit:
        X = X / jnp.swapaxes(d, -1, -2)         # inv = inv(I+D⁻¹N)·D⁻¹
    return X


def _blocked_tri_inverse(T, *, lower: bool, unit: bool, base: int = 64):
    """inv(T) for batched (…, k, k) triangular T by 2×2 block
    recursion:  inv([[A,0],[C,B]]) = [[Ai,0],[−Bi·C·Ai,Bi]] (lower)
    and the transposed identity for upper.  O(log k) recursion depth,
    all large MXU matmuls; leaves use the exact Newton inverse."""
    k = T.shape[-1]
    if k <= base:
        return _newton_tri_inverse(T, lower=lower, unit=unit)
    h = k // 2
    A = T[..., :h, :h]
    B = T[..., h:, h:]
    Ai = _blocked_tri_inverse(A, lower=lower, unit=unit, base=base)
    Bi = _blocked_tri_inverse(B, lower=lower, unit=unit, base=base)
    if lower:
        C = T[..., h:, :h]
        off = -(Bi @ C @ Ai)
        top = jnp.concatenate([Ai, jnp.zeros_like(C.swapaxes(-1, -2))],
                              axis=-1)
        bot = jnp.concatenate([off, Bi], axis=-1)
    else:
        C = T[..., :h, h:]
        off = -(Ai @ C @ Bi)
        top = jnp.concatenate([Ai, off], axis=-1)
        bot = jnp.concatenate([jnp.zeros_like(C.swapaxes(-1, -2)), Bi],
                              axis=-1)
    return jnp.concatenate([top, bot], axis=-2)


def _tiny_replace(piv, thresh, dtype):
    """GESP tiny-pivot replacement: |piv| < thresh → sign(piv)·thresh
    (SRC/pdgstrf2.c; counted into stat->TinyPivots).  Also flags an
    exactly-zero pivot that was NOT replaced (thresh == 0, i.e.
    ReplaceTinyPivot=NO) — the reference's info=k singularity signal
    (SRC/pdgstrf.c header)."""
    apiv = jnp.abs(piv)
    is_tiny = apiv < thresh
    if jnp.issubdtype(dtype, jnp.complexfloating):
        unit = jnp.where(apiv == 0, jnp.ones((), dtype), piv / apiv)
        newpiv = jnp.where(is_tiny, unit * thresh, piv)
    else:
        sgn = jnp.where(piv >= 0, jnp.ones((), dtype), -jnp.ones((), dtype))
        newpiv = jnp.where(is_tiny, sgn * thresh, piv)
    was_zero = jnp.logical_and(apiv == 0, jnp.logical_not(is_tiny))
    return newpiv, is_tiny.astype(jnp.int32), was_zero.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("wb", "nb"))
@jax.named_scope("slu.partial_lu")
def partial_lu_panels(F, thresh, *, wb: int, nb: int = 32):
    """Factor the leading `wb` columns of the square front F (mb×mb),
    panel first: returns (C, R, S, tiny_count, zero_pivot_count) where
    C (mb×wb) is the column panel F'[:, :wb] (L unit lower below the
    diagonal, U11 on and above it), R (wb×r, r = mb − wb) the row
    panel U12 = F'[:wb, wb:], and S (r×r) the Schur complement
    F[wb:, wb:] − L21·U12.
    `thresh` is the tiny-pivot threshold (0 disables replacement —
    pass a tiny positive to keep the guard).

    The block loop carries the two panels only: each block's rank-nb
    update touches mb·wb + wb·r entries, and the r×r trailing matrix
    is read once, after the loop, by one K = wb matmul.  The
    sequential rank-1 elimination runs on the (nb, nb) diagonal block
    ONLY; the column panel (L21 = A21·U11⁻¹) and the block row
    (U12 = L11⁻¹·A12) are batched triangular solves — O(nb²) work per
    sequential step instead of O(mb·nb), with the mb-sized dimension
    entirely on matrix units."""
    mb = F.shape[-1]
    r = mb - wb
    dtype = F.dtype
    nb = min(nb, wb)
    assert wb % nb == 0, "width buckets must be multiples of the block"
    rows = jnp.arange(mb)
    cols_wb = jnp.arange(wb)
    rows_nb = jax.lax.broadcasted_iota(jnp.int32, (nb, 1), 0)
    cols_nb = jax.lax.broadcasted_iota(jnp.int32, (1, nb), 1)

    def _rank1_step(t, D, tiny, nzero):
        """One masked rank-1 elimination step of the (nb, nb) diagonal
        block.  `t` may be a traced index: column/row t are extracted
        by iota-mask reductions and the update is a full-block outer
        product that is exactly zero outside the trailing submatrix,
        so the result is bitwise the sliced formulation's."""
        is_t_col = cols_nb == t
        ck = jnp.sum(jnp.where(is_t_col, D, 0), axis=1,
                     keepdims=True)                       # (nb, 1)
        piv = jnp.sum(jnp.where(rows_nb == t, ck, 0))
        piv, was_tiny, was_zero = _tiny_replace(piv, thresh, dtype)
        below = rows_nb > t
        scaled = jnp.where(below, ck / piv, ck)
        newcol = jnp.where(rows_nb == t, piv, scaled)
        D = jnp.where(is_t_col, newcol, D)
        rk = jnp.sum(jnp.where(rows_nb == t, D, 0), axis=0,
                     keepdims=True)                       # (1, nb)
        # broadcast multiply, NOT (nb,1)@(1,nb): a matmul would run at
        # the ambient matmul precision (bf16 single-pass for f32 off
        # the _hi_prec paths); the elementwise product is exact
        D = D - jnp.where(below, scaled, 0) * jnp.where(
            cols_nb > t, rk, 0)
        return D, tiny + was_tiny, nzero + was_zero

    # chain-unroll granularity: the nb-step scalar critical path is
    # unrolled in chunks of `cu` inside a fori_loop — full unrolling
    # made program size (and so compile time) scale with the whole
    # chain, while per-chunk unrolling keeps the fused-body count at
    # nb/cu with compile cost O(cu)
    cu = max(1, min(_DIAG_UNROLL, nb))
    while nb % cu:
        cu -= 1

    def _factor_diag(D, tiny, nzero):
        def chunk(c, carry):
            D, tiny, nzero = carry
            for i in range(cu):
                D, tiny, nzero = _rank1_step(c * cu + i, D, tiny,
                                             nzero)
            return D, tiny, nzero
        return jax.lax.fori_loop(0, nb // cu, chunk, (D, tiny, nzero))

    def block_step(kb, carry):
        C, R, tiny, nzero = carry
        k0 = kb * nb
        D = jax.lax.dynamic_slice(C, (k0, k0), (nb, nb))
        D, tiny, nzero = _factor_diag(D, tiny, nzero)
        C = jax.lax.dynamic_update_slice(C, D, (k0, k0))
        # exact Newton triangular inverses of the nb×nb factors: MXU
        # matmuls instead of triangular_solve's sequential column sweep
        with jax.named_scope("slu.tri_inverse"):
            U11i = _newton_tri_inverse(D, lower=False, unit=False)
            L11i = _newton_tri_inverse(D, lower=True, unit=True)
        # L21 = A21 · U11⁻¹ over the full column slice; keep rows ≥
        # k0+nb (rows < k0 hold finished U entries, D already written)
        colp = jax.lax.dynamic_slice(C, (0, k0), (mb, nb))
        L21 = colp @ U11i
        keep_r = (rows >= k0 + nb)[:, None]
        colp2 = jnp.where(keep_r, L21, colp)
        C = jax.lax.dynamic_update_slice(C, colp2, (0, k0))
        # U12 = L11⁻¹ · A12 over the block row, which lies in both
        # panels: inside the column panel keep cols ≥ k0+nb (cols < k0
        # hold finished L entries); the row panel takes it whole
        rowc = jax.lax.dynamic_slice(C, (k0, 0), (nb, wb))
        keep_c = (cols_wb >= k0 + nb)[None, :]
        rowc2 = jnp.where(keep_c, L11i @ rowc, rowc)
        C = jax.lax.dynamic_update_slice(C, rowc2, (k0, 0))
        # this block's rank-nb update of the PANELS alone, restricted
        # to i, j ≥ k0+nb via masking; the trailing r×r waits
        Lcol = jnp.where(keep_r, colp2, 0)
        C = C - Lcol @ jnp.where(keep_c, rowc2, 0)
        if r:
            rowr = L11i @ jax.lax.dynamic_slice(R, (k0, 0), (nb, r))
            R = jax.lax.dynamic_update_slice(R, rowr, (k0, 0))
            R = R - Lcol[:wb] @ rowr
        return C, R, tiny, nzero

    tiny0 = jnp.zeros((), jnp.int32)
    C, R, tiny, nzero = jax.lax.fori_loop(
        0, wb // nb, block_step,
        (F[:, :wb], F[:wb, wb:], tiny0, tiny0))
    S = F[wb:, wb:]
    if r:
        # the Schur complement, once: K = wb on the MXU
        with jax.named_scope("slu.schur"):
            S = S - C[wb:] @ R
    return C, R, S, tiny, nzero


@functools.partial(jax.jit, static_argnames=("wb", "nb"))
def partial_lu(F, thresh, *, wb: int, nb: int = 32):
    """partial_lu_panels reassembled in place of the front: returns
    (F', tiny_count, zero_pivot_count) where F' holds L (unit lower,
    cols < wb), U (upper, rows < wb) and the Schur complement
    F'[wb:, wb:].  For callers of the whole-front contract; the factor
    program takes the pieces (partial_lu_panels_batch)."""
    C, R, S, tiny, nzero = partial_lu_panels(F, thresh, wb=wb, nb=nb)
    right = jnp.concatenate([R, S], axis=0)
    return jnp.concatenate([C, right], axis=1), tiny, nzero


def _use_pallas(F, pallas: bool | None) -> bool:
    """`pallas` overrides the env-resolved routing: True routes this
    call through the VMEM-resident Pallas kernel (ops/pallas_lu.py)
    when Mosaic can lower the dtype (the merged factor segments'
    small-bucket promotion, ops/batched.factor_seg_metas), False
    forces the XLA path, None keeps the historical SLU_TPU_PALLAS
    resolution."""
    from . import pallas_lu
    use = (pallas_lu.enabled(F.dtype) if pallas is None
           else bool(pallas) and pallas_lu.mosaic_dtype(F.dtype))
    return use and pallas_lu.usable(F.shape[-1], F.dtype)


def partial_lu_batch(F, thresh, *, wb: int, nb: int = 32,
                     pallas: bool | None = None):
    """vmapped partial_lu over a batch of fronts (N, mb, mb).
    Returns (F', tiny_count, zero_pivot_count).  Dispatches to the
    Pallas kernel where `_use_pallas` says so."""
    if _use_pallas(F, pallas):
        from . import pallas_lu
        with jax.named_scope("slu.partial_lu"):
            return pallas_lu.partial_lu_batch_pallas(F, thresh, wb=wb)
    Fs, tinys, nzeros = jax.vmap(
        lambda x: partial_lu(x, thresh, wb=wb, nb=nb))(F)
    return Fs, jnp.sum(tinys), jnp.sum(nzeros)


def partial_lu_panels_batch(F, thresh, *, wb: int, nb: int = 32,
                            pallas: bool | None = None):
    """vmapped partial_lu_panels over a batch of fronts (N, mb, mb), in
    the three pieces the factor program stores: returns (Lsrc (N, mb,
    wb) = F'[:, :, :wb], Usrc (N, wb, mb) = F'[:, :wb, :], the Schur
    complements (N, r, r), tiny_count, zero_pivot_count).  No mb×mb
    front is reassembled; the Pallas kernel, which works on the whole
    front in VMEM, is sliced.  `pallas` as in `_use_pallas`."""
    if _use_pallas(F, pallas):
        F, tiny, nzero = partial_lu_batch(F, thresh, wb=wb,
                                          pallas=pallas)
        return F[:, :, :wb], F[:, :wb, :], F[:, wb:, wb:], tiny, nzero
    C, R, S, tinys, nzeros = jax.vmap(
        lambda x: partial_lu_panels(x, thresh, wb=wb, nb=nb))(F)
    Usrc = jnp.concatenate([C[:, :wb, :], R], axis=2)
    return C, Usrc, S, jnp.sum(tinys), jnp.sum(nzeros)


@jax.named_scope("slu.tri_inverse")
def unit_lower_inverse(L):
    """inv(L) for batched unit-lower (N, w, w) — the DiagInv
    preparation (SRC/pdgssvx.c:1436-1447): turns the solve's TRSV into
    GEMM.  Blocked 2×2 recursion + exact Newton leaves, all MXU."""
    return _blocked_tri_inverse(L, lower=True, unit=True)


@jax.named_scope("slu.tri_inverse")
def upper_inverse(U):
    """inv(U) for batched upper-triangular (N, w, w)."""
    return _blocked_tri_inverse(U, lower=False, unit=False)
