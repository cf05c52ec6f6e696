"""Complex dense kernels in real-pair arithmetic (the TPU complex
lowering detour).

On a TPU v5e under jax 0.9.0 (PR 23, tools/complex_probe.py) even a
tiny jitted NATIVE complex128 program (one 48×48 partial_lu + one
GEMM) aborts the process in the compiler's 64-bit rewriter, while the
same math through this module — an all-real program — compiles in
~20 s and matches numpy to 2e-15.  The triangular-sweep side of the solver
already routes around it (the real-view codec, ops/batched._mm_enc:
complex X carried as concatenated real/imag planes, panels contracted
per-plane).  This module is the FACTOR-side counterpart: the dense
partial-LU / triangular-inverse kernels of ops/dense_lu.py re-expressed
on stacked real/imag planes, so a complex factorization compiles to a
program containing NO complex ops at all.

Storage convention: a complex array of shape S is carried as a real
array of shape (2,) + S — plane 0 real, plane 1 imaginary (the same
stacking ops/batched._solve_view uses for solve-side factor storage,
which is why pair-factored flats feed the existing sweeps unchanged).
A complex multiply is the 4-product cross form, a divide goes through
the |b|² denominator, and a complex GEMM is four real GEMMs — the MXU
executes those natively; nothing here changes the math, only the
representation (the reference's z-precision kernels, e.g.
SRC/pzgstrf2.c / SRC/pzgstrs.c, reach the same arithmetic through
C doublecomplex).

Reference parity notes: partial_lu_pair mirrors ops/dense_lu.partial_lu
(pdgstrf2_trsm/Local_Dgstrf2 + pdgstrs2 analog, SRC/pdgstrf2.c:26-98)
including GESP tiny-pivot replacement (|piv| < thresh → unit(piv)·
thresh, complex unit direction as in SRC/pzgstrf2.c); the triangular
inverses mirror dense_lu's exact-Newton/blocked recursion (the DiagInv
preparation, SRC/pdgssvx.c:1436-1447).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .dense_lu import _env_unroll

_DIAG_UNROLL = _env_unroll()


# ---------------------------------------------------------------- algebra

def pmul(a, b):
    """(ar+i·ai)(br+i·bi) on (2, …) pair arrays (broadcasting)."""
    ar, ai = a[0], a[1]
    br, bi = b[0], b[1]
    return jnp.stack([ar * br - ai * bi, ar * bi + ai * br])


def pdiv(a, b):
    """a / b on pair arrays via the |b|² denominator."""
    ar, ai = a[0], a[1]
    br, bi = b[0], b[1]
    den = br * br + bi * bi
    return jnp.stack([(ar * br + ai * bi) / den,
                      (ai * br - ar * bi) / den])


def pabs(a):
    """|a| (a real array, no leading plane axis)."""
    return jnp.sqrt(a[0] * a[0] + a[1] * a[1])


def pmatmul(a, b):
    """Complex matmul as four real matmuls: (2,…,m,k) @ (2,…,k,n)."""
    ar, ai = a[0], a[1]
    br, bi = b[0], b[1]
    return jnp.stack([ar @ br - ai @ bi, ar @ bi + ai @ br])


def peinsum(sub, a, b):
    """Complex einsum over pair arrays (sub is the per-plane spec)."""
    ar, ai = a[0], a[1]
    br, bi = b[0], b[1]
    rr = jnp.einsum(sub, ar, br) - jnp.einsum(sub, ai, bi)
    ri = jnp.einsum(sub, ar, bi) + jnp.einsum(sub, ai, br)
    return jnp.stack([rr, ri])


def encode(x):
    """numpy/jnp complex array -> (2, …) real pair array."""
    return jnp.stack([jnp.real(x), jnp.imag(x)])


def decode(xp):
    """(2, …) real pair array -> complex array."""
    return jax.lax.complex(xp[0], xp[1])


# ------------------------------------------------- triangular inverses

def _newton_tri_inverse_pair(T, *, lower: bool, unit: bool):
    """Pair port of dense_lu._newton_tri_inverse: exact triangular
    inverse after ⌈log2 k⌉ Newton steps X ← X(2I − TX), every step a
    pair matmul (4 real MXU matmuls)."""
    k = T.shape[-1]
    rdt = T.dtype
    eye = jnp.eye(k, dtype=rdt)
    # complex identity, batch-rank aligned: the plane axis leads, so a
    # bare (2, k, k) constant would misalign against (2, batch…, k, k)
    # under right-aligned broadcasting
    E = jnp.stack([eye, jnp.zeros_like(eye)]).reshape(
        (2,) + (1,) * (T.ndim - 3) + (k, k))
    rows = jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    keep = rows > cols if lower else rows < cols
    N = jnp.where(keep, T, 0)                      # strict part
    if unit:
        X = E - N
        A = E + N
    else:
        d = jnp.expand_dims(
            jnp.diagonal(T, axis1=-2, axis2=-1), -1)   # (2, …, k, 1)
        Nn = pdiv(N, d)
        X = E - Nn
        A = E + Nn
    steps = max(0, (k - 1).bit_length() - 1)
    if steps > 0:
        X = jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(steps),
            lambda _, X: pmatmul(X, 2 * E - pmatmul(A, X)), X)
    if not unit:
        X = pdiv(X, jnp.swapaxes(d, -1, -2))
    return X


def _blocked_tri_inverse_pair(T, *, lower: bool, unit: bool,
                              base: int = 64):
    """Pair port of dense_lu._blocked_tri_inverse (2×2 block
    recursion, Newton leaves)."""
    k = T.shape[-1]
    if k <= base:
        return _newton_tri_inverse_pair(T, lower=lower, unit=unit)
    h = k // 2
    A = T[..., :h, :h]
    B = T[..., h:, h:]
    Ai = _blocked_tri_inverse_pair(A, lower=lower, unit=unit, base=base)
    Bi = _blocked_tri_inverse_pair(B, lower=lower, unit=unit, base=base)
    if lower:
        C = T[..., h:, :h]
        off = -pmatmul(pmatmul(Bi, C), Ai)
        top = jnp.concatenate(
            [Ai, jnp.zeros_like(C.swapaxes(-1, -2))], axis=-1)
        bot = jnp.concatenate([off, Bi], axis=-1)
    else:
        C = T[..., :h, h:]
        off = -pmatmul(pmatmul(Ai, C), Bi)
        top = jnp.concatenate([Ai, off], axis=-1)
        bot = jnp.concatenate(
            [jnp.zeros_like(C.swapaxes(-1, -2)), Bi], axis=-1)
    return jnp.concatenate([top, bot], axis=-2)


def unit_lower_inverse_pair(L):
    """inv(L) for pair unit-lower (2, N, w, w)."""
    return _blocked_tri_inverse_pair(L, lower=True, unit=True)


def upper_inverse_pair(U):
    """inv(U) for pair upper-triangular (2, N, w, w)."""
    return _blocked_tri_inverse_pair(U, lower=False, unit=False)


# ------------------------------------------------------- partial LU

def _tiny_replace_pair(piv, thresh):
    """GESP tiny-pivot replacement on a pair scalar (2,): |piv| <
    thresh → unit-direction(piv)·thresh (SRC/pzgstrf2.c's z analog of
    the sqrt(eps)·‖A‖ rule); exact zeros count separately when
    replacement is disabled (thresh == 0)."""
    apiv = pabs(piv)
    is_tiny = apiv < thresh
    one = jnp.stack([jnp.ones((), piv.dtype), jnp.zeros((), piv.dtype)])
    # the zero-apiv division lands in the unselected where branch —
    # same shielding as the real kernel's complex path
    unit = jnp.where(apiv == 0, one, piv / apiv)
    newpiv = jnp.where(is_tiny, unit * thresh, piv)
    was_zero = jnp.logical_and(apiv == 0, jnp.logical_not(is_tiny))
    return newpiv, is_tiny.astype(jnp.int32), was_zero.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("wb", "nb"))
def partial_lu_pair(F, thresh, *, wb: int, nb: int = 32):
    """Pair port of dense_lu.partial_lu: factor the leading `wb`
    columns of the square pair front F (2, mb, mb) in place.  Returns
    (F', tiny_count, zero_pivot_count): F' holds L (unit lower, cols <
    wb), U (upper, rows < wb) and the Schur complement F'[:, wb:, wb:].
    Same blocked structure as the real kernel — sequential rank-1
    elimination only on the (nb, nb) diagonal block, panels and
    trailing update as batched pair matmuls."""
    mb = F.shape[-1]
    nb = min(nb, wb)
    assert wb % nb == 0, "width buckets must be multiples of the block"
    rows = jnp.arange(mb)
    rows_nb = jax.lax.broadcasted_iota(jnp.int32, (nb, 1), 0)
    cols_nb = jax.lax.broadcasted_iota(jnp.int32, (1, nb), 1)

    def _rank1_step(t, D, tiny, nzero):
        is_t_col = cols_nb == t
        ck = jnp.sum(jnp.where(is_t_col, D, 0), axis=-1,
                     keepdims=True)                    # (2, nb, 1)
        piv = jnp.sum(jnp.where(rows_nb == t, ck, 0),
                      axis=(-2, -1))                   # (2,)
        piv, was_tiny, was_zero = _tiny_replace_pair(piv, thresh)
        below = rows_nb > t
        pivb = piv[:, None, None]
        scaled = jnp.where(below, pdiv(ck, pivb), ck)
        newcol = jnp.where(rows_nb == t, pivb, scaled)
        D = jnp.where(is_t_col, newcol, D)
        rk = jnp.sum(jnp.where(rows_nb == t, D, 0), axis=-2,
                     keepdims=True)                    # (2, 1, nb)
        # elementwise pair outer product (exact, like the real kernel's
        # broadcast multiply — no matmul-precision dependence)
        D = D - pmul(jnp.where(below, scaled, 0),
                     jnp.where(cols_nb > t, rk, 0))
        return D, tiny + was_tiny, nzero + was_zero

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
        F, tiny, nzero = carry
        k0 = kb * nb
        D = jax.lax.dynamic_slice(F, (0, k0, k0), (2, nb, nb))
        D, tiny, nzero = _factor_diag(D, tiny, nzero)
        F = jax.lax.dynamic_update_slice(F, D, (0, k0, k0))
        U11i = _newton_tri_inverse_pair(D, lower=False, unit=False)
        L11i = _newton_tri_inverse_pair(D, lower=True, unit=True)
        colp = jax.lax.dynamic_slice(F, (0, 0, k0), (2, mb, nb))
        L21 = pmatmul(colp, U11i)
        keep_r = (rows >= k0 + nb)[:, None]
        colp2 = jnp.where(keep_r, L21, colp)
        F = jax.lax.dynamic_update_slice(F, colp2, (0, 0, k0))
        rowp = jax.lax.dynamic_slice(F, (0, k0, 0), (2, nb, mb))
        U12 = pmatmul(L11i, rowp)
        keep_c = (rows >= k0 + nb)[None, :]
        rowp2 = jnp.where(keep_c, U12, rowp)
        F = jax.lax.dynamic_update_slice(F, rowp2, (0, k0, 0))
        Lcol = jnp.where(keep_r, colp2, 0)
        Urow = jnp.where(keep_c, rowp2, 0)
        F = F - pmatmul(Lcol, Urow)
        return F, tiny, nzero

    tiny0 = jnp.zeros((), jnp.int32)
    F, tiny, nzero = jax.lax.fori_loop(
        0, wb // nb, block_step, (F, tiny0, tiny0))
    return F, tiny, nzero


def partial_lu_pair_batch(F, thresh, *, wb: int, nb: int = 32):
    """vmapped partial_lu_pair over a batch of pair fronts
    (2, N, mb, mb); returns (F', tiny_count, zero_pivot_count)."""
    f = functools.partial(partial_lu_pair, wb=wb, nb=nb)
    Fs, tinys, nzeros = jax.vmap(
        lambda x: f(x, thresh), in_axes=1, out_axes=(1, 0, 0))(F)
    return Fs, jnp.sum(tinys), jnp.sum(nzeros)
