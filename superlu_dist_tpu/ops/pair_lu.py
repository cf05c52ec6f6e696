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

from .dense_lu import _DIAG_UNROLL


# ---------------------------------------------------------------- algebra
#
# A pair is anything whose [0] is the real and whose [1] the imaginary
# plane: a stacked (2, …) array at the kernels' boundaries, a Python
# tuple (re, im) of two real arrays INSIDE them.  The functions take
# either and return tuples: every `stack` of an intermediate is a
# concatenate and every `a[0]` of a stacked array a slice, neither
# fuses on the TPU (a concatenate lowers to pads and a combine), and in
# stacked form a rank-1 step or a Newton step was mostly those (the
# pair factor program of PETSc ex11 at n=4,096 compiled for a v5e: 8,139
# fusions stacked, 6,178 on tuples, for the real program's 3,530;
# PR 32).  Planes are split once where a kernel is entered (`_split`)
# and stacked once where it returns.

def pmul(a, b):
    """(ar+i·ai)(br+i·bi), planes broadcasting."""
    (ar, ai), (br, bi) = _split(a), _split(b)
    return ar * br - ai * bi, ar * bi + ai * br


def pdiv(a, b):
    """a / b via the |b|² denominator."""
    (ar, ai), (br, bi) = _split(a), _split(b)
    den = br * br + bi * bi
    return (ar * br + ai * bi) / den, (ai * br - ar * bi) / den


def pabs(a):
    """|a|: one real array."""
    return jnp.sqrt(a[0] * a[0] + a[1] * a[1])


def pmatmul(a, b):
    """Complex matmul as four real matmuls: (…,m,k) @ (…,k,n) planes."""
    (ar, ai), (br, bi) = _split(a), _split(b)
    return ar @ br - ai @ bi, ar @ bi + ai @ br


def _split(a):
    return a[0], a[1]


def _map(fn, a):
    return fn(a[0]), fn(a[1])


def _where(mask, a, b):
    return jnp.where(mask, a[0], b[0]), jnp.where(mask, a[1], b[1])


def _sub(a, b):
    return a[0] - b[0], a[1] - b[1]


def encode(x):
    """numpy/jnp complex array -> (2, …) real pair array."""
    return jnp.stack([jnp.real(x), jnp.imag(x)])


def decode(xp):
    """pair (stacked, or a tuple of planes) -> complex array."""
    return jax.lax.complex(xp[0], xp[1])


# ------------------------------------------------- triangular inverses

def _newton_tri_inverse_planes(T, *, lower: bool, unit: bool):
    """Pair port of dense_lu._newton_tri_inverse on planes (Tr, Ti),
    each (…, k, k): exact triangular inverse after ⌈log2 k⌉ Newton
    steps X ← X(2I − TX), every step a pair matmul (4 real MXU
    matmuls)."""
    k = T[0].shape[-1]
    eye = jnp.eye(k, dtype=T[0].dtype)     # the complex identity's
    rows = jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)   # real plane
    cols = jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    keep = rows > cols if lower else rows < cols
    N = _map(lambda t: jnp.where(keep, t, 0), T)   # strict part
    if not unit:
        d = _map(lambda t: jnp.expand_dims(
            jnp.diagonal(t, axis1=-2, axis2=-1), -1), T)   # (…, k, 1)
        N = pdiv(N, d)
    X = (eye - N[0], -N[1])
    A = (eye + N[0], N[1])
    steps = max(0, (k - 1).bit_length() - 1)
    if steps > 0:
        def step(_, X):
            AX = pmatmul(A, X)
            return pmatmul(X, (2 * eye - AX[0], -AX[1]))
        X = jax.lax.fori_loop(jnp.int32(0), jnp.int32(steps), step, X)
    if not unit:
        X = pdiv(X, _map(lambda t: jnp.swapaxes(t, -1, -2), d))
    return X


def _blocked_tri_inverse_planes(T, *, lower: bool, unit: bool,
                                base: int = 64):
    """Pair port of dense_lu._blocked_tri_inverse (2×2 block
    recursion, Newton leaves) on planes."""
    k = T[0].shape[-1]
    if k <= base:
        return _newton_tri_inverse_planes(T, lower=lower, unit=unit)
    h = k // 2
    Ai = _blocked_tri_inverse_planes(
        _map(lambda t: t[..., :h, :h], T), lower=lower, unit=unit,
        base=base)
    Bi = _blocked_tri_inverse_planes(
        _map(lambda t: t[..., h:, h:], T), lower=lower, unit=unit,
        base=base)

    def cat(a, b, axis):
        return (jnp.concatenate([a[0], b[0]], axis=axis),
                jnp.concatenate([a[1], b[1]], axis=axis))

    if lower:
        C = _map(lambda t: t[..., h:, :h], T)
        off = _map(jnp.negative, pmatmul(pmatmul(Bi, C), Ai))
        zero = _map(lambda c: jnp.zeros_like(c.swapaxes(-1, -2)), C)
        top, bot = cat(Ai, zero, -1), cat(off, Bi, -1)
    else:
        C = _map(lambda t: t[..., :h, h:], T)
        off = _map(jnp.negative, pmatmul(pmatmul(Ai, C), Bi))
        zero = _map(lambda c: jnp.zeros_like(c.swapaxes(-1, -2)), C)
        top, bot = cat(Ai, off, -1), cat(zero, Bi, -1)
    return cat(top, bot, -2)


@jax.named_scope("slu.tri_inverse")
def unit_lower_inverse_pair(L):
    """inv(L) for pair unit-lower (2, N, w, w)."""
    return jnp.stack(_blocked_tri_inverse_planes(_split(L), lower=True,
                                              unit=True))


@jax.named_scope("slu.tri_inverse")
def upper_inverse_pair(U):
    """inv(U) for pair upper-triangular (2, N, w, w)."""
    return jnp.stack(_blocked_tri_inverse_planes(_split(U), lower=False,
                                              unit=False))


# ------------------------------------------------------- partial LU

def _tiny_replace_planes(piv, thresh):
    """GESP tiny-pivot replacement on a pair scalar (re, im): |piv| <
    thresh → unit-direction(piv)·thresh (SRC/pzgstrf2.c's z analog of
    the sqrt(eps)·‖A‖ rule); exact zeros count separately when
    replacement is disabled (thresh == 0)."""
    apiv = pabs(piv)
    is_tiny = apiv < thresh
    # the zero-apiv division lands in the unselected where branch —
    # same shielding as the real kernel's complex path
    one = (jnp.ones((), apiv.dtype), jnp.zeros((), apiv.dtype))
    unit = _where(apiv == 0, one, (piv[0] / apiv, piv[1] / apiv))
    newpiv = _where(is_tiny, (unit[0] * thresh, unit[1] * thresh), piv)
    was_zero = jnp.logical_and(apiv == 0, jnp.logical_not(is_tiny))
    return newpiv, is_tiny.astype(jnp.int32), was_zero.astype(jnp.int32)


@jax.named_scope("slu.partial_lu")
def _partial_lu_planes(F, thresh, *, wb: int, nb: int = 32):
    """partial_lu_pair on planes F = (Fr, Fi), each (mb, mb)."""
    mb = F[0].shape[-1]
    nb = min(nb, wb)
    assert wb % nb == 0, "width buckets must be multiples of the block"
    rows = jnp.arange(mb)
    rows_nb = jax.lax.broadcasted_iota(jnp.int32, (nb, 1), 0)
    cols_nb = jax.lax.broadcasted_iota(jnp.int32, (1, nb), 1)

    def _rank1_step(t, D, tiny, nzero):
        is_t_col = cols_nb == t
        is_t_row = rows_nb == t
        ck = _map(lambda d: jnp.sum(jnp.where(is_t_col, d, 0), axis=-1,
                                    keepdims=True), D)      # (nb, 1)
        piv = _map(lambda c: jnp.sum(jnp.where(is_t_row, c, 0)), ck)
        piv, was_tiny, was_zero = _tiny_replace_planes(piv, thresh)
        below = rows_nb > t
        scaled = _where(below, pdiv(ck, piv), ck)
        D = _where(is_t_col, _where(is_t_row, piv, scaled), D)
        rk = _map(lambda d: jnp.sum(jnp.where(is_t_row, d, 0), axis=-2,
                                    keepdims=True), D)      # (1, nb)
        # elementwise pair outer product (exact, like the real kernel's
        # broadcast multiply — no matmul-precision dependence)
        upd = pmul(_map(lambda s: jnp.where(below, s, 0), scaled),
                   _map(lambda r: jnp.where(cols_nb > t, r, 0), rk))
        return _sub(D, upd), tiny + was_tiny, nzero + was_zero

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

        def take(at, size):
            return _map(lambda f: jax.lax.dynamic_slice(f, at, size), F)

        def put(P, at):
            return (jax.lax.dynamic_update_slice(F[0], P[0], at),
                    jax.lax.dynamic_update_slice(F[1], P[1], at))

        D, tiny, nzero = _factor_diag(take((k0, k0), (nb, nb)), tiny,
                                      nzero)
        F = put(D, (k0, k0))
        # the kernel scopes of dense_lu.partial_lu, at the same places
        with jax.named_scope("slu.tri_inverse"):
            U11i = _newton_tri_inverse_planes(D, lower=False,
                                              unit=False)
            L11i = _newton_tri_inverse_planes(D, lower=True, unit=True)
        colp = take((0, k0), (mb, nb))
        keep_r = (rows >= k0 + nb)[:, None]
        colp2 = _where(keep_r, pmatmul(colp, U11i), colp)
        F = put(colp2, (0, k0))
        rowp = take((k0, 0), (nb, mb))
        keep_c = (rows >= k0 + nb)[None, :]
        rowp2 = _where(keep_c, pmatmul(L11i, rowp), rowp)
        F = put(rowp2, (k0, 0))
        with jax.named_scope("slu.schur"):
            F = _sub(F, pmatmul(
                _map(lambda c: jnp.where(keep_r, c, 0), colp2),
                _map(lambda r: jnp.where(keep_c, r, 0), rowp2)))
        return F, tiny, nzero

    tiny0 = jnp.zeros((), jnp.int32)
    return jax.lax.fori_loop(0, wb // nb, block_step, (F, tiny0, tiny0))


@functools.partial(jax.jit, static_argnames=("wb", "nb"))
def partial_lu_pair(F, thresh, *, wb: int, nb: int = 32):
    """Pair port of dense_lu.partial_lu: factor the leading `wb`
    columns of the square pair front F (2, mb, mb) in place.  Returns
    (F', tiny_count, zero_pivot_count): F' holds L (unit lower, cols <
    wb), U (upper, rows < wb) and the Schur complement F'[:, wb:, wb:].
    Same blocked structure as the real kernel — sequential rank-1
    elimination only on the (nb, nb) diagonal block, panels and
    trailing update as batched pair matmuls."""
    Fp, tiny, nzero = _partial_lu_planes(_split(F), thresh, wb=wb,
                                         nb=nb)
    return jnp.stack(Fp), tiny, nzero


def partial_lu_pair_batch(F, thresh, *, wb: int, nb: int = 32):
    """_partial_lu_planes vmapped over a batch of pair fronts
    (2, N, mb, mb); returns (F', tiny_count, zero_pivot_count)."""
    f = functools.partial(_partial_lu_planes, wb=wb, nb=nb)
    Fs, tinys, nzeros = jax.vmap(lambda x: f(x, thresh))(_split(F))
    return jnp.stack(Fs), jnp.sum(tinys), jnp.sum(nzeros)
