"""Device partial-LU kernel vs numpy oracle."""

import numpy as np
import pytest

import jax.numpy as jnp

from superlu_dist_tpu.ops.dense_lu import (partial_lu, partial_lu_batch,
                                           partial_lu_panels,
                                           partial_lu_panels_batch,
                                           unit_lower_inverse,
                                           upper_inverse)


def np_partial_lu(F, wb):
    F = F.copy()
    for k in range(wb):
        F[k + 1:, k] /= F[k, k]
        F[k + 1:, k + 1:] -= np.outer(F[k + 1:, k], F[k, k + 1:])
    return F


@pytest.mark.parametrize("mb,wb", [(8, 8), (32, 16), (48, 32), (96, 64)])
def test_partial_lu_matches_numpy(mb, wb):
    rng = np.random.default_rng(0)
    F = rng.standard_normal((mb, mb)) + mb * np.eye(mb)
    ref = np_partial_lu(F, wb)
    out, tiny, _ = partial_lu(jnp.asarray(F), 0.0, wb=wb, nb=min(wb, 32))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-10,
                               atol=1e-10)
    assert int(tiny) == 0


def test_partial_lu_identity_padding():
    """Padding columns with identity diagonal must not change the true
    block's factors."""
    rng = np.random.default_rng(1)
    w, wb, m, mb = 5, 8, 12, 16
    F = np.zeros((mb, mb))
    A = rng.standard_normal((m, m)) + m * np.eye(m)
    # true block occupies [0:w] and [wb:wb+(m-w)]
    idx = np.concatenate([np.arange(w), wb + np.arange(m - w)])
    F[np.ix_(idx, idx)] = A
    for t in range(w, wb):
        F[t, t] = 1.0
    ref = np_partial_lu(A, w)
    out, _, _ = partial_lu(jnp.asarray(F), 0.0, wb=wb, nb=8)
    out = np.asarray(out)
    np.testing.assert_allclose(out[np.ix_(idx, idx)], ref, rtol=1e-10,
                               atol=1e-10)


def test_tiny_pivot_replacement():
    F = np.array([[1e-30, 1.0], [1.0, 1.0]])
    out, tiny, _ = partial_lu(jnp.asarray(F), 1e-8, wb=2, nb=2)
    assert int(tiny) == 1
    assert np.isfinite(np.asarray(out)).all()


def test_batch_and_inverses():
    rng = np.random.default_rng(2)
    B, mb, wb = 4, 32, 16
    F = rng.standard_normal((B, mb, mb)) + mb * np.eye(mb)
    out, tiny, _ = partial_lu_batch(jnp.asarray(F), 0.0, wb=wb, nb=16)
    out = np.asarray(out)
    for i in range(B):
        ref = np_partial_lu(F[i], wb)
        np.testing.assert_allclose(out[i], ref, rtol=1e-9, atol=1e-9)
    L11 = np.tril(out[:, :wb, :wb], -1) + np.eye(wb)
    U11 = np.triu(out[:, :wb, :wb])
    Li = np.asarray(unit_lower_inverse(jnp.asarray(L11)))
    Ui = np.asarray(upper_inverse(jnp.asarray(U11)))
    for i in range(B):
        np.testing.assert_allclose(Li[i] @ L11[i], np.eye(wb), atol=1e-9)
        np.testing.assert_allclose(Ui[i] @ U11[i], np.eye(wb), atol=1e-9)


def test_complex_dtype():
    rng = np.random.default_rng(3)
    mb, wb = 16, 8
    F = (rng.standard_normal((mb, mb)) + 1j * rng.standard_normal((mb, mb))
         + mb * np.eye(mb)).astype(np.complex128)
    ref = np_partial_lu(F, wb)
    out, _, _ = partial_lu(jnp.asarray(F), 0.0, wb=wb, nb=8)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-10, atol=1e-10)


# ---- the panel-first form (partial_lu_panels): the block loop carries
# the column panel and the row panel, the Schur complement is one
# K = wb product after it -------------------------------------------

def np_partial_lu_gesp(F, wb, thresh):
    """Unblocked right-looking elimination with the GESP tiny-pivot
    rule: the arithmetic every blocked formulation of partial_lu
    (whole-front or panel-first) reorders.  Returns (F', tiny, zero)."""
    F = F.copy()
    tiny = zero = 0
    for k in range(wb):
        piv = F[k, k]
        if abs(piv) < thresh:
            tiny += 1
            piv = (piv / abs(piv) if abs(piv) else 1.0) * thresh
            F[k, k] = piv
        elif piv == 0:
            zero += 1
        with np.errstate(divide="ignore", invalid="ignore"):
            F[k + 1:, k] /= piv
            F[k + 1:, k + 1:] -= np.outer(F[k + 1:, k], F[k, k + 1:])
    return F, tiny, zero


def _front(rng, mb, dtype, n=None):
    shape = (mb, mb) if n is None else (n, mb, mb)
    F = rng.standard_normal(shape)
    if np.dtype(dtype).kind == "c":
        F = F + 1j * rng.standard_normal(shape)
    return (F + mb * np.eye(mb)).astype(dtype)


def _plant(F, wb, value):
    """Decoupled pivots at block starts, block ends and the panel's
    last column: row and column k are zero off the diagonal, so the
    pivot met at step k is exactly `value` and a replaced one scales
    nothing.  Returns the number planted."""
    ks = sorted({0, min(31, wb - 1), min(32, wb - 1), wb // 2, wb - 1})
    for k in ks:
        F[..., k, :] = 0
        F[..., :, k] = 0
        F[..., k, k] = value
    return len(ks)


def _check_pieces(C, R, S, ref, wb, tol):
    """L, U and the Schur complement of one front against the oracle's
    assembled F'."""
    C, R, S = (np.asarray(x) for x in (C, R, S))
    mb = ref.shape[0]
    assert C.shape == (mb, wb) and R.shape == (wb, mb - wb)
    assert S.shape == (mb - wb, mb - wb)
    scale = np.abs(ref).max()
    kw = dict(rtol=tol, atol=tol * scale)
    low = np.tril(np.ones((mb, wb), bool), -1)
    np.testing.assert_allclose(np.where(low, C, 0),
                               np.where(low, ref[:, :wb], 0), **kw)  # L
    np.testing.assert_allclose(np.triu(C[:wb]), np.triu(ref[:wb, :wb]),
                               **kw)                                 # U11
    np.testing.assert_allclose(R, ref[:wb, wb:], **kw)               # U12
    np.testing.assert_allclose(S, ref[wb:, wb:], **kw)               # Schur


# shapes the cells run: block-aligned, deep (K = 512), a root front
# (no row panel, no Schur), and pivot widths under a lane tile
_PANEL_SHAPES = [(256, 32), (384, 128), (768, 512), (1024, 256),
                 (256, 256), (128, 8), (64, 16)]


@pytest.mark.parametrize("mb,wb", _PANEL_SHAPES)
def test_panel_first_matches_numpy(mb, wb):
    F = _front(np.random.default_rng(mb + wb), mb, np.float64)
    ref, _, _ = np_partial_lu_gesp(F, wb, 0.0)
    C, R, S, tiny, nzero = partial_lu_panels(jnp.asarray(F), 0.0, wb=wb)
    _check_pieces(C, R, S, ref, wb, 1e-9)
    assert (int(tiny), int(nzero)) == (0, 0)
    # the whole-front contract is the same pieces, put back
    out, _, _ = partial_lu(jnp.asarray(F), 0.0, wb=wb)
    out = np.asarray(out)
    assert np.array_equal(out[:, :wb], np.asarray(C))
    assert np.array_equal(out[:wb, wb:], np.asarray(R))
    assert np.array_equal(out[wb:, wb:], np.asarray(S))


@pytest.mark.parametrize("mb,wb", _PANEL_SHAPES)
def test_panel_first_counts_planted_pivots(mb, wb):
    """Tiny and exactly-zero pivots at block boundaries: replaced and
    counted as the elimination's rule says, and an unreplaced zero
    (thresh = 0) is flagged."""
    rng = np.random.default_rng(7 * mb + wb)
    thresh = 1e-8
    F = _front(rng, mb, np.float64)
    planted = _plant(F, wb, 1e-30)
    F[0, 0] = 0.0                     # an exact zero is tiny too (+thresh)
    ref, tiny_ref, zero_ref = np_partial_lu_gesp(F, wb, thresh)
    C, R, S, tiny, nzero = partial_lu_panels(jnp.asarray(F), thresh,
                                             wb=wb)
    assert (int(tiny), int(nzero)) == (tiny_ref, zero_ref) == (planted, 0)
    _check_pieces(C, R, S, ref, wb, 1e-9)
    # ReplaceTinyPivot=NO: a zero is the singularity signal.  It is
    # divided by, so everything eliminated after it is NaN and no
    # later pivot can be told: one, at the panel's last column
    G = _front(rng, mb, np.float64)
    G[wb - 1, :] = G[:, wb - 1] = 0
    _, tiny_ref, zero_ref = np_partial_lu_gesp(G, wb, 0.0)
    _, _, _, tiny, nzero = partial_lu_panels(jnp.asarray(G), 0.0, wb=wb)
    assert (int(tiny), int(nzero)) == (tiny_ref, zero_ref) == (0, 1)


@pytest.mark.parametrize("mb,wb", [(96, 32), (64, 8), (128, 128)])
def test_panel_first_batch_under_vmap(mb, wb):
    """n = 3 fronts through partial_lu_panels_batch: the three pieces
    the factor program stores, and the summed counters."""
    rng = np.random.default_rng(mb)
    F = _front(rng, mb, np.float64, n=3)
    planted = _plant(F[1], wb, 1e-30)
    Lsrc, Usrc, upd, tiny, nzero = partial_lu_panels_batch(
        jnp.asarray(F), 1e-8, wb=wb)
    assert Lsrc.shape == (3, mb, wb) and Usrc.shape == (3, wb, mb)
    assert upd.shape == (3, mb - wb, mb - wb)
    assert (int(tiny), int(nzero)) == (planted, 0)
    whole, tiny_w, _ = partial_lu_batch(jnp.asarray(F), 1e-8, wb=wb)
    assert int(tiny_w) == planted
    for i in range(3):
        ref, _, _ = np_partial_lu_gesp(F[i], wb, 1e-8)
        _check_pieces(Lsrc[i], Usrc[i][:, wb:], upd[i], ref, wb, 1e-9)
        np.testing.assert_allclose(np.triu(np.asarray(Usrc[i])[:, :wb]),
                                   np.triu(ref[:wb, :wb]), rtol=1e-9,
                                   atol=1e-9 * np.abs(ref).max())
        np.testing.assert_allclose(np.asarray(whole[i]), ref, rtol=1e-9,
                                   atol=1e-9 * np.abs(ref).max())


@pytest.mark.parametrize("mb,wb", [(384, 128), (64, 16)])
def test_panel_first_complex64(mb, wb):
    F = _front(np.random.default_rng(5), mb, np.complex64)
    planted = _plant(F, wb, 1e-30)
    ref, tiny_ref, _ = np_partial_lu_gesp(F.astype(np.complex128), wb,
                                          1e-6)
    C, R, S, tiny, nzero = partial_lu_panels(jnp.asarray(F),
                                             np.float32(1e-6), wb=wb)
    assert C.dtype == R.dtype == S.dtype == np.complex64
    assert (int(tiny), int(nzero)) == (tiny_ref, 0) == (planted, 0)
    _check_pieces(C, R, S, ref, wb, 2e-4)
