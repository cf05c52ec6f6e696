"""Matrix generator `stokes2d`: the variable-viscosity Stokes "sinker"
of PETSc's src/dm/impls/stag/tutorials/ex4.c (-dim 2 -nondimensional)
on a staggered (MAC) grid of N x N cells over the unit square, h = 1/N:

    -d/dx(2 eta du/dx) - d/dy(eta (du/dy + dv/dx)) + dp/dx = f_x
    -d/dy(2 eta dv/dy) - d/dx(eta (du/dy + dv/dx)) + dp/dy = f_y
                                      du/dx + dv/dy        = 0

u lives on the (N+1) x N vertical faces, v on the N x (N+1) horizontal
faces, p and eta at the N x N cell centres, eta also at the cell
corners for the shear terms.  eta = eta2 inside the circle of `radius`
about the centre, eta1 outside.  Free slip on all four walls: the
wall-normal velocity rows are identity rows kept in the matrix, and a
momentum row beside a wall drops its wall-side shear term.  The
pressure of cell (0, 0) is pinned by an identity row.  The continuity
rows and the pressure columns carry ex4's Kcont = eta1 / h, the
boundary and pin rows Kbound = eta1 / h**2.

Unknowns are numbered as DMStag numbers them: by cell, x fastest,
(bottom face, left face, centre); the right-most face of a row of
cells follows that row, the top-most faces follow the last row.
n = 2 N (N + 1) + N**2.  An interior momentum row stores 11 entries
(5 of its own velocity, 4 of the other, 2 pressures), a continuity
row 4, and the N**2 - 1 diagonal entries of the continuity rows are
structurally zero: the saddle point [[K, G], [D, 0]].

Part of the plain reference: numpy and scipy only, nothing of the
program, built from the equations.  scipy CSR in float64."""

import numpy as np
import scipy.sparse as sp


def size(N: int) -> int:
    return 2 * N * (N + 1) + N * N


def numbering(N: int):
    """DMStag's global numbers of v (N x (N+1)), u ((N+1) x N) and p
    (N x N), each indexed [i, j] with i along x."""
    row = 3 * N + 1                       # unknowns of one row of cells
    iv = np.empty((N, N + 1), dtype=np.int64)
    iu = np.empty((N + 1, N), dtype=np.int64)
    i = np.arange(N)[:, None]
    j = np.arange(N)[None, :]
    iv[:, :N] = j * row + 3 * i
    iu[:N, :] = j * row + 3 * i + 1
    ip = j * row + 3 * i + 2
    iu[N, :] = np.arange(N) * row + 3 * N
    iv[:, N] = N * row + np.arange(N)
    return iv, iu, ip


def viscosity(N: int, eta1: float, eta2: float, radius: float):
    """eta at the cell centres (N x N) and the cell corners
    ((N+1) x (N+1)), sampled pointwise."""
    h = 1.0 / N

    def eta(x, y):
        inside = (x - 0.5) ** 2 + (y - 0.5) ** 2 < radius ** 2
        return np.where(inside, eta2, eta1)

    c = (np.arange(N) + 0.5) * h
    k = np.arange(N + 1) * h
    return (eta(c[:, None], c[None, :]), eta(k[:, None], k[None, :]))


def generate(N: int, eta1: float = 1.0, eta2: float = 100.0,
             radius: float = 0.3):
    h = 1.0 / N
    kcont, kbound = eta1 / h, eta1 / h ** 2
    iv, iu, ip = numbering(N)
    ec, en = viscosity(N, eta1, eta2, radius)
    rows, cols, vals = [], [], []

    def put(r, c, v):
        r, c, v = np.broadcast_arrays(r, c, v)
        rows.append(r.ravel())
        cols.append(c.ravel())
        vals.append(np.asarray(v, dtype=np.float64).ravel())

    # wall-normal velocities and the pinned pressure: identity rows
    for wall in (iu[0, :], iu[N, :], iv[:, 0], iv[:, N], ip[0, 0]):
        put(wall, wall, kbound)

    # x-momentum at the interior vertical faces, i = 1 .. N-1
    i = np.arange(1, N)[:, None]
    j = np.arange(N)[None, :]
    r = iu[i, j]
    er, el = ec[i, j], ec[i - 1, j]       # centres right and left
    eu, ed = en[i, j + 1], en[i, j]       # corners above and below
    up = np.broadcast_to(j < N - 1, r.shape)    # a face above: no wall
    dn = np.broadcast_to(j > 0, r.shape)
    h2 = h * h
    put(r, r, (2 * (er + el) + eu * up + ed * dn) / h2)
    put(r, iu[i + 1, j], -2 * er / h2)
    put(r, iu[i - 1, j], -2 * el / h2)
    put(r[up], iu[i, np.minimum(j + 1, N - 1)][up], -eu[up] / h2)
    put(r[dn], iu[i, np.maximum(j - 1, 0)][dn], -ed[dn] / h2)
    put(r[up], iv[i, j + 1][up], -eu[up] / h2)
    put(r[up], iv[i - 1, j + 1][up], eu[up] / h2)
    put(r[dn], iv[i, j][dn], ed[dn] / h2)
    put(r[dn], iv[i - 1, j][dn], -ed[dn] / h2)
    put(r, ip[i, j], kcont / h)
    put(r, ip[i - 1, j], -kcont / h)

    # y-momentum at the interior horizontal faces, j = 1 .. N-1
    i = np.arange(N)[:, None]
    j = np.arange(1, N)[None, :]
    r = iv[i, j]
    eu, ed = ec[i, j], ec[i, j - 1]       # centres above and below
    er, el = en[i + 1, j], en[i, j]       # corners right and left
    rt = np.broadcast_to(i < N - 1, r.shape)
    lt = np.broadcast_to(i > 0, r.shape)
    put(r, r, (2 * (eu + ed) + er * rt + el * lt) / h2)
    put(r, iv[i, j + 1], -2 * eu / h2)
    put(r, iv[i, j - 1], -2 * ed / h2)
    put(r[rt], iv[np.minimum(i + 1, N - 1), j][rt], -er[rt] / h2)
    put(r[lt], iv[np.maximum(i - 1, 0), j][lt], -el[lt] / h2)
    put(r[rt], iu[i + 1, j][rt], -er[rt] / h2)
    put(r[rt], iu[i + 1, j - 1][rt], er[rt] / h2)
    put(r[lt], iu[i, j][lt], el[lt] / h2)
    put(r[lt], iu[i, j - 1][lt], -el[lt] / h2)
    put(r, ip[i, j], kcont / h)
    put(r, ip[i, j - 1], -kcont / h)

    # continuity at every cell but the pinned one
    i = np.arange(N)[:, None]
    j = np.arange(N)[None, :]
    free = np.ones((N, N), dtype=bool)
    free[0, 0] = False
    r = ip[i, j][free]
    put(r, iu[i + 1, j][free], kcont / h)
    put(r, iu[i, j][free], -kcont / h)
    put(r, iv[i, j + 1][free], kcont / h)
    put(r, iv[i, j][free], -kcont / h)

    n = size(N)
    a = sp.coo_matrix((np.concatenate(vals), (np.concatenate(rows),
                                              np.concatenate(cols))),
                      shape=(n, n)).tocsr()
    a.sort_indices()
    return a
