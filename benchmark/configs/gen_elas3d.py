"""Matrix generator `elas3d`: the stiffness matrix of 3D linear
elasticity on the unit cube, as PETSc's src/ksp/ksp/tutorials/ex56.c
assembles it: ne x ne x ne trilinear hexahedra (Q1) of side 1/ne on
(ne+1)**3 nodes, displacement formulation, E = 1, nu = 0.25, three
unknowns a node interleaved (3i, 3i+1, 3i+2), the face y = 0 clamped.
scipy CSR in float64, n = 3 (ne+1)**3.

Part of the plain reference: numpy and scipy only, nothing of the
program.  The element stiffness is integrated by 2 x 2 x 2 Gauss
quadrature from the equations (B' D B over the element), not copied
from a table.  The assembled pattern is kept: every entry an element
contributes is stored, cancelled or not, as MatSetValues leaves it
(81 in the row of an interior node); a clamped row or column keeps
its stored entries as zeros beside a unit diagonal, as MatZeroRows-
Columns leaves them."""

import numpy as np
import scipy.sparse as sp

E, NU = 1.0, 0.25
# the eight corners of the reference element, x fastest
CORNERS = np.array([[x, y, z] for z in (-1, 1) for y in (-1, 1)
                    for x in (-1, 1)], dtype=np.float64)


def material():
    """The 6 x 6 isotropic matrix D of sigma = D eps, Voigt order
    (xx, yy, zz, xy, yz, zx) with engineering shear strains."""
    lam = E * NU / ((1.0 + NU) * (1.0 - 2.0 * NU))
    mu = E / (2.0 * (1.0 + NU))
    d = np.zeros((6, 6))
    d[:3, :3] = lam
    d[np.arange(3), np.arange(3)] += 2.0 * mu
    d[np.arange(3, 6), np.arange(3, 6)] = mu
    return d


def element_stiffness(h: float):
    """The 24 x 24 stiffness of a trilinear cube of side h, unknowns
    interleaved by corner (corner c's x, y, z at 3c, 3c+1, 3c+2)."""
    d = material()
    k = np.zeros((24, 24))
    g = 1.0 / np.sqrt(3.0)
    for point in CORNERS * g:           # the 2 x 2 x 2 Gauss points
        # shape function c: prod_a (1 + s_ca xi_a) / 8; its gradient
        # in x is the reference gradient times 2 / h
        f = 1.0 + CORNERS * point       # (8, 3)
        grad = np.empty((8, 3))
        for a in range(3):
            others = [b for b in range(3) if b != a]
            grad[:, a] = (CORNERS[:, a] * f[:, others[0]]
                          * f[:, others[1]] / 8.0) * (2.0 / h)
        b = np.zeros((6, 24))
        for c in range(8):
            gx, gy, gz = grad[c]
            b[:, 3 * c:3 * c + 3] = [[gx, 0, 0], [0, gy, 0], [0, 0, gz],
                                     [gy, gx, 0], [0, gz, gy],
                                     [gz, 0, gx]]
        k += b.T @ d @ b * (h / 2.0) ** 3   # unit Gauss weights
    return k


def assemble(ne: int):
    """The unclamped stiffness matrix (singular: six rigid-body
    modes), every contributed entry stored."""
    nn = ne + 1
    e = np.arange(ne)
    ex, ey, ez = np.meshgrid(e, e, e, indexing="ij")
    first = (ex + nn * (ey + nn * ez)).ravel()        # corner 0's node
    offs = np.array([x + nn * (y + nn * z) for z in (0, 1)
                     for y in (0, 1) for x in (0, 1)])
    nodes = first[:, None] + offs[None, :]            # (ne^3, 8)
    dofs = (3 * nodes[:, :, None] + np.arange(3)).reshape(-1, 24)
    ke = element_stiffness(1.0 / ne)
    rows = np.repeat(dofs, 24, axis=1).ravel()
    cols = np.tile(dofs, (1, 24)).ravel()
    vals = np.tile(ke.ravel(), len(dofs))
    n = 3 * nn ** 3
    # coo -> csr sums duplicates and keeps entries that sum to zero
    a = sp.coo_matrix((vals, (rows, cols)), shape=(n, n)).tocsr()
    a.sort_indices()
    return a


def clamped_dofs(ne: int):
    """The unknowns of the nodes on the face y = 0."""
    nn = ne + 1
    x, z = np.meshgrid(np.arange(nn), np.arange(nn), indexing="ij")
    nodes = (x + nn * nn * z).ravel()
    return np.sort((3 * nodes[:, None] + np.arange(3)).ravel())


def generate(ne: int):
    a = assemble(ne)
    fixed = np.zeros(a.shape[0], dtype=bool)
    fixed[clamped_dofs(ne)] = True
    row_of = np.repeat(np.arange(a.shape[0]), np.diff(a.indptr))
    diag = row_of == a.indices
    # rows and columns of clamped unknowns zeroed in place (the
    # entries stay stored), unit diagonal
    a.data[(fixed[row_of] | fixed[a.indices]) & ~diag] = 0.0
    a.data[fixed[row_of] & diag] = 1.0
    return a
