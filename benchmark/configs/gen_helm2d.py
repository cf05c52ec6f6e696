"""Matrix generator `helm2d`: the matrix PETSc's
src/ksp/ksp/tutorials/ex11.c assembles (complex scalars required): the
Helmholtz equation -Laplace(u) - sigma1 u + i sigma2 u = f on the unit
square, u = 0 on the boundary, by the 2-D five-point stencil on an
n x n grid (dim = n**2, natural ordering, x fastest) with h**2 =
1 / (n + 1)**2: every off-diagonal -1, every diagonal entry
4 - sigma1 h**2 + i sigma2_imag h**2 (ex11 adds `sigma2*h2` with
sigma2 = 10i under -norandom).  As scipy CSR in complex128, indices
sorted, every stored entry kept.  numpy and scipy only."""

import scipy.sparse as sp


def generate(n: int, sigma1: float, sigma2_imag: float):
    h2 = 1.0 / ((n + 1) * (n + 1))
    t = sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(n, n))
    a = sp.kronsum(t, t, format="csr").astype("complex128")
    a.setdiag(a.diagonal() + (-sigma1 * h2 + 1j * sigma2_imag * h2))
    a = a.tocsr()
    a.sort_indices()
    return a
