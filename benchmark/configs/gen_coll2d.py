"""Matrix generator `coll2d`: one backward-Euler Picard system of a
linearized Fokker-Planck collision operator on a velocity grid, the
shape of the XGC collision matrices of Kashi et al. (IPDPS 2022):
npar x nperp cells in (v_par, v_perp), v_par fastest, a nine-point
stencil cut at the walls: n = npar * nperp, nnz = (3 npar - 2) *
(3 nperp - 2); 32 x 31 gives n = 992, nnz = 8,554.

    A = I - dtnu * C,      C f = div(D grad f - F f)

in cylindrical velocity coordinates (Jacobian v_perp):

    C f = d/dvpar (G_par) + (1 / v_perp) d/dvperp (v_perp G_perp),
    G   = D grad f - F f,
    D   = T (I + k(w) (|w|**2 I - w w')),  k(w) = Z / (1 + |w|**2)**2.5,
    F   = -(v - u),       w = (v - u) / sqrt(T)

with v - u = (v_par - u, v_perp).  D w = T w, so the Maxwellian of
temperature T about the flow u is stationary in the continuum; the
pitch-angle term k (|w|**2 I - w w'), smooth at w = 0 and falling
like |w|**-3 far out, is the full tensor's off-diagonal part and
gives the stencil its corners, the drag makes it nonsymmetric.  Conservative finite volumes: cells centred at
v_par_i = -vmax + (i + 1/2) hpar, v_perp_j = (j + 1/2) hperp (the
axis v_perp = 0 is a face, where the metric makes the flux vanish);
the normal derivative on a face is the two-cell difference, the
tangential one the mean of the two cells' centred differences
(one-sided in a cell at a wall), f on a face the mean of its two
cells; zero flux through all four walls.  The cell volumes
(v_perp_j) are then in the null space of C': density is conserved to
rounding.

A member of a batch is a (density, T, u, dtnu0): dtnu = dtnu0 *
density / T**1.5.  Every entry of C is linear in the face
coefficients, so B members' values are (B, faces) coefficient fields
through a fixed sparse map: `values(grid, params)` makes (B, nnz) in
the CSR order of `generate`'s pattern with no loop over members.

Part of the plain reference: numpy and scipy only, nothing of the
program, written from the equations; nothing is copied from a table
of XGC's."""

import functools

import numpy as np
import scipy.sparse as sp

Z_EFF = 1.0         # strength of the pitch-angle term


@functools.lru_cache(maxsize=4)
def grid(npar: int = 32, nperp: int = 31, vmax: float = 4.0):
    """The grid's fixed operators.  Cells are numbered j * npar + i.
    For each family of faces (par: between i and i + 1; perp: between
    j and j + 1; the walls carry no flux and are left out): `diff`
    the normal difference, `cross` the tangential derivative, `mean`
    the face value, each faces x cells, and `div` cells x faces, the
    divergence with the metric in it."""
    hpar, hperp = 2.0 * vmax / npar, vmax / nperp
    vpar = -vmax + (np.arange(npar) + 0.5) * hpar
    vperp = (np.arange(nperp) + 0.5) * hperp
    n = npar * nperp
    eye_par, eye_perp = sp.identity(npar), sp.identity(nperp)

    def step(m):         # (m - 1) x m: cell k -> -1, cell k + 1 -> +1
        return sp.diags([-np.ones(m - 1), np.ones(m - 1)], [0, 1],
                        shape=(m - 1, m))

    def centred(m, h):   # m x m: centred difference, one-sided at a wall
        d = sp.diags([-np.ones(m - 1), np.ones(m - 1)], [-1, 1],
                     shape=(m, m)).tolil()
        d[0, 0], d[0, 1] = -2.0, 2.0
        d[m - 1, m - 2], d[m - 1, m - 1] = -2.0, 2.0
        return d.tocsr() / (2.0 * h)

    g = {"npar": npar, "nperp": nperp, "n": n, "hpar": hpar,
         "hperp": hperp, "vpar": vpar, "vperp": vperp,
         "volume": np.repeat(vperp, npar)}
    # faces between i and i + 1, numbered j * (npar - 1) + i
    to_face = abs(step(npar)) / 2.0
    g["par"] = {
        "diff": sp.kron(eye_perp, step(npar) / hpar, format="csr"),
        "cross": sp.kron(centred(nperp, hperp), to_face, format="csr"),
        "mean": sp.kron(eye_perp, to_face, format="csr"),
        "div": sp.kron(eye_perp, -step(npar).T / hpar, format="csr"),
        "v": ((vpar[:-1] + hpar / 2.0)[None, :].repeat(nperp, 0).ravel(),
              vperp[:, None].repeat(npar - 1, 1).ravel()),
    }
    # faces between j and j + 1, numbered j * npar + i; the metric:
    # (v_perp_face G)_(j+1/2) - (v_perp_face G)_(j-1/2) over
    # v_perp_j hperp
    to_face = abs(step(nperp)) / 2.0
    vface = vperp[:-1] + hperp / 2.0
    metric = sp.diags(1.0 / vperp) @ (-step(nperp).T) @ sp.diags(vface)
    g["perp"] = {
        "diff": sp.kron(step(nperp) / hperp, eye_par, format="csr"),
        "cross": sp.kron(to_face, centred(npar, hpar), format="csr"),
        "mean": sp.kron(to_face, eye_par, format="csr"),
        "div": sp.kron(metric / hperp, eye_par, format="csr"),
        "v": (vpar[None, :].repeat(nperp - 1, 0).ravel(),
              vface[:, None].repeat(npar, 1).ravel()),
    }
    # the pattern: the nine-point stencil, cut at the walls
    band_par = sp.diags([np.ones(npar - 1), np.ones(npar),
                         np.ones(npar - 1)], [-1, 0, 1])
    band_perp = sp.diags([np.ones(nperp - 1), np.ones(nperp),
                          np.ones(nperp - 1)], [-1, 0, 1])
    pattern = sp.kron(band_perp, band_par, format="csr")
    pattern.sort_indices()
    g["indptr"], g["indices"] = pattern.indptr, pattern.indices
    slot = sp.csr_matrix(
        (np.arange(1, pattern.nnz + 1, dtype=np.float64),
         pattern.indices, pattern.indptr), shape=(n, n))
    # every entry of C is linear in the face coefficients: for the
    # term div @ diag(c) @ op, entry (r, k) = sum_f div[r, f] c[f]
    # op[f, k]; `maps[family][term]` is nnz x faces
    g["maps"] = {}
    for family in ("par", "perp"):
        div = g[family]["div"].tocoo()
        g["maps"][family] = {}
        for term in ("diff", "cross", "mean"):
            op = g[family][term].tocsr()
            # each (r, f) of div against each (f, k) of op's row f
            count = np.diff(op.indptr)[div.col]
            first = np.repeat(op.indptr[div.col]
                              - (np.cumsum(count) - count), count)
            at = first + np.arange(count.sum())
            where = np.asarray(slot[np.repeat(div.row, count),
                                    op.indices[at]]).ravel() - 1
            assert (where >= 0).all()
            g["maps"][family][term] = sp.csr_matrix(
                (np.repeat(div.data, count) * op.data[at],
                 (where.astype(np.int64), np.repeat(div.col, count))),
                shape=(pattern.nnz, op.shape[0]))
    g["diagonal"] = np.asarray(
        slot[np.arange(n), np.arange(n)]).ravel().astype(np.int64) - 1
    return g


def coefficients(g, family: str, temperature, flow):
    """The face fields of one family for B members: D's normal and
    tangential components and the drag's normal one, each
    (B, faces)."""
    vpar, vperp = g[family]["v"]
    T = np.asarray(temperature, dtype=np.float64)[:, None]
    u = np.asarray(flow, dtype=np.float64)[:, None]
    wpar, wperp = (vpar[None, :] - u), vperp[None, :] * np.ones_like(u)
    if family == "par":
        normal, tangent = wpar, wperp
    else:
        normal, tangent = wperp, wpar
    k = Z_EFF / (1.0 + (wpar ** 2 + wperp ** 2) / T) ** 2.5
    # D = T I + k (|v - u|**2 I - (v - u)(v - u)'): its
    # normal-normal and normal-tangent components on the face
    d_nn = T + k * tangent ** 2
    d_nt = -k * normal * tangent
    return d_nn, d_nt, -normal              # F = -(v - u)


def collision_values(g, temperature, flow):
    """C's values for B members, (B, nnz) in the pattern's CSR
    order."""
    out = 0.0                               # (nnz, B) until the end
    for family in ("par", "perp"):
        d_nn, d_nt, drag = coefficients(g, family, temperature, flow)
        maps = g["maps"][family]
        out = out + (maps["diff"] @ d_nn.T + maps["cross"] @ d_nt.T
                     - maps["mean"] @ drag.T)
    return np.ascontiguousarray(out.T)


def values(g, density, temperature, flow, dtnu0):
    """A = I - dtnu C for B members, dtnu = dtnu0 density / T**1.5:
    (B, nnz) float64 in the pattern's CSR order."""
    T = np.asarray(temperature, dtype=np.float64)
    dtnu = (np.asarray(dtnu0, dtype=np.float64)
            * np.asarray(density, dtype=np.float64) / T ** 1.5)
    out = -dtnu[:, None] * collision_values(g, T, flow)
    out[:, g["diagonal"]] += 1.0
    return out


def matrix(g, vals):
    """One member's values as scipy CSR on the pattern."""
    return sp.csr_matrix((np.asarray(vals, dtype=np.float64),
                          g["indices"], g["indptr"]),
                         shape=(g["n"], g["n"]))


def generate(npar: int = 32, nperp: int = 31, vmax: float = 4.0,
             density: float = 1.0, temperature: float = 1.0,
             flow: float = 0.0, dtnu0: float = 0.5):
    """The pattern with one member's values (the configuration's
    reference plasma), scipy CSR in float64."""
    g = grid(npar, nperp, vmax)
    return matrix(g, values(g, [density], [temperature], [flow],
                            [dtnu0])[0])
