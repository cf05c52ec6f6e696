"""Native C++ host library (csrc/slu_host.cpp) vs Python oracles.

Mirrors the reference's stance that preprocessing passes are native
(SRC/etree.c, SRC/mmd.c, SRC/mc64ad_dist.c, SRC/symbfact.c) while
keeping the Python implementations as the comparison oracle.
"""

import numpy as np
import pytest
import scipy.sparse as sp

from superlu_dist_tpu.plan.etree import (col_counts_postordered_py,
                                         etree_symmetric_py, postorder_py,
                                         relabel_tree)
from superlu_dist_tpu.plan.rowperm import large_diag_perm_py
from superlu_dist_tpu.plan.supernodes import find_supernodes
from superlu_dist_tpu.plan.symbolic import symbolic_factorize_py
from superlu_dist_tpu.sparse import CSRMatrix
from superlu_dist_tpu.utils import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")


def _random_pattern(rng, n):
    d = rng.uniform(0.03, 0.25)
    a = sp.random(n, n, density=d, random_state=rng) + sp.eye(n)
    b = ((a + a.T) != 0).tocsr()
    b.sort_indices()
    return a.tocsr(), b


def _sym_cases():
    rng = np.random.default_rng(7)
    return [(_random_pattern(rng, n)) for n in (5, 23, 60, 150)]


def test_etree_postorder_colcounts_match_python():
    for _, b in _sym_cases():
        n = b.shape[0]
        ip = b.indptr.astype(np.int64)
        ix = b.indices.astype(np.int64)
        parent_py = etree_symmetric_py(ip, ix, n)
        parent_c = native.etree(ip, ix, n)
        np.testing.assert_array_equal(parent_py, parent_c)
        post_py = postorder_py(parent_py)
        post_c = native.postorder(parent_c)
        np.testing.assert_array_equal(post_py, post_c)
        bp = b[post_py][:, post_py].tocsr()
        bp.sort_indices()
        par2 = relabel_tree(parent_py, post_py)
        bpp = bp.indptr.astype(np.int64)
        bpi = bp.indices.astype(np.int64)
        np.testing.assert_array_equal(
            col_counts_postordered_py(bpp, bpi, par2),
            native.col_counts(bpp, bpi, par2))


def test_mdorder_is_perm_and_fill_competitive():
    """Native MD must produce a valid permutation with fill within 1.3×
    of the (exact, slow) Python minimum degree."""
    rng = np.random.default_rng(3)
    for n in (30, 80, 160):
        _, b = _random_pattern(rng, n)
        ip = b.indptr.astype(np.int64)
        ix = b.indices.astype(np.int64)
        order_c = native.amd_order(ip, ix, n)
        assert sorted(order_c) == list(range(n))

        def fill(order):
            perm = np.empty(n, dtype=np.int64)
            perm[order] = np.arange(n)
            bp = b[order][:, order].tocsr()
            bp.sort_indices()
            parent = etree_symmetric_py(bp.indptr.astype(np.int64),
                                        bp.indices.astype(np.int64), n)
            post = postorder_py(parent)
            bpp = bp[post][:, post].tocsr()
            bpp.sort_indices()
            par2 = relabel_tree(parent, post)
            return int(col_counts_postordered_py(
                bpp.indptr.astype(np.int64),
                bpp.indices.astype(np.int64), par2).sum())

        from superlu_dist_tpu.plan.mindeg import md_order
        fill_c = fill(order_c)
        fill_py = fill(md_order(ip, ix, n))
        assert fill_c <= 1.3 * fill_py + 10, (fill_c, fill_py)


def test_mc64_optimal_and_feasible():
    rng = np.random.default_rng(11)
    for n in (10, 40, 120):
        a, _ = _random_pattern(rng, n)
        acsc = a.tocsc()
        acsc.sort_indices()
        perm, u, v = native.mc64(n, acsc.indptr.astype(np.int64),
                                 acsc.indices.astype(np.int64),
                                 np.abs(acsc.data))
        assert sorted(perm) == list(range(n))
        ad = np.abs(a.toarray())
        diag = np.array([ad[i, perm[i]] for i in range(n)])
        assert (diag > 0).all()
        # optimality: log-product equals the scipy-matching oracle's
        A = CSRMatrix(n, n, a.indptr.astype(np.int64),
                      a.indices.astype(np.int64), a.data)
        perm_py = large_diag_perm_py(A)
        lp_py = np.log([ad[i, perm_py[i]] for i in range(n)]).sum()
        lp_c = np.log(diag).sum()
        assert abs(lp_py - lp_c) <= 1e-8 * max(1.0, abs(lp_py))
        # dual feasibility + complementary slackness on matched edges
        for j in range(n):
            rows = acsc.indices[acsc.indptr[j]:acsc.indptr[j + 1]]
            av = np.abs(acsc.data[acsc.indptr[j]:acsc.indptr[j + 1]])
            w = np.log(av.max()) - np.log(av)
            assert (w - u[rows] - v[j]).min() > -1e-9
        inv = np.empty(n, dtype=np.int64)
        inv[perm] = np.arange(n)
        for j in range(n):
            i = inv[j]
            w_ij = np.log(ad[:, j].max()) - np.log(ad[i, j])
            assert abs(w_ij - u[i] - v[j]) < 1e-8


# -- MC64 on a saddle point (the configuration `stokes2d_sinker`) ------

def _saddle_point(N):
    """The staggered-grid Stokes matrix after the plan's own
    equilibration: what `plan/rowperm.large_diag_perm` is handed.  A
    third of its diagonal is structurally zero."""
    from superlu_dist_tpu.plan import equilibrate
    from test_stokes2d import GEN
    a = GEN.generate(N)
    csr = CSRMatrix(a.shape[0], a.shape[1], a.indptr.astype(np.int64),
                    a.indices.astype(np.int64), a.data)
    r, c, rowcnd, colcnd, amax = equilibrate.gsequ(csr)
    _, r, c = equilibrate.laqgs(csr, r, c, rowcnd, colcnd, amax)
    s = (sp.diags(r) @ a @ sp.diags(c)).tocsr()
    s.sort_indices()
    assert np.count_nonzero(s.diagonal() == 0.0) == N * N - 1
    return s


def _edge_weights(acsc):
    av = np.abs(acsc.data)
    cmax = np.maximum.reduceat(av, acsc.indptr[:-1])
    col = np.repeat(np.arange(acsc.shape[0]), np.diff(acsc.indptr))
    return col, np.log(cmax[col]) - np.log(av)


@pytest.mark.parametrize("N", [8, 16, 24])
def test_mc64_on_the_saddle_point_equals_the_oracle(N):
    s = _saddle_point(N)
    n = s.shape[0]
    acsc = s.tocsc()
    acsc.sort_indices()
    perm, u, v = native.mc64(n, acsc.indptr.astype(np.int64),
                             acsc.indices.astype(np.int64),
                             np.abs(acsc.data))
    assert sorted(perm) == list(range(n))
    # every continuity row but the pin's leaves a zero diagonal
    assert np.count_nonzero(perm != np.arange(n)) == 2 * (N * N - 1)
    col, w = _edge_weights(acsc)
    matched = perm[acsc.indices] == col
    assert matched.sum() == n
    oracle = large_diag_perm_py(CSRMatrix(
        n, n, s.indptr.astype(np.int64), s.indices.astype(np.int64),
        s.data))
    theirs = oracle[acsc.indices] == col
    # equal log-product of the diagonal magnitudes
    assert abs(w[matched].sum() - w[theirs].sum()) <= 1e-10
    # the duals are feasible, and tight on the matching
    slack = w - u[acsc.indices] - v[col]
    assert slack.min() >= -1e-12
    assert np.abs(slack[matched]).max() <= 1e-12


def test_mc64_work_on_the_saddle_point_is_bounded():
    """No clock: the rows that all shortest-path searches finalize.
    The cheap pass leaves some seventy of the 2,303 pressure columns
    free at N = 48; each costs one search, which resets only the rows
    it touched (no length-n refill per augmentation)."""
    N = 48
    s = _saddle_point(N)
    n = s.shape[0]
    acsc = s.tocsc()
    acsc.sort_indices()
    perm, _, _, work = native.mc64_counted(
        n, acsc.indptr.astype(np.int64), acsc.indices.astype(np.int64),
        np.abs(acsc.data))
    assert np.count_nonzero(perm != np.arange(n)) == 2 * (N * N - 1)
    assert 0 < work["searches"] <= 2 * N
    bound = acsc.nnz * np.log2(n)
    assert work["rows_finalized"] <= bound / 4
    assert work["edges_scanned"] <= 2 * bound
    # and where the cheap pass matches everything, no search runs
    rng = np.random.default_rng(3)
    d = (sp.random(200, 200, density=0.03, random_state=rng)
         + sp.diags(np.full(200, 10.0))).tocsc()
    d.sort_indices()
    perm, _, _, work = native.mc64_counted(
        200, d.indptr.astype(np.int64), d.indices.astype(np.int64),
        np.abs(d.data))
    assert np.array_equal(perm, np.arange(200))
    assert work == {"searches": 0, "rows_finalized": 0,
                    "edges_scanned": 0}


def test_symbfact_matches_python():
    rng = np.random.default_rng(5)
    for n in (20, 70, 140):
        _, b = _random_pattern(rng, n)
        ip = b.indptr.astype(np.int64)
        ix = b.indices.astype(np.int64)
        parent = etree_symmetric_py(ip, ix, n)
        post = postorder_py(parent)
        bp = b[post][:, post].tocsr()
        bp.sort_indices()
        par2 = relabel_tree(parent, post)
        bpp = bp.indptr.astype(np.int64)
        bpi = bp.indices.astype(np.int64)
        cc = col_counts_postordered_py(bpp, bpi, par2)
        part = find_supernodes(par2, cc, relax=4, max_super=16)
        sym_py = symbolic_factorize_py(bpp, bpi, part)
        struct_c = native.symbfact(n, bpp, bpi, part.nsuper,
                                   part.xsup, part.sparent)
        assert len(struct_c) == part.nsuper
        for s in range(part.nsuper):
            np.testing.assert_array_equal(sym_py.struct[s], struct_c[s])
        # level-parallel variant (symbfact_dist analog) must be
        # bit-identical to the serial pass
        struct_p = native.symbfact(n, bpp, bpi, part.nsuper,
                                   part.xsup, part.sparent, threads=4)
        for s in range(part.nsuper):
            np.testing.assert_array_equal(struct_c[s], struct_p[s])


def test_supernodes_match_python_oracle():
    """Native slu_supernodes must be bit-identical to the Python
    find_supernodes (relaxed subtrees, over-wide splits, fundamental
    runs, sparent derivation)."""
    from superlu_dist_tpu.plan.supernodes import (find_supernodes,
                                                  find_supernodes_py)
    from superlu_dist_tpu.plan.etree import col_counts_postordered
    rng = np.random.default_rng(9)
    for n in (30, 120, 400):
        _, b = _random_pattern(rng, n)
        ip = b.indptr.astype(np.int64)
        ix = b.indices.astype(np.int64)
        parent = etree_symmetric_py(ip, ix, n)
        post = postorder_py(parent)
        bp = b[post][:, post].tocsr()
        bp.sort_indices()
        par2 = relabel_tree(parent, post)
        cc = col_counts_postordered(bp.indptr.astype(np.int64),
                                    bp.indices.astype(np.int64), par2)
        for relax, msup in ((1, 4), (4, 16), (32, 128)):
            p1 = find_supernodes_py(par2, cc, relax, msup)
            p2 = find_supernodes(par2, cc, relax, msup)
            assert p1.nsuper == p2.nsuper
            np.testing.assert_array_equal(p1.xsup, p2.xsup)
            np.testing.assert_array_equal(p1.supno, p2.supno)
            np.testing.assert_array_equal(p1.sparent, p2.sparent)
            np.testing.assert_array_equal(p1.levels, p2.levels)


def test_ndorder_matches_python_oracle():
    """Native nested dissection must be BIT-IDENTICAL to the numpy
    implementation (same BFS level sets, same pseudo-peripheral
    restarts, same median split, same emit order), threaded or not."""
    from superlu_dist_tpu.plan.nested import nd_order_py
    from superlu_dist_tpu.plan.colperm import symmetrize_pattern
    from superlu_dist_tpu.utils.testmat import (laplacian_2d,
                                                convection_diffusion_2d)
    import scipy.sparse as sp
    from superlu_dist_tpu.sparse import csr_from_scipy
    cases = [laplacian_2d(40), convection_diffusion_2d(25),
             csr_from_scipy((sp.random(300, 300, density=0.02,
                                       random_state=3)
                             + sp.eye(300)).tocsr())]
    for a in cases:
        b = symmetrize_pattern(a)
        o_py = nd_order_py(b.indptr, b.indices, a.n)
        for th in (1, 4):
            o_c = native.nd_order(b.indptr, b.indices, a.n, threads=th)
            np.testing.assert_array_equal(o_py, o_c)
        assert np.array_equal(np.sort(o_c), np.arange(a.n))


def test_ndorder_disconnected():
    """Many components: must not recurse per component (stack) nor
    peel one component per BFS (quadratic); output matches oracle."""
    import scipy.sparse as sp
    from superlu_dist_tpu.plan.nested import nd_order_py
    # 2000 isolated vertices — pure component-labeling path
    n = 2000
    ip = np.arange(n + 1, dtype=np.int64)
    ix = np.arange(n, dtype=np.int64)
    o = native.nd_order(ip, ix, n, threads=1)
    assert np.array_equal(np.sort(o), np.arange(n))
    # mixed component sizes, threaded and not, vs oracle
    blocks = [sp.random(30, 30, density=0.15, random_state=i)
              + sp.eye(30) for i in range(8)]
    A = sp.block_diag(blocks).tocsr()
    B = ((A + A.T) != 0).astype(float).tocsr()
    bp = B.indptr.astype(np.int64)
    bi = B.indices.astype(np.int64)
    o_py = nd_order_py(bp, bi, B.shape[0])
    for th in (1, 4):
        np.testing.assert_array_equal(
            o_py, native.nd_order(bp, bi, B.shape[0], threads=th))


def test_symbfact_parallel_wide_level():
    """Drive the threaded branch for real: ≥64 independent supernodes
    at one etree level (the cnt<64 serial guard in
    slu_symbfact_create_par would otherwise hide worker bugs)."""
    import scipy.sparse as sp
    rng = np.random.default_rng(11)
    nb, bs = 96, 4                      # 96 independent dense blocks
    blocks = []
    for _ in range(nb):
        d = np.abs(rng.standard_normal((bs, bs))) + np.eye(bs) * bs
        blocks.append(sp.csr_matrix(d))
    # couple every block's last column into one shared root column so
    # the level-1 root depends on all 96 level-0 supernodes
    A = sp.block_diag(blocks, format="lil")
    n = nb * bs + 1
    A.resize((n, n))
    A[n - 1, n - 1] = 1.0
    for k in range(nb):
        A[k * bs + bs - 1, n - 1] = 1.0
        A[n - 1, k * bs + bs - 1] = 1.0
    b = A.tocsr()
    b.sort_indices()
    ip, ix = b.indptr.astype(np.int64), b.indices.astype(np.int64)
    parent = etree_symmetric_py(ip, ix, n)
    post = postorder_py(parent)
    bp = b[post][:, post].tocsr()
    bp.sort_indices()
    par2 = relabel_tree(parent, post)
    bpp = bp.indptr.astype(np.int64)
    bpi = bp.indices.astype(np.int64)
    cc = col_counts_postordered_py(bpp, bpi, par2)
    part = find_supernodes(par2, cc, relax=1, max_super=bs)
    assert part.nsuper >= 65, "pattern must give a wide level"
    lev0 = int(np.sum(part.levels == part.levels.min()))
    assert lev0 >= 64, f"widest level only {lev0} supernodes"
    s1 = native.symbfact(n, bpp, bpi, part.nsuper, part.xsup,
                         part.sparent, threads=1)
    s4 = native.symbfact(n, bpp, bpi, part.nsuper, part.xsup,
                         part.sparent, threads=4)
    for a_, b_ in zip(s1, s4):
        np.testing.assert_array_equal(a_, b_)


def test_end_to_end_solve_with_native(laplacian_solver_check=None):
    """Full pipeline with native preprocessing must solve correctly."""
    from superlu_dist_tpu import Options, gssvx
    from superlu_dist_tpu.utils.testmat import (laplacian_2d,
                                                manufactured_rhs)
    a = laplacian_2d(14)
    xtrue, b = manufactured_rhs(a)
    x, lu, stats = gssvx(Options(), a, b, backend="host")
    relerr = np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue)
    assert relerr < 1e-10


def test_cpuid_fast_matches_full_library(monkeypatch):
    """The standalone CPUID helper must report the same words as the
    full host library — the compile-cache fingerprint has to be
    IDENTICAL whether or not the big .so was built yet, else the
    session's first process orphans its persistent-cache entries
    (the 2026-08-01 TPU-window regression).  so_is_current is forced
    False so cpuid_words_fast actually takes the standalone-helper
    branch rather than delegating back to the big library."""
    full = native.cpuid_words()
    if len(full) == 0:
        pytest.skip("non-x86 host: CPUID words empty by design")
    monkeypatch.setattr(native, "so_is_current", lambda: False)
    fast = native.cpuid_words_fast()
    assert len(fast), "standalone helper produced no words"
    np.testing.assert_array_equal(np.asarray(full), np.asarray(fast))


def test_cpuid_fast_honors_no_native_optout(monkeypatch):
    """SLU_TPU_NO_NATIVE must suppress the helper build entirely —
    environments opted out of native code get the /proc fingerprint,
    not a g++ spawn per process."""
    monkeypatch.setenv("SLU_TPU_NO_NATIVE", "1")
    monkeypatch.setattr(native, "so_is_current", lambda: False)
    assert len(native.cpuid_words_fast()) == 0


def test_cache_dir_stable_and_accel_split(tmp_path):
    """cache_dir_for: accelerator runs share one stable
    un-fingerprinted dir; CPU runs get the host-fingerprinted dir,
    and that fingerprint is deterministic across calls."""
    from superlu_dist_tpu.utils.cache import cache_dir_for, host_cache_dir
    base = str(tmp_path / "jc")
    assert cache_dir_for(base, accel=True) == base + "-accel"
    cpu_dir = cache_dir_for(base, accel=False)
    assert cpu_dir == host_cache_dir(base) != base + "-accel"
    assert host_cache_dir(base) == cpu_dir  # deterministic
