"""Block-copy extend-add lane (ISSUE 2b).

The slab↔GEMM-buffer traffic restructuring: contiguous-run detection
on the host (crafted index-map unit tests), the device block-copy
formulation (HLO pins dynamic-slice/dynamic-update-slice, zero
scatter) and numerical parity of the block lane against the element
formulation."""

import os

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

import superlu_dist_tpu as slu
from superlu_dist_tpu.ops.batched import (_contig_runs, _ea_add_blocks,
                                          _plan_child_blocks,
                                          factorize_device,
                                          get_schedule)
from superlu_dist_tpu.plan.plan import plan_factorization
from superlu_dist_tpu.sparse import csr_from_scipy


def _testmat(n=35):
    t = sp.diags([-1.0, 2.3, -1.07], [-1, 0, 1], shape=(n, n))
    return csr_from_scipy(sp.kronsum(t, t, format="csr").tocsr())


# ---- host-side detector unit tests on crafted index maps ----

def test_contig_runs_crafted():
    assert _contig_runs([]) == []
    assert _contig_runs([4]) == [(0, 1)]
    assert _contig_runs([2, 3, 4, 5]) == [(0, 4)]
    assert _contig_runs([2, 3, 7, 8, 9]) == [(0, 2), (2, 3)]
    assert _contig_runs([5, 3, 1]) == [(0, 1), (1, 1), (2, 1)]
    # a descending step breaks a run even between equal-diff segments
    assert _contig_runs([1, 2, 2, 3]) == [(0, 2), (2, 2)]


def test_plan_child_blocks_crafted():
    # fully contiguous: one run covering the vector
    assert _plan_child_blocks(np.arange(10, 30), min_run=8) \
        == [(0, 20)]
    # two long runs
    assert _plan_child_blocks(
        np.r_[np.arange(0, 10), np.arange(40, 52)], min_run=8) \
        == [(0, 10), (10, 12)]
    # ragged: any short run disqualifies (stays on the element path)
    assert _plan_child_blocks(
        np.r_[np.arange(0, 10), [99]], min_run=8) is None
    # too many runs disqualifies even when each is long
    v = np.r_[np.arange(0, 8), np.arange(20, 28), np.arange(40, 48),
              np.arange(60, 68), np.arange(80, 88)]
    assert _plan_child_blocks(v, min_run=8, max_runs=4) is None
    assert _plan_child_blocks(v, min_run=8, max_runs=5) is not None


# ---- device block-copy formulation ----

def test_ea_add_blocks_oracle_and_hlo():
    """_ea_add_blocks == numpy extend-add oracle on crafted block
    records, and its jitted HLO moves data with dynamic-slice /
    dynamic-update-slice, never scatter."""
    rng = np.random.default_rng(5)
    n_pad, mb = 2, 12
    st = 6                                   # child slab stride
    upd_buf = rng.standard_normal(100 + st)  # + tail pad
    # two blocks into front 0 (overlapping dests) + one into front 1,
    # plus one masked-off padding record
    recs = [  # (li, lj, so, dr, dc, w)
        (3, 3, 10, 0 * mb + 2, 2, 1),
        (3, 3, 40, 0 * mb + 3, 3, 1),
        (3, 3, 70, 1 * mb + 5, 5, 1),
        (3, 3, 0, 0, 0, 0),
    ]
    li, lj = 3, 3
    K = len(recs)
    so = jnp.asarray([r[2] for r in recs], jnp.int32)
    dr = jnp.asarray([r[3] for r in recs], jnp.int32)
    dc = jnp.asarray([r[4] for r in recs], jnp.int32)
    w = jnp.asarray([r[5] for r in recs], jnp.int32)
    eb_meta = ((li, lj, st, K),)
    F0 = rng.standard_normal(n_pad * mb * mb)

    fn = jax.jit(lambda F, u: _ea_add_blocks(
        F, u, ((so, dr, dc, w),), eb_meta, mb=mb, n_pad=n_pad))
    out = np.asarray(fn(jnp.asarray(F0), jnp.asarray(upd_buf)))

    ref = F0.reshape(n_pad * mb, mb).copy()
    for (rli, rlj, soff, drow, dcol, wt) in recs:
        if not wt:
            continue
        blk = upd_buf[soff:soff + rli * st].reshape(rli, st)[:, :rlj]
        ref[drow:drow + rli, dcol:dcol + rlj] += blk
    np.testing.assert_allclose(out, ref.reshape(-1), rtol=1e-14)

    txt = fn.lower(jnp.asarray(F0),
                   jnp.asarray(upd_buf)).compile().as_text()
    assert "dynamic-slice(" in txt or "dynamic_slice" in txt, \
        "block lane must read via dynamic_slice"
    assert "dynamic-update-slice(" in txt \
        or "dynamic_update_slice" in txt, \
        "block lane must write via dynamic_update_slice"
    assert "scatter(" not in txt, "block lane must not scatter"


def test_block_lane_engages_and_matches_element_lane():
    """The 2D-Laplacian schedule routes real children through the
    block lane, and the factorization matches the element formulation
    to rounding (add order differs; values must agree)."""
    a = _testmat(40)

    def run(env):
        os.environ["SLU_EA_BLOCK"] = env
        try:
            plan = plan_factorization(a, slu.Options())
            lu = factorize_device(plan, plan.scaled_values(a))
            sched = get_schedule(plan, 1)
            nblk = sum(len(g.eb_meta) for g in sched.groups)
            return np.asarray(lu.L_flat), np.asarray(lu.U_flat), nblk
        finally:
            del os.environ["SLU_EA_BLOCK"]

    L1, U1, nblk1 = run("1")
    L0, U0, nblk0 = run("0")
    assert nblk1 > 0, "no child took the block lane on a 2D Laplacian"
    assert nblk0 == 0, "SLU_EA_BLOCK=0 must disable the lane"
    scale = max(np.abs(L0).max(), 1.0)
    assert np.abs(L1 - L0).max() / scale < 1e-12
    scale = max(np.abs(U0).max(), 1.0)
    assert np.abs(U1 - U0).max() / scale < 1e-12


def test_block_lane_solve_end_to_end(monkeypatch):
    """Full gssvx through the block-lane schedule stays at f64
    accuracy; also covers upd-slab tail padding (no clamped reads)."""
    monkeypatch.setenv("SLU_EA_BLOCK", "1")
    a = _testmat(45)
    A = a.to_scipy()
    xtrue = np.random.default_rng(1).standard_normal(a.n)
    x, lu, _ = slu.gssvx(slu.Options(), a, A @ xtrue)
    assert np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue) < 1e-10
    sched = get_schedule(lu.plan, 1)
    assert sched.upd_pad > 1   # the tail pad actually engaged


def test_block_lane_complex_pair(monkeypatch):
    """Block lane under the pair (stacked real/imag plane) factor
    storage: the vmapped plane-wise copies must stay exact."""
    monkeypatch.setenv("SLU_EA_BLOCK", "1")
    monkeypatch.setenv("SLU_COMPLEX_PAIR", "1")
    from superlu_dist_tpu.utils.testmat import helmholtz_2d
    a = helmholtz_2d(6)
    A = a.to_scipy()
    rng = np.random.default_rng(2)
    xtrue = rng.standard_normal(a.n) + 1j * rng.standard_normal(a.n)
    x, _, _ = slu.gssvx(slu.Options(), a, A @ xtrue)
    assert np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue) < 1e-9


def test_block_lane_dist_mesh():
    """Block lane inside the shard_map'd distributed factor+solve:
    multi-device parity against the truth."""
    from superlu_dist_tpu.utils.testmat import convection_diffusion_2d
    import jax as _jax
    if len(_jax.devices()) < 4:
        pytest.skip("needs >= 4 virtual devices")
    from jax.sharding import Mesh
    from superlu_dist_tpu.ops.batched import make_fused_solver
    from superlu_dist_tpu.utils.testmat import manufactured_rhs
    a = convection_diffusion_2d(9)
    plan = plan_factorization(a, slu.Options(factor_dtype="float32"))
    xtrue, b = manufactured_rhs(a, nrhs=2)
    mesh = Mesh(np.array(_jax.devices()[:4]).reshape(2, 2), ("r", "c"))
    step = make_fused_solver(plan, dtype="float32", mesh=mesh)
    x, berr, *_ = step(jnp.asarray(a.data), jnp.asarray(b))
    relerr = np.linalg.norm(np.asarray(x) - xtrue) / np.linalg.norm(xtrue)
    assert relerr < 1e-10, relerr
