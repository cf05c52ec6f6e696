"""Level-merged factor sweep (ISSUE 12, ops/batched.py): chains of
small consecutive factor groups coalesce into one donated-buffer
dispatch segment.  The acceptance bar is the PR 7 trisolve bar —
merged factors BITWISE-identical (array_equal) to the legacy per-group
sweep at fp64 — pinned here across the staged, fused-device, host and
dist lanes, plus the segment cost model, the arm labeling the
factor-timing records carry, and the warmup/dispatch signature
alignment."""

import numpy as np
import pytest
import scipy.sparse as sp

from superlu_dist_tpu import Options
from superlu_dist_tpu.ops import batched as B
from superlu_dist_tpu.plan.plan import plan_factorization
from superlu_dist_tpu.sparse import csr_from_scipy


def _testmat(m=40):
    t = sp.diags([-1.0, 2.3, -1.1], [-1, 0, 1], shape=(m, m))
    return csr_from_scipy(sp.kronsum(t, t, format="csr").tocsr())


def _plan(a, dtype="float64"):
    return plan_factorization(a, Options(factor_dtype=dtype))


def _panels_equal(p1, p2):
    return len(p1) == len(p2) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for a_, b_ in zip(p1, p2) for x, y in zip(a_, b_))


# --------------------------------------------------------------------
# segment cost model
# --------------------------------------------------------------------

class _G:
    def __init__(self, n_loc, mb, wb=1, cp=0):
        self.n_loc, self.mb, self.wb, self.cp = n_loc, mb, wb, cp


class _S:
    def __init__(self, groups):
        self.groups = groups


def test_segments_chain_small_groups():
    # four tiny groups chain into one segment
    s = _S([_G(1, 8)] * 4)
    assert B.compute_factor_segments(s, cells=1024, cap=10**9) \
        == [[0, 1, 2, 3]]


def test_segments_large_group_stands_alone():
    s = _S([_G(1, 8), _G(64, 128), _G(1, 8), _G(1, 8)])
    segs = B.compute_factor_segments(s, cells=1024, cap=10**9)
    assert segs == [[0], [1], [2, 3]]


def test_segments_cap_bounds_program_size():
    # cells(G(1,8)) = 64; cap=128 -> two per segment
    s = _S([_G(1, 8)] * 5)
    segs = B.compute_factor_segments(s, cells=1024, cap=128)
    assert segs == [[0, 1], [2, 3], [4]]
    # every group appears exactly once, in order
    assert [i for seg in segs for i in seg] == list(range(5))


def test_segments_cached_per_knobs(monkeypatch):
    a = _testmat(20)
    sched = B.get_schedule(_plan(a), 1)
    s1 = B.get_factor_segments(sched)
    assert B.get_factor_segments(sched) is s1
    monkeypatch.setenv("SLU_FACTOR_MERGE_CELLS", "1")
    s2 = B.get_factor_segments(sched)
    assert s2 is not s1          # knob change rebuilds, never stale


# --------------------------------------------------------------------
# the bitwise contract (fp64, the PR 7 bar) across lanes
# --------------------------------------------------------------------

@pytest.fixture
def staged(monkeypatch):
    monkeypatch.setenv("SLU_STAGED", "1")


def _factor_arms(plan, vals, dtype, monkeypatch):
    monkeypatch.setenv("SLU_FACTOR_MERGE_CELLS", "0")
    lu_leg = B.factorize_device(plan, vals, dtype)
    monkeypatch.setenv("SLU_FACTOR_MERGE_CELLS", "65536")
    lu_m = B.factorize_device(plan, vals, dtype)
    return lu_leg, lu_m


def test_merged_staged_factor_bitwise_fp64(staged, monkeypatch):
    a = _testmat(26)
    plan = _plan(a)
    vals = plan.scaled_values(a)
    lu_leg, lu_m = _factor_arms(plan, vals, np.float64, monkeypatch)
    assert isinstance(lu_m, B.StagedLU)
    # the merged dispatch actually merged something
    segs = B.get_factor_segments(lu_m.schedule)
    assert any(len(s) > 1 for s in segs)
    assert _panels_equal(lu_leg.panels, lu_m.panels)
    # solves through the merged factors are bitwise too (staged lane)
    b = np.random.default_rng(0).standard_normal((a.n, 3))
    assert np.array_equal(B.solve_device(lu_leg, b),
                          B.solve_device(lu_m, b))


def test_merged_staged_matches_fused_device_lane(staged, monkeypatch):
    """StagedLU panels concatenated in group order ARE the DeviceLU
    slab layout (the StagedLU docstring contract) — the merged sweep
    must preserve that identity against the FUSED one-program lane at
    fp64."""
    a = _testmat(20)
    plan = _plan(a)
    vals = plan.scaled_values(a)
    monkeypatch.setenv("SLU_FACTOR_MERGE_CELLS", "65536")
    lu_m = B.factorize_device(plan, vals, np.float64)
    monkeypatch.setenv("SLU_STAGED", "0")
    lu_f = B.factorize_device(plan, vals, np.float64)
    assert isinstance(lu_f, B.DeviceLU)
    cat = [np.concatenate([np.asarray(p[i]).ravel()
                           for p in lu_m.panels])
           for i in range(4)]
    for got, want in zip(cat, (lu_f.L_flat, lu_f.U_flat,
                               lu_f.Li_flat, lu_f.Ui_flat)):
        assert np.array_equal(got, np.asarray(want))


def test_merged_flag_inert_on_host_and_dist_lanes(monkeypatch):
    """The merge flag is dispatch granularity for the STAGED lane
    only: the host backend and the mesh factor program must be
    bit-for-bit unaffected by flipping it."""
    from superlu_dist_tpu import factorize
    from superlu_dist_tpu.models.gssvx import solve as lu_solve
    a = _testmat(20)
    rng = np.random.default_rng(1)
    b = rng.standard_normal(a.n)
    monkeypatch.setenv("SLU_FACTOR_MERGE_CELLS", "0")
    x0 = lu_solve(factorize(a, Options(), backend="host"), b)
    monkeypatch.setenv("SLU_FACTOR_MERGE_CELLS", "65536")
    x1 = lu_solve(factorize(a, Options(), backend="host"), b)
    assert np.array_equal(x0, x1)

    # dist lane: the shard_map'd _factor_loop never reads the flag —
    # factor flats across a 2-device CPU mesh are bitwise stable
    # under a flip
    import jax
    from jax.sharding import Mesh
    from superlu_dist_tpu.parallel.factor_dist import make_dist_factor
    devs = jax.devices()
    if len(devs) < 2 or devs[0].platform != "cpu":
        pytest.skip("no 2-device CPU mesh in this process")
    mesh = Mesh(np.array(devs[:2]), axis_names=("z",))
    plan = _plan(a)
    vals = plan.scaled_values(a)
    monkeypatch.setenv("SLU_FACTOR_MERGE_CELLS", "0")
    d0 = make_dist_factor(plan, mesh, dtype=np.float64)(vals)
    monkeypatch.setenv("SLU_FACTOR_MERGE_CELLS", "65536")
    d1 = make_dist_factor(plan, mesh, dtype=np.float64)(vals)
    for f0, f1 in ((d0.L_flat, d1.L_flat), (d0.U_flat, d1.U_flat)):
        assert np.array_equal(np.asarray(f0), np.asarray(f1))


def test_complex_stays_legacy_and_bitwise(staged, monkeypatch):
    """Complex factorization keeps the per-group dispatch under the
    merged flag (complex multiplies re-associate when XLA:CPU fuses
    across group boundaries — measured ~1e-17 drift), so flipping the
    flag is bitwise inert on the complex lane and the arm label says
    so."""
    a = _testmat(16)
    ac = csr_from_scipy(
        (a.to_scipy() + 1j * sp.eye(a.n, format="csr") * 0.3).tocsr())
    plan = plan_factorization(ac, Options(factor_dtype="complex128"))
    vals = plan.scaled_values(ac)
    lu_leg, lu_m = _factor_arms(plan, vals, np.complex128,
                                monkeypatch)
    assert _panels_equal(lu_leg.panels, lu_m.panels)
    assert B.factor_arm(lu_m.schedule, np.complex128) == "legacy"


# --------------------------------------------------------------------
# arm labeling + warmup alignment
# --------------------------------------------------------------------

def test_factor_arm_labels(monkeypatch):
    monkeypatch.setenv("SLU_FACTOR_MERGE_CELLS", "0")
    assert B.factor_arm() == "legacy"
    monkeypatch.delenv("SLU_FACTOR_MERGE_CELLS", raising=False)
    assert B.factor_arm() == "merged"     # default arm is merged
    a = _testmat(20)
    sched = B.get_schedule(_plan(a), 1)
    # on CPU without the force flag the kernel never engages
    assert B.factor_arm(sched, np.float32) == "merged"
    # f64 is structurally ineligible even when forced
    monkeypatch.setenv("SLU_TPU_PALLAS", "1")
    assert B.factor_arm(sched, np.float64) == "merged"
    # forced + eligible dtype claims the kernel
    assert B.factor_arm(sched, np.float32) == "merged+pallas"
    assert B.factor_arm() == "merged+pallas"
    monkeypatch.setenv("SLU_TPU_PALLAS", "0")
    assert B.factor_arm(sched, np.float32) == "merged"


def test_warmup_signatures_are_segment_keys(monkeypatch):
    """staged_signatures under the merged arm must key by SEGMENT —
    exactly what _staged_factor_run dispatches — via the shared
    factor_seg_metas definition (a drift would turn warmed programs
    into dead compiles, the trisolve seg_metas lesson)."""
    from superlu_dist_tpu.utils.warmup import staged_signatures
    a = _testmat(30)
    sched = B.get_schedule(_plan(a, "float32"), 1)
    monkeypatch.setenv("SLU_FACTOR_MERGE_CELLS", "65536")
    fsigs, ssigs = staged_signatures(sched)
    # the sweep a staged handle dispatches is one program, whatever
    # the factor arm
    assert len(ssigs) == 1
    segs = B.get_factor_segments(sched)
    assert 0 < len(fsigs) <= len(segs)
    for (metas, _opnd), seg_i in fsigs.items():
        assert metas == B.factor_seg_metas(sched, segs[seg_i],
                                           np.float32)
    # legacy arm keeps the per-group keys
    monkeypatch.setenv("SLU_FACTOR_MERGE_CELLS", "0")
    fsigs_leg, ssigs_leg = staged_signatures(sched)
    assert all(len(k) == 9 for k in fsigs_leg)
    assert ssigs_leg == ssigs


def test_factor_segment_hlo_contract():
    """The registry entry next to the code: donated slab streaming +
    promised assembly scatters survive the merged segment lowering
    (tools/slulint assert_contract, the one-line migration shape)."""
    import sys
    sys.path.insert(0, str(__import__("pathlib").Path(
        __file__).resolve().parents[1]))
    from tools.slulint.contracts import assert_contract
    assert_contract("factor.staged_segment")
