"""SLU_LEVEL_MERGE: one padded group per etree level — the
sequential-chain lever for the latency-bound accelerator regime
(fewer group bodies on the device per step, paying padded flops/slab;
its only chip price is a pre-round record, not re-measured).  Correctness
contract here: the merged schedule must solve to the same accuracy as
the bucketed one on every path (single-device, fused, trans, mesh),
with the child-slab stride read exactly as written (sup_slab_rb —
the cross-bucket extend-add regression this knob originally exposed).
"""

import numpy as np
import pytest

from superlu_dist_tpu import Options, gssvx
from superlu_dist_tpu.options import Trans
from superlu_dist_tpu.ops.batched import get_schedule
from superlu_dist_tpu.plan.plan import plan_factorization
from superlu_dist_tpu.utils.testmat import (laplacian_3d,
                                            manufactured_rhs,
                                            random_unsymmetric)


@pytest.fixture(autouse=True)
def _merge_on(monkeypatch):
    monkeypatch.setenv("SLU_LEVEL_MERGE", "1")


@pytest.mark.parametrize("mk", [lambda: laplacian_3d(10),
                                lambda: random_unsymmetric(
                                    300, density=0.03, seed=5)])
def test_level_merge_solves_to_oracle(mk, monkeypatch):
    # unbounded limit: exercise the maximal cross-bucket merge (the
    # correctness-hard case — mixed true extents in one padded frame)
    monkeypatch.setenv("SLU_LEVEL_MERGE_LIMIT", "1e9")
    a = mk()
    xtrue, b = manufactured_rhs(a)
    plan = plan_factorization(a, Options())
    merged = get_schedule(plan, 1)
    monkeypatch.setenv("SLU_LEVEL_MERGE", "0")
    bucketed = get_schedule(plan, 1)
    monkeypatch.setenv("SLU_LEVEL_MERGE", "1")
    assert len(merged.groups) < len(bucketed.groups)
    # one group per level at the unbounded limit
    assert len(merged.groups) == len({g.level for g in merged.groups})
    x, _, _ = gssvx(Options(), a, b, backend="jax")
    np.testing.assert_allclose(x, xtrue, rtol=1e-8)
    xt, _, _ = gssvx(Options(trans=Trans.TRANS), a,
                     a.to_scipy().T @ xtrue, backend="jax")
    np.testing.assert_allclose(xt, xtrue, rtol=1e-8)


def test_coalesce_key_collision_drops_no_front():
    """Two greedy groups in one level can close with the SAME padded
    frame; they must fold together, not overwrite — overwriting
    silently removed the first group's fronts from the schedule
    (never factored, wrong solve)."""
    from superlu_dist_tpu.ops.batched import _coalesce_buckets
    # (wb, mb) buckets engineered so group A = {(3,12),(4,6)} closes
    # at frame (4, 17) after (4,7) fails the 1.5x cost check, then
    # group B = {(4,7),(4,13)} closes at the same (4, 17) frame
    by_bucket = {(3, 12): [0, 1, 2], (4, 6): [3],
                 (4, 7): [4], (4, 13): [5]}
    out = _coalesce_buckets(by_bucket, 1.5)
    got = sorted(s for sl in out.values() for s in sl)
    assert got == [0, 1, 2, 3, 4, 5], out
    # and every input front survives at ANY limit
    for lim in (1.0, 1.2, 2.0, 1e9):
        out = _coalesce_buckets(by_bucket, lim)
        assert sorted(s for sl in out.values() for s in sl) \
            == [0, 1, 2, 3, 4, 5]
        for (wb, mb), sl in out.items():
            # frame holds every member's true extents
            for s in sl:
                owb, omb = [k for k, v in by_bucket.items()
                            if s in v][0]
                assert wb >= owb and mb - wb >= omb - owb


def test_level_merge_cost_bound(monkeypatch):
    """At the default limit the merged schedule's padded update-slab
    cells stay within ~the bound of the bucketed schedule's (the
    memory guard: an unbounded per-level merge measured 2.9× slab
    elements at n=262k, past HBM)."""
    a = laplacian_3d(10)
    plan = plan_factorization(a, Options())
    merged = get_schedule(plan, 1)           # default limit 1.5
    monkeypatch.setenv("SLU_LEVEL_MERGE", "0")
    bucketed = get_schedule(plan, 1)
    assert len(merged.groups) <= len(bucketed.groups)
    assert merged.upd_total <= 1.6 * bucketed.upd_total
    assert merged.L_total <= 1.6 * bucketed.L_total


def test_level_merge_fused_f32():
    import jax.numpy as jnp
    from superlu_dist_tpu.ops.batched import make_fused_solver
    a = laplacian_3d(8)
    xtrue, b = manufactured_rhs(a)
    plan = plan_factorization(a, Options(factor_dtype="float32"))
    step = make_fused_solver(plan, dtype="float32")
    x, berr, steps, tiny, nzero = step(jnp.asarray(a.data),
                                       jnp.asarray(b[:, None]))
    relerr = np.linalg.norm(np.asarray(x)[:, 0] - xtrue) \
        / np.linalg.norm(xtrue)
    assert relerr < 1e-9


def test_level_merge_on_mesh():
    import jax
    from jax.sharding import Mesh
    from superlu_dist_tpu.parallel import factor_dist
    devs = np.array(jax.devices()[:4])
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = Mesh(devs.reshape(4), ("d",))
    a = laplacian_3d(8)
    xtrue, b = manufactured_rhs(a)
    plan = plan_factorization(a, Options())
    step, _ = factor_dist.make_dist_step(plan, mesh)
    # RHS permuted/scaled into factor space, like the driver does
    bf = np.empty_like(b)
    bf[plan.final_row] = b * plan.row_scale
    x = np.asarray(step(plan.scaled_values(a), bf[:, None]))
    xs = x[plan.final_col][:, 0] * plan.col_scale
    np.testing.assert_allclose(xs, xtrue, rtol=1e-8, atol=1e-8)
