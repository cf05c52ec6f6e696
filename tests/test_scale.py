"""Scaling guards: 3D-mesh-shaped problems (audikw_1-class front
populations) must plan with bounded padding and update-slab memory.

These lock in two fixes that only bite at scale:
  - the liveness-based update-slab allocator (ops/batched.py
    build_schedule): peak buffer = live working set, not the sum of
    every slab in the factorization;
  - the relative-cost bucket autotuner (plan/autotune.py): thousands
    of small leaf fronts must not be rounded up to separator-sized
    buckets (observed pre-fix: 7x rounding, a 468M-element slab of
    pure padding).
"""

import numpy as np
import scipy.sparse as sp

from superlu_dist_tpu import Options
from superlu_dist_tpu.ops.batched import get_schedule
from superlu_dist_tpu.plan.plan import plan_factorization
from superlu_dist_tpu.sparse import csr_from_scipy
from superlu_dist_tpu.utils.testmat import manufactured_rhs


def lap3d(k):
    t = sp.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(k, k))
    return csr_from_scipy(
        sp.kronsum(sp.kronsum(t, t), t, format="csr").tocsr())


def test_3d_mesh_padding_bounded():
    a = lap3d(20)
    plan = plan_factorization(a, Options(factor_dtype="float32"),
                              autotune=True)
    sched = get_schedule(plan, 1)
    # padded flops within a small factor of true flops
    pad_flops = 0.0
    for g in sched.groups:
        wb, mb = g.wb, g.mb
        pad_flops += g.n_loc * (wb * wb * mb + wb * (mb - wb) ** 2)
    assert pad_flops < 8.0 * plan.factor_flops, (
        f"padding blowup: {pad_flops / plan.factor_flops:.1f}x")
    # update buffer peak must be far below the sum of all slabs
    slab_sum = sum(g.n_loc * (g.mb - g.wb) ** 2 for g in sched.groups)
    assert sched.upd_total <= slab_sum
    # and the schedule still factors correctly
    xtrue, b = manufactured_rhs(a)
    from superlu_dist_tpu import gssvx
    x, _, _ = gssvx(Options(factor_dtype="float32"), a, b,
                    backend="jax")
    relerr = np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue)
    assert relerr < 1e-9


def test_slab_reuse_actually_reuses():
    """On a chain-heavy 2D problem consecutive-level slabs must share
    address space (peak << sum)."""
    from superlu_dist_tpu.utils.testmat import laplacian_2d
    a = laplacian_2d(64)
    plan = plan_factorization(a, Options(), autotune=True)
    sched = get_schedule(plan, 1)
    slab_sum = sum(g.n_loc * (g.mb - g.wb) ** 2 for g in sched.groups)
    assert sched.upd_total < slab_sum, "no slab reuse happened"


def test_extend_add_indexes_huge_slab():
    """audikw_1-class update slabs pass 2^31 elements; jax's gather
    needs the index dtype to represent the ARRAY SIZE (wrap
    normalization), so int32 source offsets must upcast at trace time
    even when the group's own span is small.  Trace-only via
    eval_shape — no 8 GiB allocation (found by a compile-only run
    at K=100: OverflowError 5516008065 out of bounds for int32)."""
    import functools
    import jax
    import jax.numpy as jnp
    from superlu_dist_tpu.ops.batched import _ea_add

    mb, n_pad, rc_b, K = 8, 2, 4, 3
    big = 2**31 + 128          # slab longer than int32 can address
    ea_meta = ((rc_b, rc_b, K, K),)
    pos = jnp.zeros((K, rc_b), jnp.int32)
    ea_blocks = ((jnp.zeros(K, jnp.int32), jnp.ones(K, jnp.int32),
                  jnp.zeros(K, jnp.int32), pos, pos),)
    out = jax.eval_shape(
        functools.partial(_ea_add, ea_meta=ea_meta, mb=mb,
                          n_pad=n_pad),
        jax.ShapeDtypeStruct((n_pad * mb * mb,), jnp.float32),
        jax.ShapeDtypeStruct((big,), jnp.float32),
        ea_blocks)
    assert out.shape == (n_pad * mb * mb,)
