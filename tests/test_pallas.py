"""Pallas partial-LU kernel vs the XLA formulation (interpret mode on
CPU; the same kernel compiles with Mosaic on TPU)."""

import numpy as np
import pytest

import jax.numpy as jnp

from superlu_dist_tpu.ops.dense_lu import partial_lu_batch
from superlu_dist_tpu.ops import pallas_lu


@pytest.mark.parametrize("mb,wb,n", [(16, 8, 3), (32, 32, 2),
                                     (64, 16, 5),
                                     # multi-block panels (wb > nb=32)
                                     (104, 64, 2), (128, 96, 1),
                                     # non-pow2 width: _pick_nb(48)=24
                                     (64, 48, 2),
                                     # dense-root case wb == mb
                                     (64, 64, 1)])
def test_pallas_matches_xla(mb, wb, n):
    rng = np.random.default_rng(0)
    F = rng.standard_normal((n, mb, mb)).astype(np.float32)
    # diagonal dominance so no tiny pivots interfere
    F += mb * np.broadcast_to(np.eye(mb, dtype=np.float32), F.shape)
    ref, t_ref, z_ref = partial_lu_batch(jnp.asarray(F),
                                         jnp.float32(0.0), wb=wb, nb=8)
    got, t_got, z_got = pallas_lu.partial_lu_batch_pallas(
        jnp.asarray(F), jnp.float32(0.0), wb=wb, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    assert int(t_got) == int(t_ref) == 0
    assert int(z_got) == int(z_ref) == 0


def test_pallas_tiny_pivot_replacement():
    mb, wb = 16, 8
    F = np.broadcast_to(np.eye(mb, dtype=np.float32),
                        (1, mb, mb)).copy()
    F[0, 3, 3] = 1e-9          # tiny pivot
    got, tiny, nzero = pallas_lu.partial_lu_batch_pallas(
        jnp.asarray(F), jnp.float32(1e-3), wb=wb, interpret=True)
    assert int(tiny) == 1
    assert int(nzero) == 0
    assert abs(float(np.asarray(got)[0, 3, 3]) - 1e-3) < 1e-9


def test_pallas_end_to_end_solve(monkeypatch):
    """Force the Pallas dispatch through the whole device solver."""
    monkeypatch.setenv("SLU_TPU_PALLAS", "1")
    from superlu_dist_tpu import Options, gssvx
    from superlu_dist_tpu.utils.testmat import laplacian_2d
    a = laplacian_2d(8)
    xtrue = np.arange(1.0, a.n + 1.0)
    b = a.to_scipy() @ xtrue
    x, _, _ = gssvx(Options(factor_dtype="float32"), a, b,
                    backend="jax")
    relerr = np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue)
    assert relerr < 1e-10
