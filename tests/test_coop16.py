"""16-device scaling evidence for the cooperative tree-top LU (VERDICT
round-1 item 6): the conftest pins 8 virtual devices, so these tests
run a fresh subprocess with a 16-device CPU platform and check

  * mesh-shape invariance at (4,4) and (4,2,2), and
  * the coop-psum share of total step traffic stays a minority share
    (the 1-D column-sharded scheme does not become psum-bound at 16
    devices; reference frame: the 2D block-cyclic panel map,
    SRC/superlu_defs.h:357-382).

Subprocess strategy mirrors the reference's oversubscribed-MPI-ranks
CTest sweep (TEST/CMakeLists.txt:48-53) at a rank count the main
process cannot host."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

_SCRIPT = r"""
import json
import numpy as np
import scipy.sparse as sp

import jax
jax.config.update("jax_platforms", "cpu")
from superlu_dist_tpu.utils.compat import set_cpu_devices
set_cpu_devices(16)

from superlu_dist_tpu.utils.cache import (host_cache_dir,
                                          place_compile_cache)
import os
place_compile_cache(host_cache_dir(
    os.path.join(os.environ["PYTHONPATH"], ".jax_cache")))

from superlu_dist_tpu import Options, csr_from_scipy
from superlu_dist_tpu.ops.batched import get_schedule
from superlu_dist_tpu.parallel.factor_dist import (make_dist_step,
                                                   measure_comm,
                                                   make_dist_factor)
from superlu_dist_tpu.parallel.grid import make_solver_mesh
from superlu_dist_tpu.plan.plan import plan_factorization

t = sp.diags([-1.0, 2.4, -1.1], [-1, 0, 1], shape=(48, 48))
a = csr_from_scipy(sp.kronsum(t, t, format="csr").tocsr())
rng = np.random.default_rng(0)
xtrue = rng.standard_normal((a.n, 2))
b = a.to_scipy() @ xtrue

plan = plan_factorization(a, Options())
# factor-space RHS/solution transforms (what the gssvx driver does)
vals = plan.scaled_values(a)
bf = np.empty_like(b)
bf[plan.final_row] = b * plan.row_scale[:, None]
out = {}
for shape in ((4, 4), (4, 2, 2)):
    g = make_solver_mesh(*shape)
    step, sched = make_dist_step(plan, g.mesh)
    x = np.asarray(step(vals, bf))
    xs = x[plan.final_col] * plan.col_scale[:, None]
    out[str(shape)] = float(np.linalg.norm(xs - xtrue)
                            / np.linalg.norm(xtrue))
    coop = [gr for gr in sched.groups if gr.coop]
    cs = sched.comm_summary(np.float64, nrhs=2)
    out.setdefault("coop_groups", {})[str(shape)] = len(coop)
    out.setdefault("comm", {})[str(shape)] = cs
# measured traffic on the 16-device flat partition
factor = make_dist_factor(plan, make_solver_mesh(4, 4).mesh)
dlu = factor(vals)
out["measured"] = measure_comm(dlu, nrhs=2)
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow    # ~52 s 16-device subprocess; the 8-dev coop
def test_16dev_invariance_and_coop_share():   # pins stay in tier-1
    from superlu_dist_tpu.utils.cache import ensure_portable_cpu_isa
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # drop the 8-device forcing (the script sets 16 via jax.config)
    # but keep codegen AVX2-portable like conftest (shared cache dir)
    env["XLA_FLAGS"] = ensure_portable_cpu_isa("")
    env["SLU_COOP_MB"] = "32"  # engage coop on the small test fronts
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    p = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=1500)
    assert p.returncode == 0, p.stderr[-2000:]
    line = [ln for ln in p.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    # mesh-shape invariance: both 16-device shapes solve to f64 class
    assert out["(4, 4)"] < 1e-10
    assert out["(4, 2, 2)"] < 1e-10
    # the same flat front partition underlies both shapes
    assert out["comm"]["(4, 4)"] == out["comm"]["(4, 2, 2)"]
    # coop actually engaged at 16 devices (tree-top groups)
    assert out["coop_groups"]["(4, 4)"] >= 1
    # measured factor all-gather bytes equal the prediction at 16 dev
    # (update-slab gathers + coop trailing-slice recombination)
    cs = out["comm"]["(4, 4)"]
    ag = out["measured"]["FACT"].get("all-gather",
                                     {"count": 0, "bytes": 0})
    assert ag["bytes"] == (cs["factor_allgather_bytes"]
                           + cs["coop_gather_bytes"]), (ag, cs)


def test_coop_traffic_accounted_at_16dev_bench_matrix():
    """On the bench-class matrix (3D Laplacian n=27k) with the
    PRODUCTION coop threshold at 16 devices, the sharded coop chain
    (ops/coop_sharded.py) must hold the traffic gains it was built
    for, versus the legacy replicated scheme (SLU_COOP_SHARDED=0):

      * the Ω(mb²)-per-front trailing recombination gather is GONE
        (coop_gather_bytes == 0 — Schur slices stay device-local and
        coop→coop extend-adds are owner-aligned by construction);
      * total predicted step traffic halves (measured at this pin:
        380 MB → 184 MB, ratio 0.483);
      * coop bytes drop ≥ 2x (261 MB → 102 MB).

    What REMAINS is the asymptotic floor: 2·mb·wb words per coop
    front — one pass of the panel columns (the reference's L-panel
    column broadcast, SRC/pdgstrf.c:1108) plus one (wb, mb) U-stripe
    psum (its U-panel row broadcast) — the same per-front movement
    the reference's 2D block-cyclic map pays.  The share lands at
    ~0.56, not the <0.20 the round-2 design sketch hoped for, because
    the DENOMINATOR halved too (forced-coop conversion of tree-top
    groups also removed their update-slab all_gathers); the absolute
    numbers above are the real guarantee, the share bound below is a
    regression backstop.  Pure schedule accounting, no device
    execution."""
    from superlu_dist_tpu import Options
    from superlu_dist_tpu.ops.batched import build_schedule
    from superlu_dist_tpu.plan.plan import plan_factorization
    from superlu_dist_tpu.utils.testmat import laplacian_3d

    assert os.environ.get("SLU_COOP_MB") is None  # production default
    a = laplacian_3d(30)
    plan = plan_factorization(a, Options(factor_dtype="float32"))
    sched = build_schedule(plan, 16)
    assert any(g.coop for g in sched.groups), \
        "tree-top coop must engage on the bench matrix at 16 devices"
    assert all(g.cp > 0 for g in sched.groups if g.coop), \
        "sharded coop must be the production default"

    def totals(s):
        cs = s.comm_summary(np.float32)
        coop_b = cs["coop_psum_bytes"] + cs["coop_gather_bytes"]
        return (coop_b, cs["factor_allgather_bytes"] + coop_b
                + cs["solve_sync_bytes"], cs)

    coop_b, total, cs = totals(sched)
    # the recombination gather is structurally eliminated
    assert cs["coop_gather_bytes"] == 0
    share = coop_b / total
    assert 0.0 < share < 0.60, f"coop share {share:.2%} of {total}"
    # versus the legacy replicated scheme: total halves, coop ≥ 2x
    os.environ["SLU_COOP_SHARDED"] = "0"
    try:
        legacy = build_schedule(plan, 16)
    finally:
        del os.environ["SLU_COOP_SHARDED"]
    lcoop_b, ltotal, lcs = totals(legacy)
    assert lcs["coop_gather_bytes"] > 0   # the old scheme's broadcast
    assert total < 0.55 * ltotal, (total, ltotal)
    assert coop_b < 0.45 * lcoop_b, (coop_b, lcoop_b)


def test_coop_solve_ownership_rotation_tradeoff(monkeypatch):
    """Coop solve-update ownership (VERDICT r3 item 5): rotation
    (SLU_COOP_SOLVE_ROTATE=1) balances per-device MEANINGFUL solve
    flops across a 16-device schedule — the pdgstrs per-supernode
    distributed-trisolve analog (SRC/pdgstrs.c:1463,2133) — with the
    sweep group count unchanged.  The default stays owner-pinned
    because the balance buys no SPMD wall-clock (every device executes
    identical-shaped sweep einsums; sentinel masking only selects
    which results survive the psum) while rotation COSTS backward
    interior syncs: parent/child owner changes inside the coop chain
    break the bwd elision the pinned design gets for free.  The fwd
    side pays a psum per coop level under EITHER design (cross_desc is
    transitive from the distributed subtrees).  This test pins all
    three facts with schedule accounting — flop balance restored,
    step count unchanged, the exact bwd sync cost."""
    from superlu_dist_tpu import Options
    from superlu_dist_tpu.ops.batched import build_schedule
    from superlu_dist_tpu.plan.plan import plan_factorization
    from superlu_dist_tpu.utils.testmat import laplacian_3d

    monkeypatch.delenv("SLU_COOP_SOLVE_ROTATE", raising=False)
    a = laplacian_3d(16)
    plan = plan_factorization(a, Options(factor_dtype="float32"))
    pinned = build_schedule(plan, 16)
    monkeypatch.setenv("SLU_COOP_SOLVE_ROTATE", "1")
    rotated = build_schedule(plan, 16)

    def coop_solve_flops(s):
        """Per-device meaningful solve-update flops: mb·wb per OWNED
        coop front (owner = the device whose col_idx row is real,
        everyone else holds sentinels)."""
        n = s.n
        fl = np.zeros(s.ndev)
        for g in s.groups:
            if not g.coop:
                continue
            owned = (g.col_idx[:, :, 0] < n).sum(axis=1)  # (ndev,)
            fl += owned * g.mb * g.wb
        return fl

    # sweep step count unchanged; coop census identical
    assert len(rotated.groups) == len(pinned.groups)
    assert ([g.coop for g in rotated.groups]
            == [g.coop for g in pinned.groups])
    fp_, fr = coop_solve_flops(pinned), coop_solve_flops(rotated)
    assert fp_.sum() == fr.sum() > 0       # same total meaningful work
    # pinned: device 0 owns ALL coop solve work
    assert fp_[0] == fp_.sum() and (fp_[1:] == 0).all()
    # rotated: useful work spreads over the chain.  Perfect balance is
    # impossible — the root front is one indivisible atom and tree-top
    # groups hold one front each — so the guarantees are (a) several
    # devices own work, (b) the busiest device is bounded by the
    # largest single front plus an even share of the rest.
    atom = max(g.mb * g.wb for g in rotated.groups if g.coop)
    assert (fr > 0).sum() >= 3, fr.tolist()
    assert fr.max() <= atom + (fr.sum() - atom) / 2, \
        (fr.tolist(), atom)
    # sync cost model: fwd syncs identical (paid per coop level either
    # way); rotation adds bwd syncs — the documented price of balance
    fwd_p = sum(g.fwd_sync for g in pinned.groups)
    fwd_r = sum(g.fwd_sync for g in rotated.groups)
    bwd_p = sum(g.bwd_sync for g in pinned.groups)
    bwd_r = sum(g.bwd_sync for g in rotated.groups)
    assert fwd_r == fwd_p
    assert bwd_r >= bwd_p, (bwd_r, bwd_p)
