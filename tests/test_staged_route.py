"""Which route a factorization took, as the program says it.

`ops/batched.factorize_device` sends a schedule of more than 96 groups
through the staged dispatch (`staged_enabled`: a program a segment,
donated buffers, `StagedLU`) and every other through the one
`jit_slu_factor`.  Pinned here: the handle's `route`, the health
ring's `last_factor` and solve records, `Stats.dispatch` and
`Stats.report()` name the route and count what was dispatched
(`dispatch`, `segments`, `groups`, `pallas_buckets`, `pallas_shapes`,
`sweep_segments`) against the schedule's own segment lists, under
either staged arm; the staged run opens `slu.fact.scale`,
`slu.fact.dispatch` and `slu.fact.wait` once a factorization inside
`FACT`, in that order, and the one-program route opens neither of the
last two; the Pallas panel LU traces under `slu.pallas_lu` inside the
caller's scope, and the staged answers stay those of the one-program
route and of scipy."""

import numpy as np
import pytest
import scipy.sparse.linalg as spla

import jax
import jax.numpy as jnp

from superlu_dist_tpu import (Options, Stats, csr_from_scipy, factorize,
                              obs, solve)
from superlu_dist_tpu.ops import batched, pallas_lu, trisolve
from superlu_dist_tpu.plan.plan import plan_factorization
from superlu_dist_tpu.utils.testmat import laplacian_3d

from test_pack_program import _inside

ROUTE_KEYS = {"dispatch", "segments", "groups", "pallas_buckets",
              "pallas_shapes"}


def _step(opts=None, k=6):
    """One refactorization on a held plan and one refined solve."""
    a = laplacian_3d(k)
    opts = opts or Options(factor_dtype="float32")
    plan = plan_factorization(a, opts)
    xt = np.random.default_rng(7).standard_normal(a.n)
    st = Stats()
    lu = factorize(a, opts, plan=plan, backend="jax", stats=st)
    x = solve(lu, a.to_scipy() @ xt, stats=st)
    return a, lu, st, x, xt


def test_the_one_program_route_says_so(monkeypatch):
    monkeypatch.delenv("SLU_STAGED", raising=False)
    a, lu, st, x, xt = _step()
    d = lu.device_lu
    groups = len(d.schedule.groups)
    assert isinstance(d, batched.DeviceLU) and groups <= 96
    want = {"dispatch": "program", "segments": 1, "groups": groups,
            "pallas_buckets": 0, "pallas_shapes": []}
    assert d.route == want
    snap = obs.HEALTH.snapshot()
    assert {k: snap["last_factor"][k] for k in ROUTE_KEYS} == want
    assert lu.factor_record["dispatch"] == "program"
    assert snap["last_solve"]["sweep_segments"] == 1
    assert st.dispatch == dict(want, sweep_segments=1)
    assert st.snapshot()["dispatch"] == st.dispatch
    assert (f"dispatch:             program, 1 programs a factorization "
            f"({groups} groups, 0 on the Pallas panel LU), 1 a sweep"
            ) in st.report()
    assert np.linalg.norm(x - xt) / np.linalg.norm(xt) < 1e-12


@pytest.mark.parametrize("merge_cells", [None, "0"])
def test_the_staged_route_counts_what_it_dispatched(monkeypatch,
                                                    merge_cells):
    """Either staged arm: a program a merged segment, or (the legacy
    arm, `SLU_FACTOR_MERGE_CELLS=0`) a program a group."""
    monkeypatch.setenv("SLU_STAGED", "1")
    if merge_cells is not None:
        monkeypatch.setenv("SLU_FACTOR_MERGE_CELLS", merge_cells)
    a, lu, st, x, xt = _step()
    d = lu.device_lu
    assert isinstance(d, batched.StagedLU)
    sched = d.schedule
    programs = (len(sched.groups) if merge_cells == "0"
                else len(batched.get_factor_segments(sched)))
    sweeps = 2 * len(trisolve.get_trisolve(sched).segments)
    want = {"dispatch": "staged", "segments": programs,
            "groups": len(sched.groups), "pallas_buckets": 0,
            "pallas_shapes": []}
    assert d.route == want
    snap = obs.HEALTH.snapshot()
    assert {k: snap["last_factor"][k] for k in ROUTE_KEYS} == want
    assert snap["last_solve"]["sweep_segments"] == sweeps
    assert st.dispatch == dict(want, sweep_segments=sweeps)
    assert (f"dispatch:             staged, {programs} programs a "
            f"factorization ({len(sched.groups)} groups, 0 on the "
            f"Pallas panel LU), {sweeps} a sweep") in st.report()
    # a solve under a Stats of its own still says whose factors it rode
    st2 = Stats()
    solve(lu, a.to_scipy() @ xt, stats=st2)
    assert st2.dispatch == st.dispatch
    assert np.linalg.norm(x - xt) / np.linalg.norm(xt) < 1e-12
    # nothing of the staged run's stamp is left on the thread
    assert obs.take_cost("dispatch") is None


def test_the_legacy_sweep_dispatches_a_program_a_group_each_way(
        monkeypatch):
    monkeypatch.setenv("SLU_STAGED", "1")
    monkeypatch.setenv("SLU_TRISOLVE", "legacy")
    _, lu, st, _, _ = _step()
    groups = len(lu.device_lu.schedule.groups)
    assert st.dispatch["sweep_segments"] == 2 * groups
    assert obs.HEALTH.snapshot()["last_solve"]["sweep_segments"] \
        == 2 * groups


def test_the_host_oracle_has_no_route():
    a = laplacian_3d(4)
    st = Stats()
    lu = factorize(a, Options(), backend="host", stats=st)
    solve(lu, np.ones(a.n), stats=st)
    assert st.dispatch == {} and "dispatch:" not in st.report()
    last = obs.HEALTH.snapshot()["last_factor"]
    assert not ROUTE_KEYS & set(last)
    assert obs.HEALTH.snapshot()["last_solve"]["sweep_segments"] is None


@pytest.mark.parametrize("staged", [True, False])
def test_the_spans_of_a_factorization(monkeypatch, staged):
    monkeypatch.setenv("SLU_STAGED", "1" if staged else "0")
    t = obs.configure(enabled=True)
    t.clear()
    try:
        for _ in range(2):
            _step()
        events = t.events()
    finally:
        obs.configure(enabled=False)

    def named(name):
        return [e for e in events if e["name"] == name]

    facts = named("FACT")
    assert len(facts) == 2
    # `models/gssvx.factorize` scales, `factorize_device` casts: two
    # `fact.scale` a factorization on either route
    assert len(named("fact.scale")) == 4
    dispatch, wait = named("fact.dispatch"), named("fact.wait")
    if not staged:
        assert not dispatch and not wait
        return
    assert len(dispatch) == len(wait) == 2
    for f, d, w in zip(facts, dispatch, wait):
        assert _inside(d, f) and _inside(w, f)
        assert d["ts"] + d["dur"] <= w["ts"]
        scales = [e for e in named("fact.scale") if _inside(e, f)]
        assert len(scales) == 2
        assert all(e["ts"] + e["dur"] <= d["ts"] for e in scales)
        # the pack is dispatched after the counters are read
        (pack,) = [e for e in named("solve.pack") if _inside(e, f)]
        assert w["ts"] + w["dur"] <= pack["ts"]


def test_forced_pallas_is_counted_with_its_shapes(monkeypatch):
    """`SLU_TPU_PALLAS=1` routes every usable bucket through the
    kernel (interpret mode here): the route says which, and the
    answer is still the system's."""
    monkeypatch.setenv("SLU_STAGED", "1")
    monkeypatch.setenv("SLU_TPU_PALLAS", "1")
    a, lu, st, x, xt = _step(k=4)
    sched = lu.device_lu.schedule
    shapes = [[g.n_loc, g.mb, g.wb] for g in sched.groups]
    assert st.dispatch["pallas_shapes"] == shapes
    assert st.dispatch["pallas_buckets"] == len(sched.groups) > 0
    assert f"{len(shapes)} on the Pallas panel LU" in st.report()
    assert np.linalg.norm(x - xt) / np.linalg.norm(xt) < 1e-12


def test_merged_eligible_members_are_the_ones_counted(monkeypatch):
    """On a TPU the merged arm hands the kernel its (mb <= 16, wb <= 8)
    members: `_pallas_shapes` counts exactly those of the segment
    metas, in group order."""
    monkeypatch.delenv("SLU_TPU_PALLAS", raising=False)
    a = laplacian_3d(6)
    plan = plan_factorization(a, Options(factor_dtype="float32"))
    sched = batched.get_schedule(plan, 1)
    dt = np.dtype("float32")
    segs = batched.get_factor_segments(sched)

    def picked():
        return [m[-1] for seg in segs
                for m in batched.factor_seg_metas(sched, seg, dt)]

    assert batched._pallas_shapes(sched, dt, picked()) == []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    want = [[g.n_loc, g.mb, g.wb] for g in sched.groups
            if g.wb <= 8 and g.mb <= 16]
    assert want and batched._pallas_shapes(sched, dt, picked()) == want
    assert all(pallas_lu.merged_eligible(wb, mb, dt)
               for _, mb, wb in want)
    # float64 is structurally ineligible (no 64-bit in Mosaic)
    assert batched._pallas_shapes(
        sched, np.dtype("float64"),
        [m[-1] for seg in segs for m in batched.factor_seg_metas(
            sched, seg, np.dtype("float64"))]) == []


def test_the_kernel_traces_under_its_own_scope():
    """`slu.pallas_lu` inside the caller's `slu.partial_lu`: what a
    device trace's operation names carry."""
    from superlu_dist_tpu.ops.dense_lu import partial_lu_batch
    F = jnp.eye(16, dtype=jnp.float32)[None].repeat(4, axis=0) * 3.0

    def f(F):
        return partial_lu_batch(F, jnp.float32(0.0), wb=8, pallas=True)

    text = jax.jit(f).lower(F).as_text(debug_info=True)
    assert "slu.partial_lu/slu.pallas_lu" in text
    # and the XLA arm carries no such name
    plain = jax.jit(lambda F: partial_lu_batch(
        F, jnp.float32(0.0), wb=8, pallas=False)).lower(F).as_text(
            debug_info=True)
    assert "slu.pallas_lu" not in plain and "slu.partial_lu" in plain


def test_staged_answers_are_the_one_program_routes(monkeypatch):
    """f32 factors, f64 residual and answer, a drifting ring of value
    sets on one held plan: the staged route's refined answers agree
    with the one-program route's and with scipy splu at 1e-9."""
    a = laplacian_3d(6)
    opts = Options(factor_dtype="float32")
    plan = plan_factorization(a, opts)
    rng = np.random.default_rng(11)
    asp = a.to_scipy().tocsr()
    for _ in range(3):
        scale = rng.uniform(0.5, 1.5, a.n)
        m = (asp.multiply(scale[:, None])).tocsr()
        m.sort_indices()
        av = csr_from_scipy(m)
        b = m @ rng.standard_normal(a.n)
        xs = {}
        for flag in ("1", "0"):
            monkeypatch.setenv("SLU_STAGED", flag)
            lu = factorize(av, opts, plan=plan, backend="jax")
            assert lu.device_lu.route["dispatch"] == (
                "staged" if flag == "1" else "program")
            xs[flag] = solve(lu, b)
        ref = spla.splu(m.tocsc()).solve(b)
        for x in xs.values():
            assert np.abs(x - ref).max() / np.abs(ref).max() < 1e-9
        assert np.abs(xs["1"] - xs["0"]).max() / np.abs(ref).max() < 1e-12
