"""Which route a factorization took, as the program says it.

`ops/batched.factorize_device` sends a schedule of more than 96 groups
through the staged dispatch (`staged_enabled`: a program a segment,
donated buffers, `StagedLU`) and every other through the one
`jit_slu_factor`.  Pinned here: the handle's `route`, the health
ring's `last_factor` and solve records, `Stats.dispatch` and
`Stats.report()` name the route and count what was dispatched
(`dispatch`, `segments`, `groups`, `pallas_buckets`, `pallas_shapes`,
`sweep_segments`) against the schedule's own segment lists, under
either staged arm; a sweep on a staged handle is ONE program under the
merged trisolve arm, `jit_slu_solve_packed` as on every other handle
(the staged rule is the factor program's), and a program a group each
way under the legacy one; the staged run opens `slu.fact.scale`,
`slu.fact.dispatch` and `slu.fact.wait` once a factorization inside
`FACT`, in that order, and the one-program route opens neither of the
last two; the Pallas panel LU traces under `slu.pallas_lu` inside the
caller's scope, and the staged answers stay those of the one-program
route and of scipy."""

import dataclasses

import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.linalg as spla

import jax
import jax.numpy as jnp

from superlu_dist_tpu import (Options, Stats, csr_from_scipy, factorize,
                              obs, solve)
from superlu_dist_tpu.options import Trans
from superlu_dist_tpu.ops import batched, pallas_lu, trisolve
from superlu_dist_tpu.plan.plan import plan_factorization
from superlu_dist_tpu.serve import solve_jit_cache_size
from superlu_dist_tpu.utils.testmat import laplacian_3d

from test_pack_program import _inside
from test_trisolve import _assert_ulp_close

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
    sweeps = 1              # `jit_slu_solve_packed`, either factor arm
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


def _staged_system(storage):
    """A small system and its options: `laplacian_3d(6)` in real
    float32 factors, or with a complex shift in complex64 factors
    (pair-stored under `SLU_COMPLEX_PAIR=1`)."""
    a = laplacian_3d(6)
    if storage == "float32":
        return a, Options(factor_dtype="float32"), np.float64
    m = (a.to_scipy() + (0.4 + 0.3j) * sp.identity(a.n)).tocsr()
    m.sort_indices()
    return (csr_from_scipy(m), Options(factor_dtype="complex64"),
            np.complex128)


@pytest.mark.parametrize("storage", ["float32", "pair_complex64"])
@pytest.mark.parametrize("nrhs", [1, 8])
@pytest.mark.parametrize("trans", [False, True])
def test_a_staged_handle_sweeps_in_one_program(monkeypatch, trans,
                                               nrhs, storage):
    """Merged arm, forced-staged plan: the handle's FACTORED sweep is
    the packed program's, within 4·eps·max|x| of the per-segment
    `trisolve.staged_sweeps` on the same packs (the same member bodies
    in the same order, compiled as one program or as many); the
    refined answer is the one-program route's and scipy's."""
    monkeypatch.setenv("SLU_STAGED", "1")
    monkeypatch.delenv("SLU_TRISOLVE", raising=False)
    pair = storage == "pair_complex64"
    if pair:
        monkeypatch.setenv("SLU_COMPLEX_PAIR", "1")
    a, opts, rdt = _staged_system(storage)
    plan = plan_factorization(a, opts)
    lu = factorize(a, opts, plan=plan, backend="jax")
    d = lu.device_lu
    assert isinstance(d, batched.StagedLU)
    assert batched._lu_is_pair(d) == pair
    assert batched.sweep_programs(d) == 1
    rng = np.random.default_rng(5)
    bf = rng.standard_normal((a.n, nrhs))
    if pair:
        bf = bf + 1j * rng.standard_normal((a.n, nrhs))
    bf = bf.astype(d.dtype)             # factor ordering and precision
    fn = batched.solve_device_trans if trans else batched.solve_device
    before = solve_jit_cache_size(lu)
    x = fn(d, bf)
    assert x.shape == bf.shape and x.dtype == bf.dtype
    assert solve_jit_cache_size(lu) == max(before, 0) + 1
    fn(d, bf)                           # the same signature: no compile
    assert solve_jit_cache_size(lu) == max(before, 0) + 1
    ts = trisolve.get_trisolve(d.schedule)
    packs = trisolve.get_packs(d)
    bin_ = batched._pair_encode_rhs(bf) if pair else bf
    ref = np.asarray(trisolve.staged_sweeps(
        ts, packs, jnp.asarray(bin_), d.dtype, trans, pair=pair))
    if pair:
        ref = batched._pair_decode_sol(ref, bf.dtype)
    _assert_ulp_close(x, ref, f"{storage} trans={trans} nrhs={nrhs}")
    # the refined answers: staged against one program against scipy
    asp = a.to_scipy().tocsc()
    xt = rng.standard_normal((a.n, nrhs)).astype(rdt)
    b = (asp.T if trans else asp) @ xt
    tr = Trans.TRANS if trans else Trans.NOTRANS
    xs = {}
    for flag in ("1", "0"):
        monkeypatch.setenv("SLU_STAGED", flag)
        h = lu if flag == "1" else factorize(a, opts, plan=plan,
                                             backend="jax")
        assert h.device_lu.route["dispatch"] == (
            "staged" if flag == "1" else "program")
        xs[flag] = solve(dataclasses.replace(
            h, options=h.effective_options.replace(trans=tr)), b)
    ref = spla.splu(asp).solve(b, trans="T" if trans else "N")
    for x in xs.values():
        assert np.abs(x - ref).max() / np.abs(ref).max() < 1e-9
    assert np.abs(xs["1"] - xs["0"]).max() / np.abs(ref).max() < 1e-12


@pytest.mark.parametrize("storage", ["float32", "pair_complex64"])
def test_the_recompile_pin_holds_for_a_staged_tenant(monkeypatch,
                                                     storage):
    """`solve_jit_cache_size` probes the packed program for a
    `StagedLU` too: at least 1 after a solve, and flat across three
    refactorizations on a held plan and across repeated solves."""
    monkeypatch.setenv("SLU_STAGED", "1")
    monkeypatch.delenv("SLU_TRISOLVE", raising=False)
    if storage == "pair_complex64":
        monkeypatch.setenv("SLU_COMPLEX_PAIR", "1")
    a, opts, rdt = _staged_system(storage)
    plan = plan_factorization(a, opts)
    b = (a.to_scipy() @ np.ones(a.n)).astype(rdt)
    sizes = []
    for _ in range(3):
        lu = factorize(a, opts, plan=plan, backend="jax")
        assert isinstance(lu.device_lu, batched.StagedLU)
        for _ in range(2):
            x = solve(lu, b)
            sizes.append(solve_jit_cache_size(lu))
        assert np.abs(x - 1).max() < 1e-9
    assert sizes[0] >= 1 and len(set(sizes)) == 1
    # a program a group each way has no one cache to probe
    monkeypatch.setenv("SLU_TRISOLVE", "legacy")
    assert solve_jit_cache_size(lu) == -1


def test_a_staged_solve_finds_its_packs(monkeypatch):
    """The pack is the factorization's (`at_factor`): the first solve
    on a staged handle takes a hit, opens no `slu.solve.pack` and
    dispatches one sweep program."""
    monkeypatch.setenv("SLU_STAGED", "1")
    monkeypatch.delenv("SLU_TRISOLVE", raising=False)
    a, opts, _ = _staged_system("float32")
    st = Stats()
    lu = factorize(a, opts, backend="jax", stats=st)
    d = lu.device_lu
    assert isinstance(d, batched.StagedLU)
    assert obs.HEALTH.snapshot()["last_factor"]["pack"] == "at_factor"
    packs = d._trisolve_packs[1]
    t = obs.configure(enabled=True)
    t.clear()
    try:
        solve(lu, a.to_scipy() @ np.ones(a.n), stats=st)
        names = [e["name"] for e in t.events()]
    finally:
        obs.configure(enabled=False)
    assert "solve.sweep" in names and "solve.pack" not in names
    assert trisolve.get_packs(d) is packs
    assert st.packs == {"at_factor": 1, "at_solve": 0}
    assert st.dispatch["sweep_segments"] == 1


def test_a_staged_handle_is_labeled_by_what_it_dispatches(monkeypatch):
    """`active_arm`: a staged handle's FACTORED solve dispatches the
    packed program of the arm like any other handle, so the label is
    the arm's, whatever the handle's form."""
    monkeypatch.setenv("SLU_STAGED", "1")
    monkeypatch.setenv("SLU_TRISOLVE", "merged")
    a, opts, _ = _staged_system("float32")
    d = factorize(a, opts, backend="jax").device_lu
    assert isinstance(d, batched.StagedLU)
    assert trisolve.active_arm() == "merged"
    monkeypatch.setenv("SLU_TRISOLVE", "legacy")
    assert trisolve.active_arm() == "legacy"


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
