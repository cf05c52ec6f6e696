"""The row lane of the extend-add (`ops/batched._ea_add_rows`): a child
bucket at or over the size test (`_ea_row_lane`) moves its updates by
whole rows, one child a loop turn, through the inverse position maps
`GroupSpec.dev` ships, where the element lane builds one index and
issues one serialized update per matrix entry.

Pinned here: the two lanes give the same fronts (crafted buckets; the
real schedules of `lap3d` k=6 and `elas3d` ne=3 at float64, on one
device, on the 2x2 CPU mesh with its sharded cooperative fronts, in
pair mode and under `vmap`); a row-lane bucket lowers to no integer
tensor of rc_b·tc_b elements and to no scatter at all; K-padding and
sentinel records add nothing; the slab's tail pad covers the lane's
over-read; the lane counter adds up to the plan's Σ rc².  (The
lane's body is compiled for a described v5e at the benchmark's
largest bucket in tests/test_pack_program.py, beside the one fixture
that describes the chip.)

The lane is forced on and off through the module constant, as the
program has no flag for it."""

import importlib.util
import math
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import superlu_dist_tpu as slu
from superlu_dist_tpu.ops import batched
from superlu_dist_tpu.ops.batched import (_ea_add, _inverse_positions,
                                          get_schedule)
from superlu_dist_tpu.plan.plan import plan_factorization
from superlu_dist_tpu.utils.testmat import helmholtz_2d, laplacian_3d

ALL_ROWS, NO_ROWS = math.inf, 0


def _elas3d(ne):
    """The benchmark's finite-element matrix (3 unknowns a node)."""
    spec = importlib.util.spec_from_file_location(
        "gen_elas3d", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmark", "configs", "gen_elas3d.py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    return slu.csr_from_scipy(gen.generate(ne=ne))


_MATS = {"lap3d_k6": lambda: laplacian_3d(6),
         "elas3d_ne3": lambda: _elas3d(3)}


def _lanes(monkeypatch, gain):
    monkeypatch.setattr(batched, "_EA_ROW_GAIN", gain)


# ---- crafted buckets ------------------------------------------------

def _crafted(rng, *, K, n_real, rc_b, tc_b, st, mb, ncols, n_pad,
             square=True):
    """One bucket's records in both lanes' forms and its numpy oracle.
    Children have integer-valued updates, so sums are exact in any
    order.  `square`: column positions are the row positions (an
    ordinary front); otherwise they are slots of their own, unsorted,
    with absent columns between (a sharded cooperative front)."""
    tcw = min(tc_b, st)
    so = np.zeros(K, np.int64)
    db = np.zeros(K, np.int64)
    pr = np.full((K, rc_b), mb, np.int64)
    pc = pr if square else np.full((K, tc_b), ncols, np.int64)
    off = 3
    recs = []
    for i in range(n_real):
        rc = int(rng.integers(1, min(rc_b, st) + 1))
        rows = np.sort(rng.choice(mb, rc, replace=False))
        if square:
            tc, cols = rc, rows
        else:
            tc = int(rng.integers(1, tcw + 1))
            cols = rng.permutation(ncols + 3)[:tc]   # ≥ ncols: absent
        pr[i, :rc] = rows
        if not square:
            pc[i, :tc] = np.minimum(cols, ncols)
        so[i], db[i] = off, int(rng.integers(n_pad)) * mb * ncols
        recs.append((off, rc, tc, rows, np.minimum(cols, ncols), db[i]))
        off += rc * st
    db[n_real:] = db[n_real - 1] if n_real else 0
    upd = rng.integers(-9, 10, off + rc_b * st).astype(np.float64)
    ref = np.zeros((n_pad * mb, ncols + 1))
    for (o, rc, tc, rows, cols, base) in recs:
        blk = upd[o:o + rc * st].reshape(rc, st)[:, :tc]
        ref[np.ix_(base // ncols + rows, cols)] += blk
    ref = ref[:, :ncols].reshape(-1)
    stv = np.full(K, st, np.int64)
    elem = (tuple(jnp.asarray(x, jnp.int32)
                  for x in (so, stv, db, pr, pc)),
            (rc_b, tc_b, K, K))
    row = (tuple(jnp.asarray(x, jnp.int32) for x in (
        so, stv, db, _inverse_positions(pr, mb, rc_b),
        _inverse_positions(pc, ncols, tc_b))),
        (rc_b, tc_b, K, 0, (st,)))
    return jnp.asarray(upd), ref, elem, row


_CRAFTED = {
    "square": dict(K=4, n_real=4, rc_b=12, tc_b=12, st=16, mb=24,
                   ncols=24, n_pad=3),
    "stride_under_bucket": dict(K=3, n_real=3, rc_b=12, tc_b=12, st=9,
                                mb=32, ncols=32, n_pad=1),
    "k_padding": dict(K=6, n_real=2, rc_b=8, tc_b=8, st=8, mb=16,
                      ncols=16, n_pad=2),
    "all_padding": dict(K=2, n_real=0, rc_b=8, tc_b=8, st=8, mb=16,
                        ncols=16, n_pad=2),
    "one_child": dict(K=1, n_real=1, rc_b=16, tc_b=16, st=20, mb=24,
                      ncols=24, n_pad=1),
    "owned_slots": dict(K=4, n_real=3, rc_b=12, tc_b=8, st=8, mb=24,
                        ncols=10, n_pad=2, square=False),
}


@pytest.mark.parametrize("case", list(_CRAFTED))
def test_crafted_bucket_both_lanes_equal_the_oracle(case):
    """Rows and columns past a child's own (sentinel positions) and
    whole K-padding records add nothing, in either lane."""
    kw = _CRAFTED[case]
    upd, ref, elem, row = _crafted(np.random.default_rng(3), **kw)
    shape = dict(mb=kw["mb"], n_pad=kw["n_pad"], ncols=kw["ncols"])
    F0 = jnp.asarray(np.random.default_rng(4).integers(
        -5, 6, ref.size).astype(np.float64))
    got = {}
    for name, (blocks, meta) in (("element", elem), ("row", row)):
        got[name] = np.asarray(jax.jit(
            lambda F, u, b=blocks, m=meta: _ea_add(F, u, (b,), (m,),
                                                   **shape))(F0, upd))
    assert np.array_equal(got["element"], np.asarray(F0) + ref)
    assert np.array_equal(got["row"], got["element"])


def test_row_lane_under_vmap_equals_the_planes_apart():
    """Pair mode and the batch engine trace `_ea_add` under `vmap`
    with the slab and the fronts batched and the records shared."""
    kw = _CRAFTED["square"]
    upd, _, _, (blocks, meta) = _crafted(np.random.default_rng(5), **kw)
    shape = dict(mb=kw["mb"], n_pad=kw["n_pad"], ncols=kw["ncols"])

    def one(F, u):
        return _ea_add(F, u, (blocks,), (meta,), **shape)

    U = jnp.stack([upd, 2.0 * upd + 1.0])
    F = jnp.zeros((2, kw["n_pad"] * kw["mb"] * kw["ncols"]))
    both = np.asarray(jax.jit(jax.vmap(one))(F, U))
    for p in range(2):
        assert np.array_equal(both[p], np.asarray(one(F[p], U[p])))


def test_row_lane_lowers_to_no_index_per_entry():
    """No integer tensor of rc_b·tc_b elements (or more), and no
    scatter that lacks the uniqueness promise: the lane has none."""
    kw = dict(K=4, n_real=4, rc_b=48, tc_b=48, st=56, mb=96, ncols=96,
              n_pad=2)
    upd, ref, elem, row = _crafted(np.random.default_rng(6), **kw)
    shape = dict(mb=kw["mb"], n_pad=kw["n_pad"], ncols=kw["ncols"])

    def text(blocks, meta):
        return jax.jit(lambda F, u: _ea_add(
            F, u, (blocks,), (meta,), **shape)).lower(
                jnp.zeros(ref.size), upd).as_text()

    def int_tensor_sizes(txt):
        return [math.prod(int(d) for d in m.group(1).split("x") if d)
                for m in re.finditer(r"tensor<((?:\d+x)+)[su]?i\d+>",
                                     txt)]

    per_entry = kw["rc_b"] * kw["tc_b"]
    assert max(int_tensor_sizes(text(*elem))) >= kw["K"] * per_entry
    txt = text(*row)
    assert max(int_tensor_sizes(txt)) < per_entry
    scatters = [ln for ln in txt.splitlines() if "scatter" in ln]
    assert all("unique_indices = true" in ln for ln in scatters)
    assert "dynamic_slice" in txt and "dynamic_update_slice" in txt


# ---- the real schedules ---------------------------------------------

def _factor_flats(monkeypatch, a, gain, **kw):
    """Factor flats on a plan of its own (a schedule's programs are
    keyed by its `ea_meta`, a plan's caches by more than that)."""
    _lanes(monkeypatch, gain)
    opts = slu.Options(**kw.pop("options", {}))
    lu = slu.factorize(a, opts, **kw)
    d = lu.device_lu
    lanes = d.schedule.ea_elements
    return [np.asarray(x) for x in (d.L_flat, d.U_flat, d.Li_flat,
                                    d.Ui_flat)], lanes


@pytest.mark.parametrize("mat", list(_MATS))
def test_schedule_row_lane_equals_element_lane(monkeypatch, mat):
    a = _MATS[mat]()
    rows, lr = _factor_flats(monkeypatch, a, ALL_ROWS, backend="jax")
    elem, le = _factor_flats(monkeypatch, a, NO_ROWS, backend="jax")
    assert lr["element"]["padded"] == 0 < lr["row"]["padded"]
    assert le["row"]["padded"] == 0 < le["element"]["padded"]
    for x, y in zip(rows, elem):
        assert np.isfinite(x).all() and np.array_equal(x, y)


@pytest.mark.parametrize("mat", list(_MATS))
def test_mesh_row_lane_equals_element_lane(monkeypatch, mat):
    """The 2x2 mesh: sharded cooperative fronts take their children's
    columns by owned slot (pc ≠ pr, tc ≠ rc, ncols = cp)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 virtual devices")
    # cooperative fronts from 32 rows up, so that these small trees
    # have some
    monkeypatch.setenv("SLU_COOP_MB", "32")
    a = _MATS[mat]()

    def run(gain):
        return _factor_flats(monkeypatch, a, gain, backend="dist",
                             grid=slu.make_solver_mesh(2, 2, 1))

    rows, lr = run(ALL_ROWS)
    elem, le = run(NO_ROWS)
    assert lr["element"]["padded"] == 0 < lr["row"]["padded"]
    assert le["row"]["padded"] == 0
    plan = plan_factorization(a, slu.Options())
    assert any(g.cp > 0 and g.ea_meta
               for g in get_schedule(plan, 4).groups)
    for x, y in zip(rows, elem):
        assert np.isfinite(x).all() and np.array_equal(x, y)


def test_pair_mode_row_lane_equals_element_lane(monkeypatch):
    monkeypatch.setenv("SLU_COMPLEX_PAIR", "1")
    a = helmholtz_2d(6)
    rows, lr = _factor_flats(monkeypatch, a, ALL_ROWS, backend="jax")
    elem, _ = _factor_flats(monkeypatch, a, NO_ROWS, backend="jax")
    assert lr["row"]["padded"] > 0 and rows[0].shape[0] == 2
    for x, y in zip(rows, elem):
        assert np.isfinite(x).all() and np.array_equal(x, y)


@pytest.mark.parametrize("mat", list(_MATS))
def test_row_lane_solves_to_accuracy(monkeypatch, mat):
    _lanes(monkeypatch, ALL_ROWS)
    a = _MATS[mat]()
    A = a.to_scipy()
    xtrue = np.random.default_rng(8).standard_normal(a.n)
    x, lu, stats = slu.gssvx(slu.Options(), a, A @ xtrue)
    assert np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue) < 1e-10
    assert stats.ea_elements["row"]["real"] > 0
    assert "extend-add elements" in stats.report()


# ---- the slab's tail pad and the counter ----------------------------

@pytest.mark.parametrize("ndev", [1, 4])
@pytest.mark.parametrize("mat", list(_MATS))
def test_upd_pad_covers_the_row_reads(monkeypatch, mat, ndev):
    """A row-lane read is rc_b·st slab elements from `so`, past the
    child's own rc·st: a dynamic_slice that ran over the slab's end
    would be clamped, and silently shifted."""
    _lanes(monkeypatch, ALL_ROWS)
    plan = plan_factorization(_MATS[mat](), slu.Options())
    sched = batched.build_schedule(plan, ndev)
    ends = [int((so + rc_b * np.where(st > 0, st, row[0][0])).max())
            for g in sched.groups
            for (rc_b, _, _, C, *row), (so, st, *_) in zip(g.ea_meta,
                                                           g.ea_hosts)
            if C == 0]
    assert ends and max(ends) <= sched.upd_total + sched.upd_pad


def test_child_last_in_the_slab_is_read_whole():
    """A child whose rows end the slab, read at a bucket taller than
    it is: with the tail pad the block is the child's; one element
    short and dynamic_slice shifts it."""
    rc, rc_b, st, mb = 5, 8, 5, 16
    rng = np.random.default_rng(9)
    slab = rng.integers(1, 9, 40 + rc * st).astype(np.float64)
    so = np.array([40])
    pr = np.full((1, rc_b), mb)
    pr[0, :rc] = np.sort(rng.choice(mb, rc, replace=False))
    inv = _inverse_positions(pr, mb, rc_b)
    blocks = tuple(jnp.asarray(x, jnp.int32) for x in (
        so, [st], [0], inv, inv))

    def run(pad):
        u = jnp.concatenate([jnp.asarray(slab), jnp.zeros(pad)])
        return np.asarray(_ea_add(jnp.zeros(mb * mb), u, (blocks,),
                                  ((rc_b, rc_b, 1, 0, (st,)),), mb=mb,
                                  n_pad=1)).reshape(mb, mb)

    ref = np.zeros((mb, mb))
    ref[np.ix_(pr[0, :rc], pr[0, :rc])] = \
        slab[40:].reshape(rc, st)
    assert np.array_equal(run((rc_b - rc) * st), ref)
    assert not np.array_equal(run((rc_b - rc) * st - 1), ref)


@pytest.mark.parametrize("mat", list(_MATS))
def test_lane_counter_adds_up_to_the_plan(mat):
    """`row + element + block` (real) is the plan's Σ rc², whatever
    the lanes; at the program's own threshold the row lane engages."""
    a = _MATS[mat]()
    plan = plan_factorization(a, slu.Options())
    want = int(sum(int(r) ** 2 for r in plan.frontal.r))
    for ndev in (1, 4):
        lanes = batched.build_schedule(plan, ndev).ea_elements
        assert set(lanes) == {"element", "row", "block"}
        assert sum(v["real"] for v in lanes.values()) == want
        assert all(v["padded"] >= v["real"] for v in lanes.values())
        assert lanes["row"]["real"] > 0
    lu = slu.factorize(a, slu.Options(), plan=plan, backend="jax")
    assert lu.stats.ea_elements == get_schedule(plan, 1).ea_elements
    last = slu.obs.HEALTH.snapshot()["last_factor"]
    assert last["extend_add"] == lu.stats.ea_elements


def test_lane_is_chosen_by_shape_alone():
    f = batched._ea_row_lane
    assert f(3072, 3072, 6144, 6144) and f(256, 256, 384, 384)
    assert not f(8, 8, 128, 128)            # under a loop turn's cost
    assert not f(128, 128, 6144, 6144)      # a sliver of a wide front
    assert f(128, 128, 1024, 1024)
