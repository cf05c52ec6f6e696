"""The row lane of the extend-add (`ops/batched._ea_add_rows`): a child
bucket at or over the size test (`_ea_row_lane`) moves its updates by
whole rows, a wave of children of distinct parents a loop turn
(`_ea_waves`), through the inverse position maps `GroupSpec.dev`
ships, where the element lane builds one index and issues one
serialized update per matrix entry.

Pinned here: the two lanes give the same fronts (crafted buckets; the
real schedules of `lap3d` k=6 and `elas3d` ne=3 at float64, on one
device, on the 2x2 CPU mesh with its sharded cooperative fronts, in
pair mode and under `vmap`); a row-lane bucket lowers to no integer
tensor of rc_b·tc_b elements and to no scatter at all; K-padding and
sentinel records add nothing; the slab's tail pad covers the lane's
over-read; the lane counter adds up to the plan's Σ rc²; a wave a
turn gives bit for bit the factors of one child a turn (the form the
lane had until PR 46, kept here as `_one_child_a_turn`), and no wave
holds two records of one parent.  (The
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
from superlu_dist_tpu.utils.testmat import (helmholtz_2d, laplacian_3d,
                                            random_unsymmetric)

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
        off += st * st          # a slot of the children's group's slab
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
    return jnp.asarray(upd), ref, elem, _row_form(
        [(int(so[i]), st, int(db[i]), pr[i], pc[i])
         for i in range(n_real)], {st: (3, n_real * st * st)}, rc_b,
        tc_b, mb, ncols, n_pad)


def _row_form(recs, slabs, rc_b, tc_b, mb, ncols, n_pad):
    """Records (so, st, base, pos_row, pos_col) in front order, the
    children of stride st slots of (st, st) in the slab `slabs[st]` =
    (offset, size) -> the row lane's blocks and meta as
    `build_schedule` and `GroupSpec.dev` make them: waves
    (`_ea_waves`), slots, inverse maps, front indices."""
    order = sorted(range(len(recs)), key=lambda i: recs[i][2])
    lay, waves = batched._ea_waves(
        [[(0, recs[i][0], recs[i][1], recs[i][2], i, 0, 0,
           slabs[recs[i][1]] + (recs[i][1],)) for i in order]], mb,
        ncols)
    K = len(lay[0])
    slot, stv, fr = (np.zeros(K, np.int64) for _ in range(3))
    pr = np.full((K, rc_b), mb, np.int64)
    pc = np.full((K, tc_b), ncols, np.int64)
    npad = 0
    for k, rec in enumerate(lay[0]):
        if rec is None:
            fr[k] = n_pad + npad
            npad += 1
            continue
        o, s, base, r, c = recs[rec[4]]
        # rec[7]: the part of the slab its loop reads (`_ea_waves`)
        slot[k], stv[k] = (o - rec[7][0]) // (s * s), s
        fr[k] = base // (mb * ncols)
        pr[k], pc[k] = r, c
    return (tuple(jnp.asarray(x, jnp.int32) for x in (
        slot, stv, fr, _inverse_positions(pr, mb, rc_b),
        _inverse_positions(pc, ncols, tc_b))),
        (rc_b, tc_b, K, 0, waves))


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
    # a write-back a loop, a loop a run of waves (a turn of one child
    # writes with a dynamic_update_slice): the four children over two
    # fronts are no four turns
    runs = batched._ea_wave_runs(row[1][4])
    assert len(scatters) == sum(1 for Wc, _, _ in runs if Wc > 1) > 0
    assert txt.count("dynamic_update_slice") == len(runs) - len(scatters)
    assert sum(t for _, t, _ in runs) < kw["K"]


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
def test_row_reads_stay_inside_their_slab(monkeypatch, mat, ndev):
    """A wave is read as slots of its source, nslots blocks of
    (rbc, stride) of a child group's slab from `voff`: the source lies
    inside the update buffer with no tail pad of the row lane's (until
    PR 46 a child was read as rc_b·stride elements from its offset,
    past its own rows, and the pad covered that), every record of the
    wave names a slot of it, and a loop's source is what its records
    span over the devices and no more."""
    _lanes(monkeypatch, ALL_ROWS)
    plan = plan_factorization(_MATS[mat](), slu.Options())
    sched = batched.build_schedule(plan, ndev)
    seen = 0
    for g in sched.groups:
        for (_, _, _, C, *row), (so, st, _, pr, _) in zip(g.ea_meta,
                                                          g.ea_hosts):
            assert C == 0
            k = 0
            for W, _, (voff, nslots, rbc, stride) in row[0]:
                assert voff + nslots * rbc * stride <= sched.upd_total
                assert (0 <= so[:, k:k + W]).all()
                assert (so[:, k:k + W] < nslots).all()
                assert (st[:, k:k + W] % stride == 0).all()
                k += W
                seen += 1
            real = (pr < g.mb).any(-1)
            k = 0
            for Wc, turns, (_, nslots, _, _) in batched._ea_wave_runs(
                    row[0]):
                used = so[:, k:k + Wc * turns][real[:, k:k + Wc * turns]]
                assert used.min() == 0 and used.max() == nslots - 1
                k += Wc * turns
    assert seen
    # the block lane alone sizes the pad now
    assert sched.upd_pad == 1 + max(
        [0] + [st for g in sched.groups for (_, _, st, _) in g.eb_meta])


def test_child_last_in_the_slab_is_read_whole():
    """A child whose block ends the slab, read at a bucket taller and
    wider than its block (rc_b > rbc, tc_b > stride): the block is
    the child's with nothing after it in the buffer."""
    rc, rc_b, st, mb = 5, 8, 5, 16
    rng = np.random.default_rng(9)
    slab = rng.integers(1, 9, 40 + 2 * st * st).astype(np.float64)
    pr = np.full((1, rc_b), mb)
    pr[0, :rc] = np.sort(rng.choice(mb, rc, replace=False))
    inv = _inverse_positions(pr, mb, rc_b)
    blocks = tuple(jnp.asarray(x, jnp.int32) for x in (
        [1], [st], [0], inv, inv))        # the second, last slot
    meta = ((rc_b, rc_b, 1, 0, ((1, 1, (40, 2, st, st)),)),)
    got = np.asarray(_ea_add(jnp.zeros(mb * mb), jnp.asarray(slab),
                             (blocks,), meta, mb=mb,
                             n_pad=1)).reshape(mb, mb)
    ref = np.zeros((mb, mb))
    ref[np.ix_(pr[0, :rc], pr[0, :rc])] = \
        slab[40 + st * st:].reshape(st, st)[:rc, :rc]
    assert np.array_equal(got, ref)


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


# ---- a wave a turn against one child a turn -------------------------

def _one_child_a_turn(F, upd_buf, slot, fr, inv_r, inv_c, *, rc_b,
                      tc_b, waves, mb, n_pad, ncols):
    """The row lane as it was until PR 46, the reference: one child a
    loop turn, read as rc_b·stride slab elements from its offset (the
    slab padded here as `upd_pad` padded it), in the order the
    schedule had then (fronts ascending, a front's children together
    in their order: the stable sort of the waves' records by front
    restores it; a wave's padding records, a front past the group's,
    come last and add zeros to a clamped slice, as K-padding records
    did)."""
    F2 = F.reshape(n_pad * mb, ncols)
    order = jnp.argsort(fr, stable=True)
    strides = sorted({src[3] for _, _, src in waves})
    per = lambda f: jnp.asarray(np.repeat(
        [f(src) for _, _, src in waves], [W for W, _, _ in waves]),
        jnp.int32)
    st = per(lambda src: src[3])
    so = per(lambda src: src[0]) + slot * per(lambda src: src[2] * src[3])
    upd_buf = jnp.pad(upd_buf, (0, rc_b * strides[-1]))

    def read_at(stride):
        def read(off):
            blk = jax.lax.dynamic_slice(upd_buf, (off,),
                                        (rc_b * stride,))
            blk = blk.reshape(rc_b, stride)[:, :tc_b]
            return jnp.pad(blk, ((0, 0), (0, tc_b - blk.shape[1])))
        return read

    reads = [read_at(s) for s in strides]
    below = jnp.asarray(strides[:-1], st.dtype)

    def add_one(k, F2):
        i = order[k]
        blk = (reads[0](so[i]) if len(reads) == 1 else jax.lax.switch(
            jnp.sum(st[i] > below), reads, so[i]))
        tall = jnp.concatenate([blk, jnp.zeros((1, tc_b), blk.dtype)]) \
            .at[inv_r[i]].get(mode="promise_in_bounds")
        wide = jnp.concatenate([tall.T, jnp.zeros((1, mb), blk.dtype)]) \
            .at[inv_c[i]].get(mode="promise_in_bounds")
        row0 = (fr[i] * mb).astype(jnp.int32)
        z = jnp.zeros((), jnp.int32)
        cur = jax.lax.dynamic_slice(F2, (row0, z), (mb, ncols))
        return jax.lax.dynamic_update_slice(F2, cur + wide.T, (row0, z))

    return jax.lax.fori_loop(0, so.shape[0], add_one, F2).reshape(-1)


def _float_bucket(rng, *, n_pad, mb, rc_b, strides, children,
                  ncols=None, tc_b=None):
    """A bucket whose updates are random floats, so that the order of
    a front's addends shows in the last bit.  `children[f]`: how many
    children front f has.  Returns (slab, records in front order,
    each stride's (offset, size) in the slab)."""
    square = ncols is None
    ncols, tc_b = ncols or mb, tc_b or rc_b
    recs = []
    for f, n in enumerate(children):
        for c in range(n):
            st = strides[(f + c) % len(strides)]
            rc = int(rng.integers(1, min(rc_b, st) + 1))
            pr = np.full(rc_b, mb)
            pr[:rc] = np.sort(rng.choice(mb, rc, replace=False))
            if square:
                pc = pr
            else:
                tc = int(rng.integers(1, min(tc_b, st) + 1))
                pc = np.full(tc_b, ncols)
                pc[:tc] = rng.permutation(ncols)[:tc]
            recs.append([None, st, f * mb * ncols, pr, pc])
    # the children of one stride are the slots of one group's slab
    slabs, off = {}, 2
    for st in strides:
        mine = [r for r in recs if r[1] == st]
        slabs[st] = (off, len(mine) * st * st)
        for r in mine:
            r[0], off = off, off + st * st
    slab = rng.standard_normal(off)
    return jnp.asarray(slab), [tuple(r) for r in recs], slabs


_WAVES = {
    # (a) parents of three and four children in one bucket
    "four_children": dict(n_pad=6, mb=24, rc_b=12, strides=(16,),
                          children=(4, 1, 0, 3, 2, 1)),
    # (b) one-child parents, a wave wider than a turn may move
    "over_the_budget": dict(n_pad=48, mb=16, rc_b=8, strides=(8,),
                            children=(1,) * 40 + (0,) * 8,
                            entries=8 * 16 * 16),
    # (c) two slab strides in one bucket, one narrower than it
    "two_strides": dict(n_pad=5, mb=24, rc_b=12, strides=(9, 16),
                        children=(2, 2, 1, 0, 3)),
    # a sharded cooperative front's owned slots (pc != pr, ncols < mb)
    "owned_slots": dict(n_pad=4, mb=24, rc_b=12, strides=(12,),
                        children=(3, 1, 2, 2), ncols=10, tc_b=8),
}


@pytest.mark.parametrize("planes", [1, 2])
@pytest.mark.parametrize("case", list(_WAVES))
def test_a_wave_a_turn_is_bitwise_one_child_a_turn(monkeypatch, case,
                                                   planes):
    """Every front entry takes the same addends in the same order;
    `planes` = 2 is pair storage's `vmap` over shared records."""
    kw = dict(_WAVES[case])
    entries = kw.pop("entries", None)
    if entries:
        monkeypatch.setattr(batched, "_EA_WAVE_ENTRIES", entries)
    slab, recs, slabs = _float_bucket(np.random.default_rng(11), **kw)
    mb, n_pad = kw["mb"], kw["n_pad"]
    ncols, rc_b = kw.get("ncols") or mb, kw["rc_b"]
    blocks, meta = _row_form(recs, slabs, rc_b, kw.get("tc_b") or rc_b,
                             mb, ncols, n_pad)
    waves = meta[4]
    assert len(waves) >= max(kw["children"])
    assert {src[3] for _, _, src in waves} == set(kw["strides"])
    if entries:
        assert any(Wc < W for W, Wc, _ in waves)
    F0 = jnp.asarray(np.random.default_rng(12).standard_normal(
        n_pad * mb * ncols))
    U = slab
    if planes == 2:
        F0, U = jnp.stack([F0, -F0]), jnp.stack([slab, 3.0 * slab])

    def run():
        f = lambda F, u: _ea_add(F, u, (blocks,), (meta,), mb=mb,
                                 n_pad=n_pad, ncols=ncols)
        return np.asarray(jax.jit(jax.vmap(f) if planes == 2 else f)(
            F0, U))

    got = run()
    monkeypatch.setattr(batched, "_ea_add_rows", _one_child_a_turn)
    want = run()
    assert np.array_equal(got, want)
    # and the sum is the right one
    ref = np.asarray(F0).reshape(planes, n_pad, mb, ncols).copy()
    for p in range(planes):
        u = np.asarray(U).reshape(planes, -1)[p]
        for (o, st, base, pr, pc) in recs:
            rows, cols = pr[pr < mb], pc[pc < ncols]
            blk = u[o:o + st * st].reshape(st, st)[:len(rows)]
            # a child's columns lie in slab order; its positions say
            # where each goes
            ref[p, base // (mb * ncols)][np.ix_(rows, cols)] += \
                blk[:, :len(cols)]
    assert np.allclose(got.reshape(ref.shape), ref, rtol=1e-12,
                       atol=1e-12)


def _flats_with(monkeypatch, impl, a, **kw):
    """Factor flats, every bucket on the row lane, with
    `_ea_add_rows` as given: a plan of its own a call, so a trace of
    its own, and no exported-program store, whose key cannot tell the
    two forms apart (`test_the_reference_is_the_one_traced` below)."""
    from superlu_dist_tpu.resilience import aot
    monkeypatch.setattr(aot, "aot_dir", lambda: None)
    if impl is not None:
        monkeypatch.setattr(batched, "_ea_add_rows", impl)
    return _factor_flats(monkeypatch, a, ALL_ROWS, **kw)[0]


_REAL = {
    "lap3d_k6": dict(mat=_MATS["lap3d_k6"], waves=True),
    "elas3d_ne3": dict(mat=_MATS["elas3d_ne3"]),
    "lap3d_k10": dict(mat=lambda: laplacian_3d(10), waves=True),
    # (d) four devices, unequal records a device, the sharded
    # cooperative fronts' owned columns
    "lap3d_k8_mesh": dict(mat=lambda: laplacian_3d(8), mesh=True,
                          unequal=True),
    "elas3d_ne3_mesh": dict(mat=_MATS["elas3d_ne3"], mesh=True),
    # (e) pair storage: the two planes under vmap
    "helm2d_n32_pair": dict(mat=lambda: helmholtz_2d(32), pair=True,
                            waves=True),
}


@pytest.mark.parametrize("case", list(_REAL))
def test_factors_are_bitwise_those_of_one_child_a_turn(monkeypatch,
                                                       case):
    c = _REAL[case]
    mesh = c.get("mesh", False)
    if mesh:
        if len(jax.devices()) < 4:
            pytest.skip("needs >= 4 virtual devices")
        monkeypatch.setenv("SLU_COOP_MB", "32")
    if c.get("pair"):
        monkeypatch.setenv("SLU_COMPLEX_PAIR", "1")
    a = c["mat"]()

    def run(impl):
        kw = (dict(backend="dist", grid=slu.make_solver_mesh(2, 2, 1))
              if mesh else dict(backend="jax"))
        return _flats_with(monkeypatch, impl, a, **kw)

    waves = run(None)
    one = run(_one_child_a_turn)
    assert waves[0].ndim == (2 if c.get("pair") else 1)
    for x, y in zip(waves, one):
        assert np.isfinite(x).all() and np.array_equal(x, y)
    sched = batched.build_schedule(
        plan_factorization(a, slu.Options()), 4 if mesh else 1)
    row = sched.ea_elements["row"]
    assert row["turns"] > 0
    if c.get("waves"):
        assert row["turns"] < row["children"]
    if mesh:
        assert any(g.cp > 0 and any(m[3] == 0 for m in g.ea_meta)
                   for g in sched.groups)
    if c.get("unequal"):
        assert len({sum(int((pr[d] < g.mb).any(-1).sum())
                        for g in sched.groups
                        for *_, pr, _ in g.ea_hosts)
                    for d in range(4)}) > 1


@pytest.mark.parametrize("mesh", [False, True])
def test_the_reference_is_the_one_traced(monkeypatch, mesh):
    """The comparison above compares two programs: a reference that
    adds nothing gives other factors."""
    if mesh and len(jax.devices()) < 4:
        pytest.skip("needs >= 4 virtual devices")
    a = laplacian_3d(6)
    kw = (dict(backend="dist", grid=slu.make_solver_mesh(2, 2, 1))
          if mesh else dict(backend="jax"))
    waves = _flats_with(monkeypatch, None, a, **kw)
    none = _flats_with(monkeypatch, lambda F, *a, **k: F, a, **kw)
    assert not np.array_equal(waves[0], none[0])


# ---- the schedule's waves and the counter ---------------------------

def _scatters_run(jaxpr, times=1):
    """Front write-backs a traced program executes: its `scatter`
    equations (a turn of one child writes its front back with a
    `dynamic_update_slice`), each times the trip counts of the loops
    around it."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("scatter", "dynamic_update_slice"):
            n += times
        for sub in jax.core.jaxprs_in_params(eqn.params):
            inner = times * (eqn.params["length"]
                             if eqn.primitive.name == "scan" else 1)
            n += _scatters_run(sub, inner)
    return n


@pytest.mark.parametrize("ndev", [1, 4])
@pytest.mark.parametrize("mat", list(_MATS))
def test_no_wave_holds_two_records_of_one_parent(monkeypatch, mat,
                                                 ndev):
    """In every wave of every row-lane bucket the fronts ascend
    strictly (padding records past the group's fronts, each its own);
    a wave's width is on the size grid and its chunk divides it; and
    the summary's `children` and `turns` are the plan's children and
    the turns the traced programs run."""
    monkeypatch.setenv("SLU_EA_BLOCK", "0")
    _lanes(monkeypatch, ALL_ROWS)
    plan = plan_factorization(_MATS[mat](), slu.Options())
    sched = batched.build_schedule(plan, ndev)
    turns = 0
    for g in sched.groups:
        ncols = g.cp if g.cp > 0 else g.mb
        for meta, (so, st, db, pr, pc) in zip(g.ea_meta, g.ea_hosts):
            rc_b, tc_b, K, C, waves = meta
            assert C == 0 and K == sum(W for W, _, _ in waves)
            fr = db // (g.mb * ncols)
            real = (pr < g.mb).any(-1)
            k = 0
            for W, Wc, src in waves:
                assert W == batched._next_bucket(W) and W % Wc == 0
                assert (np.diff(fr[:, k:k + W], axis=1) > 0).all()
                assert (real[:, k:k + W] == (fr[:, k:k + W] < g.n_loc)
                        ).all()
                k += W
            blocks = tuple(jnp.asarray(x[0], jnp.int32) for x in (
                so, st, fr, _inverse_positions(pr, g.mb, rc_b),
                _inverse_positions(pc, ncols, tc_b)))
            jaxpr = jax.make_jaxpr(lambda F, u: _ea_add(
                F, u, (blocks,), (meta,), mb=g.mb, n_pad=g.n_loc,
                ncols=ncols))(
                    jnp.zeros(g.n_loc * g.mb * ncols),
                    jnp.zeros(sched.upd_total + sched.upd_pad))
            turns += ndev * _scatters_run(jaxpr.jaxpr)
    row = sched.ea_elements["row"]
    fp = plan.frontal
    assert row["turns"] == turns
    if ndev == 1:
        assert row["children"] == sum(
            1 for c in range(fp.nsuper)
            if fp.r[c] > 0 and fp.sym.part.sparent[c] >= 0)
    assert row["children"] >= turns // ndev


_PLANS = {"lap3d_k10": lambda: laplacian_3d(10),
          "random_300": lambda: random_unsymmetric(300),
          "helm2d_k6": lambda: helmholtz_2d(6)}


@pytest.mark.parametrize("ndev", [1, 2])
@pytest.mark.parametrize("mat", list(_PLANS))
def test_every_front_has_one_slot_of_one_group(mat, ndev):
    """Every supernode of the plan sits in exactly one slot of exactly
    one group of the schedule, in a frame of its own bucket: a front
    the builder dropped is never factored, and one it placed twice is
    extend-added twice."""
    a = _PLANS[mat]()
    plan = plan_factorization(a, slu.Options(factor_dtype=a.dtype.name))
    sched = batched.build_schedule(plan, ndev)
    fp = plan.frontal
    seen = np.zeros(fp.nsuper, dtype=np.int64)
    for g in sched.groups:
        ids = np.asarray(g.sup_ids, dtype=np.int64)
        seen[ids] += 1
        assert len(ids) == g.n_true
        assert (fp.wb[ids] == g.wb).all() and (fp.mb[ids] == g.mb).all()
        slots = np.asarray(g.sup_pos)
        assert len(np.unique(slots)) == len(ids)
        assert ((0 <= slots)
                & (slots < (1 if g.coop else ndev) * g.n_loc)).all()
    assert (seen == 1).all(), np.flatnonzero(seen != 1)
