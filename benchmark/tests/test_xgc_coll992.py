"""The cell `xgc_coll992_b2048.bstep` on the CPU: the generator's
facts (n, nnz, the nine-point stencil, conservation of density,
second-order consistency with the equations, the condition of a seeded
sample), the data (`reference_b`), the configuration file against
`stokes2d_sinker.json`'s keys, the cell's rehearsal on the full
32 x 31 grid at a batch of 8, traced and untraced, both controls, a
program without the refined batched solve refused, and that the cell
and every reader it lists are declared by name."""

import json
import os

import numpy as np
import pytest
import scipy.sparse as sp

import harness
import reference_b
from conftest import ROOT
from test_correct import drive, rehearsal_run
from test_rehearsal import command

CELL = "xgc_coll992_b2048.bstep"
CONFIG = "xgc_coll992_b2048"
NEW = ("batch_factor_roofline", "sweep_member_parallel_share.bstep",
       "stage_s.bstep", "member_us.bstep")
LISTED = ("plan_s", "compile_s", "window_compiles.step", "step_median_s",
          "factor_s", "solve_s.step", "refine_steps.step",
          "residual_s.step", "sweep_device_s.step",
          "idle_attributed.step", "factor_named_share", "extend_add_s",
          "ea_row_share", "pack_s.step")


def gen():
    return harness.load_module("gen_coll2d", "configs", "gen_coll2d.py")


def config():
    return harness.load_cell(CELL)["config"]


# -- the generator ----------------------------------------------------

def test_the_generators_shapes_are_the_sources():
    g = gen()
    a = g.generate()
    assert a.shape == (992, 992) and a.nnz == 8554
    assert a.nnz == (3 * 32 - 2) * (3 * 31 - 2)
    assert a.dtype == np.float64 and a.has_sorted_indices
    grid = g.grid()
    assert (grid["npar"], grid["nperp"], grid["n"]) == (32, 31, 992)
    # nine points, cut at the walls: 4 in a corner cell, 6 on an
    # edge, 9 inside; v_par fastest, so the offsets are -33..33
    per_row = np.diff(a.indptr).reshape(31, 32)
    assert (per_row[1:-1, 1:-1] == 9).all()
    assert (per_row[0, 1:-1] == 6).all() and (per_row[1:-1, 0] == 6).all()
    assert per_row[0, 0] == per_row[-1, -1] == per_row[0, -1] == 4
    coo = a.tocoo()
    assert set(np.unique(coo.col - coo.row)) == {
        -33, -32, -31, -1, 0, 1, 31, 32, 33}
    # a symmetric pattern, nonsymmetric values, every entry in use
    assert (abs(a) > 0).nnz == a.nnz
    assert ((a != 0) != (a != 0).T).nnz == 0
    assert abs(a - a.T).max() > 1.0
    # the corner entries are the off-diagonal part of D: gone with it
    g.Z_EFF, kept = 0.0, g.Z_EFF
    try:
        bare = g.matrix(grid, g.collision_values(grid, [1.0], [0.2])[0])
    finally:
        g.Z_EFF = kept
    c = bare.tocoo()
    corner = np.isin(c.col - c.row, (-33, -31, 31, 33))
    assert abs(c.data[corner]).max() == 0.0
    full = g.matrix(grid, g.collision_values(grid, [1.0], [0.2])[0])
    c = full.tocoo()
    inner = (np.isin(c.col - c.row, (-33, -31, 31, 33))
             & (c.row // 32 > 0) & (c.row // 32 < 30))
    assert abs(c.data[inner]).min() > 0.0


def test_density_is_conserved():
    """The cell volumes (v_perp) are in the null space of C': the
    fluxes telescope and the walls carry none."""
    g = gen()
    grid = g.grid()
    vals = g.collision_values(grid, [0.7, 1.0, 1.5], [-0.5, 0.0, 0.4])
    for v in vals:
        c = g.matrix(grid, v)
        assert abs(grid["volume"] @ c).max() < 1e-11 * abs(c).max()
    # and A = I - dtnu C keeps the density of any f
    a = g.matrix(grid, g.values(grid, [1.3], [0.9], [0.1], [2.0])[0])
    f = np.random.default_rng(0).random(992)
    assert grid["volume"] @ (a @ f) == pytest.approx(grid["volume"] @ f,
                                                     rel=1e-12)


def test_the_generator_is_the_equations():
    """Second-order consistency, away from the walls.  (1) A smooth
    field under the isotropic part (Z = 0, T = 1, u = 0):
    C f = lap f + div(v f) in cylindrical coordinates.  (2) The
    Maxwellian of temperature T about u under the whole operator: the
    continuum's C f_M = 0, so what is left is the truncation error,
    cross terms and all.  Both fall by four when h halves."""
    g = gen()
    aa, bb = 0.7, 0.9
    smooth, maxwell = [], []
    for k in (1, 2, 4):
        grid = g.grid(32 * k, 31 * k)
        vp = np.tile(grid["vpar"], grid["nperp"])
        vq = grid["volume"]
        inner = (slice(2 * k, -2 * k),) * 2
        g.Z_EFF, kept = 0.0, g.Z_EFF
        try:
            c = g.matrix(grid, g.collision_values(grid, [1.0], [0.0])[0])
        finally:
            g.Z_EFF = kept
        s, co = np.sin(aa * vp), np.cos(bb * vq)
        f = s * co
        exact = (-aa ** 2 * f + s * (-bb * np.sin(bb * vq) / vq
                                     - bb ** 2 * co)
                 + f + vp * aa * np.cos(aa * vp) * co
                 + 2 * f - vq * s * bb * np.sin(bb * vq))
        r = (c @ f - exact).reshape(grid["nperp"], grid["npar"])
        smooth.append(abs(r[inner]).max())
        T, u = 0.8, 0.3
        c = g.matrix(grid, g.collision_values(grid, [T], [u])[0])
        fm = np.exp(-((vp - u) ** 2 + vq ** 2) / (2 * T))
        r = (c @ fm).reshape(grid["nperp"], grid["npar"])
        maxwell.append(abs(r[inner]).max())
    for e in (smooth, maxwell):
        assert e[0] < 0.02
        assert 3.6 < e[0] / e[1] < 4.4 and 3.6 < e[1] / e[2] < 4.4


def test_the_members_are_well_enough_conditioned():
    """cond_1 <= 1e6 over a seeded sample of both populations at the
    configuration's ranges, so that float32 factors under float64
    refinement are the right rung."""
    g = gen()
    grid = g.grid()
    model = config()["model"]
    p = reference_b.member_params(model, 2147483659, 64)
    assert np.allclose(p["dtnu0"][32:] / p["dtnu0"][:32],
                       model["mass_ratio"] ** 0.5)
    assert p["dtnu0"][0] == model["dtnu0_ion"]
    for key in ("density", "temperature", "flow"):
        lo, hi = model[key]
        assert lo <= p[key].min() and p[key].max() <= hi
    vals = g.values(grid, p["density"], p["temperature"], p["flow"],
                    p["dtnu0"])
    conds = np.array([np.linalg.cond(g.matrix(grid, v).toarray(), 1)
                      for v in vals[::4]])
    assert conds.max() <= 1e6
    assert conds[:8].max() < conds[8:].min()    # ion-like, electron-like


# -- the data ---------------------------------------------------------

def test_value_sets_and_systems_come_from_the_seed():
    g = gen()
    grid = g.grid()
    model = config()["model"]
    a = reference_b.value_sets(g, grid, model, 2147483659, 6, 3)
    b = reference_b.value_sets(g, grid, model, 2147483659, 6, 3)
    c = reference_b.value_sets(g, grid, model, 2147483660, 6, 3)
    assert len(a) == 3 and a[0].shape == (6, 8554)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    # a ring entry is the next Picard iterate: every member moves, by
    # a few per cent
    step = np.abs(a[1] - a[0]).max(axis=1) / np.abs(a[0]).max(axis=1)
    assert (step > 0).all() and step.max() < 0.3
    block = reference_b.BlockDiagonal(grid["indptr"], grid["indices"],
                                      992, 6)
    systems = reference_b.systems(block, a, 2147483659)
    xtrue, rhs = systems[1]
    assert xtrue.shape == rhs.shape == (6, 992)
    for m in (0, 5):
        assert np.allclose(g.matrix(grid, a[1][m]) @ xtrue[m], rhs[m],
                           rtol=0, atol=1e-12 * abs(rhs[m]).max())
    # the banded solve is the member's own
    x = reference_b.banded_solve(grid["indptr"], grid["indices"],
                                 a[1][3], rhs[3], 33)
    assert np.linalg.norm(x - xtrue[3]) < 1e-10 * np.linalg.norm(xtrue[3])


def test_one_member_of_one_step_outside_a_limit_fails_the_run():
    g = gen()
    grid = g.grid()
    cfg = config()
    sets = reference_b.value_sets(g, grid, cfg["model"], 7, 4, 2)
    block = reference_b.BlockDiagonal(grid["indptr"], grid["indices"],
                                      992, 4)
    systems = reference_b.systems(block, sets, 7)
    chk = reference_b.Checker(block, grid, sets, cfg["guarantees"], 7, 33)
    def answers():
        return [[j, systems[j][1], systems[j][0], systems[j][0].copy()]
                for j in (0, 1, 0)]

    v = chk.judge(answers())
    assert (v["attempted"], v["failed"], v["members_failed"]) == (3, 0, 0)
    assert v["splu_compared"] == 1
    assert [c["name"] for c in v["compared"]] == [
        "berr_max", "relerr_max", "vs_banded_max"]
    bad = answers()
    bad[1][3][2, 17] *= 1.0 + 1e-6          # one entry of one member
    v = chk.judge(bad)
    assert (v["failed"], v["members_failed"]) == (1, 1)
    nan = answers()
    nan[2][3][0, 0] = np.nan
    v = chk.judge(nan + [(1, None, None, None)])
    assert v["failed"] == 2 and v["attempted"] == 4
    assert all(np.isfinite(c["value"]) for c in v["compared"])
    # float32 answers are not the configuration's
    v = chk.judge([(0, systems[0][1], systems[0][0],
                    systems[0][0].astype(np.float32))])
    assert v["failed"] == 1


# -- the configuration ------------------------------------------------

def test_the_configuration_is_the_deployments():
    cfg = config()
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "stokes2d_sinker.json")) as f:
        stokes = json.load(f)
    # stokes2d_sinker's keys, with the batch's own beside them and
    # the one-matrix drift gone
    assert set(cfg) - set(stokes) == {"batch", "rehearsal_batch",
                                      "model", "dispatch"}
    assert set(stokes) - set(cfg) == {"value_drift"}
    for key in ("options", "precision", "controls", "grid"):
        assert cfg[key] == stokes[key]
    for key in ("equil", "row_perm", "replace_tiny_pivot"):
        assert cfg["gesp"][key] == stokes["gesp"][key]
    g = dict(stokes["guarantees"], vs_banded_max=1e-9)
    del g["vs_splu_max"]
    assert {k: v for k, v in cfg["guarantees"].items() if k != "what"} \
        == {k: v for k, v in g.items() if k != "what"}
    assert cfg["n"] == 992 and cfg["batch"] in (2048, 1024)
    assert cfg["rehearsal_batch"] == 8
    assert cfg["rehearsal_matrix_args"] == {}    # the published widths
    args = cfg["matrix"]["args"]
    assert (args["npar"], args["nperp"]) == (32, 31)
    assert cfg["matrix"]["generator"] == "coll2d"
    assert cfg["reduced"] == ["batch"]
    assert set(cfg["reduced_why"]) == {"batch"}
    assert len(cfg["assumed"]) >= 6 and len(cfg["source"]) <= 200
    assert cfg["shapes"]["nrhs"] == 1
    a = rehearsal_run(CELL).matrix()
    assert a.shape == (992, 992) and a.nnz == 8554


# -- the cell ---------------------------------------------------------

@pytest.mark.parametrize("trace,expects", [
    ("0", {"step_s", "setup_s"}),
    ("1", {"factor_s", "solve_s.step", "plan_s", "compile_s",
           "window_compiles.step", "refine_steps.step",
           "step_median_s"}),
])
def test_rehearsal(trace, expects):
    r = command(CELL, "--trace", trace, "--rehearse-cpu")
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and "metrics" not in line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # of the metrics the cell lists, those a CPU run can read: the
    # others need the chip's trace, or the program's counters
    assert set(line["metric_names"]) == expects
    listed = {m["name"] for m in harness.load_cell(CELL)["per_layer"]}
    assert expects - {"step_s", "setup_s"} <= listed


def test_sound_run_is_correct_on_every_member():
    run = rehearsal_run(CELL)
    line = drive(run)
    assert line["correct"] is True and line["attempted"] > 0
    worst = {c["name"]: c["value"] for c in line["compared"]}
    assert worst["berr_max"] < 4 * np.finfo(np.float64).eps
    assert worst["relerr_max"] < 1e-11
    assert 0 < worst["vs_banded_max"] < 1e-11
    assert run.notes["members"] == 8 and run.notes["members_failed"] == 0
    assert 2 <= max(run.readings["refine_steps"]) <= 4
    # the plan is the batch's: 47 fronts on the 32 x 31 grid
    assert len(run.readings["fronts"]["w"]) == 47
    assert run.readings["fronts"]["nnz"] == 8554
    # every step refactors: one factorization a step in the ring
    snap = run.slu.obs.HEALTH.snapshot()
    assert snap["last_factor"]["batch_members"] == 8
    assert snap["last_factor"]["dispatch"] == "batch"


@pytest.mark.parametrize("control", ["refine_float32", "no_refine"])
def test_control_is_not_correct(control):
    run = rehearsal_run(CELL, control)
    line = drive(run)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    assert run.notes["members_failed"] == 8 * line["attempted"]
    worst = {c["name"]: c for c in line["compared"]}
    assert worst["berr_max"]["value"] > worst["berr_max"]["limit"]


def test_a_program_without_the_refined_batched_solve_is_refused(
        monkeypatch):
    """The parent of the PR that brought the kind: no
    `batch_factorize` at the package root, or one that takes no
    options.  Refused before any set-up (no matrix, no plan)."""
    kind = harness.load_module("kind_bstep", "kinds", "bstep.py")
    run = rehearsal_run(CELL)
    monkeypatch.setattr(run, "matrix", lambda: pytest.fail("set-up ran"))
    monkeypatch.setattr(run.slu, "batch_factorize",
                        lambda plan, values, dtype=None: None)
    with pytest.raises(harness.Refused, match="refined batched solve"):
        kind.setup(run)
    monkeypatch.delattr(run.slu, "batch_factorize")
    with pytest.raises(harness.Refused, match=CONFIG):
        kind.setup(run)


def test_the_cell_is_declared_and_every_reader_is_there():
    """By name, never by place: a later PR appends to these lists.
    The four readers this configuration brought are files the harness
    finds; their entries in `per_layer` wait for a `benchmark` PR
    (PERF.md section 7), so nothing here says where they stand."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "bstep", 1)
    entry = {c["name"]: c for c in b["configs"]}[CONFIG]
    assert entry["file"] == "benchmark/configs/" + CONFIG + ".json"
    assert entry["reduced"] == ["batch"] and len(entry["source"]) <= 200
    with open(os.path.join(ROOT, entry["file"])) as f:
        assert json.load(f)["source"] == entry["source"]
    spec = harness.load_cell(CELL)
    assert {m["name"] for m in spec["end_to_end"]} == {"step_s",
                                                       "setup_s"}
    assert {m["name"] for m in spec["per_layer"]} == set(LISTED)
    for m in spec["per_layer"]:
        assert m["moves"] in ("step_s", "setup_s")
        assert hasattr(harness.metric_reader(m["name"]), "read")
    for name in NEW:
        assert hasattr(harness.metric_reader(name), "read")
    tr = spec["traffic"]
    assert (tr["kind"], tr["ring"], tr["warmup_steps"],
            tr["trace_steps"]) == ("bstep", 4, 2, 1)
    # nine cells, two of them on four chips
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 2
