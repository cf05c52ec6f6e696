"""The cell `stokes2d_sinker.step` on the CPU: the generator's facts
(the staggered grid, DMStag's numbering, the stored pattern, the zero
pressure block, the identity rows, second-order consistency with the
equations), its rehearsal at N = 12, traced and untraced, its two
controls, that it is declared by name, and the three readers this
configuration brought (`gesp_rows_moved_share`, `scale_s.step`,
`tiny_pivots.step`) on hand-made runs and through the program; and
the tool `tools/step_levels.py`, rehearsed."""

import copy
import json
import os
import types

import numpy as np
import pytest

import harness
import progspans
from conftest import ROOT
from test_correct import drive, rehearsal_run
from test_progspans import HAND_MADE, MAIN, US, _read, _run, span
from test_rehearsal import command

CELL = "stokes2d_sinker.step"
NEW = ("gesp_rows_moved_share", "scale_s.step", "tiny_pivots.step")
LISTED = ("plan_s", "compile_s", "window_compiles.step", "step_median_s",
          "factor_s", "solve_s.step", "refine_steps.step",
          "factor_roofline", "pack_s.step", "residual_s.step",
          "sweep_device_s.step", "idle_attributed.step",
          "factor_named_share", "extend_add_s", "ea_row_share")


def gen():
    return harness.load_module("gen_stokes2d", "configs",
                               "gen_stokes2d.py")


# -- the generator ----------------------------------------------------

@pytest.mark.parametrize("N", [6, 12])
def test_the_generators_facts(N):
    g = gen()
    a = g.generate(N)
    n = 2 * N * (N + 1) + N * N
    assert a.shape == (n, n) == (g.size(N),) * 2
    assert a.dtype == np.float64 and a.has_sorted_indices
    iv, iu, ip = g.numbering(N)
    assert iv.shape == (N, N + 1) and iu.shape == (N + 1, N)
    assert sorted(np.concatenate([iv.ravel(), iu.ravel(),
                                  ip.ravel()])) == list(range(n))
    # DMStag's numbering: (bottom face, left face, centre) by cell,
    # the row's last face after its cells, the top faces at the end
    assert (iv[0, 0], iu[0, 0], ip[0, 0]) == (0, 1, 2)
    assert (iv[1, 0], iu[1, 0], ip[1, 0]) == (3, 4, 5)
    assert iu[N, 0] == 3 * N and iv[0, 1] == 3 * N + 1
    assert list(iv[:, N]) == list(range(n - N, n))
    per_row = np.diff(a.indptr)
    # identity rows kept: the wall-normal velocities and the pin
    walls = np.concatenate([iu[0], iu[N], iv[:, 0], iv[:, N],
                            [ip[0, 0]]])
    assert (per_row[walls] == 1).all()
    assert np.allclose(a.diagonal()[walls], 1.0 * N * N)    # Kbound
    # continuity rows: four velocities of +-Kcont/h, no pressure
    cont = ip.ravel()[1:]
    assert (per_row[cont] == 4).all()
    assert np.allclose(np.abs(a[cont].data), 1.0 * N * N)
    assert abs(a[cont].sum(axis=1)).max() == 0
    # the pressure block is exactly zero: N^2 - 1 diagonal entries
    # are not stored
    assert abs(a[cont][:, ip.ravel()]).nnz == 0
    d = a.diagonal()
    stored = np.zeros(n, dtype=bool)
    coo = a.tocoo()
    stored[coo.row[coo.row == coo.col]] = True
    assert np.count_nonzero(~stored) == N * N - 1
    assert (d[cont] == 0).all() and (d[~np.isin(np.arange(n), cont)]
                                     > 0).all()
    # a momentum row away from the walls: 5 of its own velocity, 4 of
    # the other, 2 pressures; one shear term less beside a wall
    inner_u = iu[1:N, 1:N - 1].ravel()
    assert (per_row[inner_u] == 11).all()
    cols = a[iu[2, 2]].indices
    assert np.isin(cols, iu).sum() == 5 and np.isin(cols, iv).sum() == 4
    assert np.isin(cols, ip).sum() == 2
    assert (per_row[iu[1:N, 0]] == 8).all()
    assert (per_row[iv[0, 1:N]] == 8).all()
    assert a.nnz == per_row.sum() == (
        len(walls) + 4 * len(cont)
        + 2 * ((N - 1) * (N - 2) * 11 + (N - 1) * 2 * 8))
    # the viscosity: 100 inside the circle of radius 0.3, 1 outside
    ec, en = g.viscosity(N, 1.0, 100.0, 0.3)
    assert set(np.unique(ec)) == set(np.unique(en)) == {1.0, 100.0}
    assert ec[N // 2, N // 2] == 100.0 and ec[0, 0] == 1.0


def test_the_generator_is_the_equations():
    """Second-order consistency: at constant viscosity the free-slip
    field u = sin(pi x) cos(pi y), v = -cos(pi x) sin(pi y) (no
    divergence, no shear stress anywhere) with p = cos(pi x) cos(pi y)
    leaves a residual against -lap(u) + grad(p) that falls by four
    when h halves, and none in the continuity rows."""
    g = gen()
    worst = []
    for N in (8, 16, 32):
        a = g.generate(N, 1.0, 1.0)
        h = 1.0 / N
        iv, iu, ip = g.numbering(N)
        k, c = np.arange(N + 1) * h, (np.arange(N) + 0.5) * h
        su, cu = np.sin(np.pi * k)[:, None], np.cos(np.pi * c)[None, :]
        cv, sv = np.cos(np.pi * c)[:, None], np.sin(np.pi * k)[None, :]
        u, v = su * cu, -cv * sv
        p = np.cos(np.pi * c)[:, None] * np.cos(np.pi * c)[None, :]
        x = np.zeros(a.shape[0])
        x[iu], x[iv] = u, v
        x[ip] = (p - p[0, 0]) / (1.0 / h)       # the unknown is p/Kcont
        r = a @ x
        fx = 2 * np.pi ** 2 * u - np.pi * su * cu
        fy = 2 * np.pi ** 2 * v - np.pi * cv * sv
        worst.append(max(abs(r[iu[1:N]] - fx[1:N]).max(),
                         abs(r[iv[:, 1:N]] - fy[:, 1:N]).max()))
        assert abs(r[ip]).max() < 1e-9 * N * N
    assert worst[0] < 0.3
    assert 3.5 < worst[0] / worst[1] < 4.5 and 3.5 < worst[1] / worst[2] < 4.5


def test_the_matrix_is_the_configurations():
    cfg = harness.load_cell(CELL)["config"]
    args = cfg["matrix"]["args"]
    N = args["N"]
    assert N in (128, 160, 192)
    assert cfg["n"] == 2 * N * (N + 1) + N * N
    assert (args["eta1"], args["eta2"], args["radius"]) == (1.0, 100.0,
                                                            0.3)
    assert cfg["reduced"] == ["n"] and cfg["grid"] is None
    assert set(cfg["reduced_why"]) == {"n"}
    assert cfg["options"] == {"factor_dtype": "float32",
                              "refine_dtype": "float64",
                              "iter_refine": "SLU_DOUBLE"}
    # the GESP block names the library's defaults; it sets nothing
    base = rehearsal_run(CELL).options()
    gesp = cfg["gesp"]
    assert base.equil.name == gesp["equil"] == "YES"
    assert base.row_perm.name == gesp["row_perm"] == "LARGE_DIAG_MC64"
    assert base.replace_tiny_pivot.name == gesp["replace_tiny_pivot"] \
        == "YES"
    assert cfg["guarantees"]["berr_max_in_eps_float64"] == 64
    assert cfg["guarantees"]["relerr_max"] == 1e-9
    assert cfg["guarantees"]["vs_splu_max"] == 1e-9
    assert set(cfg["controls"]) == {"refine_float32", "no_refine"}
    assert cfg["rehearsal_matrix_args"] == {"N": 12}
    assert len(cfg["assumed"]) >= 8
    a = rehearsal_run(CELL).matrix()
    assert a.shape == (2 * 12 * 13 + 144,) * 2


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
    # others need the chip's trace, or the cell's own fronts
    assert set(line["metric_names"]) == expects
    listed = {m["name"] for m in harness.load_cell(CELL)["per_layer"]}
    assert expects - {"step_s", "setup_s"} <= listed


def test_sound_run_is_correct_and_the_counters_say_why():
    run = rehearsal_run(CELL)
    line = drive(run)
    assert line["correct"] is True and line["attempted"] > 0
    worst = {c["name"]: c["value"] for c in line["compared"]}
    assert worst["berr_max"] < 4 * np.finfo(np.float64).eps
    assert worst["relerr_max"] < 1e-11 and worst["vs_splu_max"] < 1e-9
    # the plan's static pivoting, through the program's ring
    moved = harness.metric_reader("gesp_rows_moved_share")
    n = 2 * 12 * 13 + 144
    assert moved.share(run) == pytest.approx(100 * 2 * 143 / n)
    assert run.notes["gesp"]["equed"] == "B"
    assert run.notes["gesp"]["zero_diagonal"] == 143
    tiny = harness.metric_reader("tiny_pivots.step")
    assert tiny.largest(run) == 0.0
    steps = len(run.readings["refine_steps"])
    assert run.notes["tiny_pivots_factorizations"] == min(steps, 64)
    # a rehearsal reports none of them
    assert moved.read(run) is None and tiny.read(run) is None
    # refinement carries the answer: more passes than a Laplacian's 3
    assert 3 <= max(run.readings["refine_steps"]) <= 5
    # a cell whose permutation is the identity reads 0
    lap = rehearsal_run("lap3d_k30.step")
    lap.slu.factorize(lap.slu.csr_from_scipy(lap.matrix()),
                      lap.options())
    assert moved.share(lap) == 0.0 and lap.notes["gesp"]["equed"] == "N"


@pytest.mark.parametrize("control", ["refine_float32", "no_refine"])
def test_control_is_not_correct(control):
    line = drive(rehearsal_run(CELL, control))
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    worst = {c["name"]: c for c in line["compared"]}
    assert worst["berr_max"]["value"] > worst["berr_max"]["limit"]


def test_the_cell_is_declared_and_every_reader_is_there():
    """By name, never by place: a later PR appends to these lists.
    The three readers this configuration brought are files the
    harness finds; their entries in `per_layer` wait for a `benchmark`
    PR (PERF.md section 7), so nothing here says where they stand."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "stokes2d_sinker", "step", 1)
    entry = {c["name"]: c for c in b["configs"]}["stokes2d_sinker"]
    assert entry["file"] == "benchmark/configs/stokes2d_sinker.json"
    assert entry["reduced"] == ["n"] and len(entry["source"]) <= 200
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
    # the traffic is the step cells' own, as it is
    assert spec["traffic"]["kind"] == "step"
    assert spec["traffic"] == harness.load_cell("lap3d_k30.step")["traffic"]


# -- the readers ------------------------------------------------------

def ring(last=None, events=None, steps=0):
    snap = {"last_factor": last, "factor_events": events}
    return types.SimpleNamespace(
        rehearse=False, notes={},
        readings={"refine_steps": [3] * steps},
        slu=types.SimpleNamespace(obs=types.SimpleNamespace(
            HEALTH=types.SimpleNamespace(snapshot=lambda: snap))))


def test_gesp_rows_moved_share_reads_the_programs_ring():
    read = harness.metric_reader("gesp_rows_moved_share").read
    gesp = {"rows_moved": 32766, "n": 49408, "equed": "B",
            "row_scale_min": 1e-7, "row_scale_max": 6e-5,
            "col_scale_min": 1.0, "col_scale_max": 600.0,
            "zero_diagonal": 16383}
    run = ring({"tiny_pivots": 0, "gesp": gesp})
    assert read(run) == pytest.approx(66.317, abs=1e-3)
    assert run.notes["gesp"] == gesp
    ident = dict(gesp, rows_moved=0, equed="N", zero_diagonal=0)
    assert read(ring({"gesp": ident})) == 0.0
    # the parent of the PR that brought the counter: no such key, an
    # empty one, or no ring at all: None, no error, no note
    for last in (None, {}, {"tiny_pivots": 0}, {"gesp": None},
                 {"gesp": {}}):
        run = ring(last)
        assert read(run) is None and not run.notes
    bare = ring()
    bare.slu.obs.HEALTH.snapshot = lambda: {}
    assert read(bare) is None


def test_tiny_pivots_reads_the_windows_factorizations():
    read = harness.metric_reader("tiny_pivots.step").read

    def f(k):
        return {"tiny_pivots": k, "dtype": "float32"}

    run = ring(events=[f(0)] * 5)
    assert read(run) == 0.0
    assert run.notes["tiny_pivots_factorizations"] == 5
    assert read(ring(events=[f(0), f(3), f(1)])) == 3.0
    # only the window's steps count: older records are warm-up's
    assert read(ring(events=[f(7)] * 2 + [f(0)] * 3, steps=3)) == 0.0
    assert read(ring(events=[f(7)] * 2 + [f(0)] * 3, steps=4)) == 7.0
    for events in (None, [], [{"dtype": "float32"}]):
        run = ring(events=events)
        assert read(run) is None and not run.notes
    bare = ring()
    bare.slu.obs.HEALTH.snapshot = lambda: {}
    assert read(bare) is None


def scaled_trace():
    """test_progspans' hand-made trace with the two `slu.fact.scale`
    spans of a step inside `slu.FACT` (10-90 us): the scaling before
    the program starts at 20, and one laid over the device's idle gap
    70-75 us."""
    loaded = copy.deepcopy(HAND_MADE)
    loaded["host"] += [span(MAIN, "slu.fact.scale", 12, 6),
                       span(MAIN, "slu.fact.scale", 71, 3)]
    return loaded


def test_scale_s_reads_the_programs_span():
    run = _run("step", scaled_trace(), steps=2)
    assert _read("scale_s.step", run) == pytest.approx(9 * US / 2)
    # and the idle seconds under it carry its name, not FACT's
    by = dict(progspans.idle_by_span(scaled_trace())["by_span"])
    assert by["slu.fact.scale"] == pytest.approx(3 * US)
    assert by["slu.FACT"] == pytest.approx(2 * US)
    # a program without the span (the parent), or no TPU plane: None
    assert _read("scale_s.step", _run("step", HAND_MADE, steps=2)) is None
    assert _read("scale_s.step", _run("step", None)) is None


# -- the tool that tells a seed's passes from a process's level -------

def test_step_levels_reads_passes_and_walls_by_seed_and_by_core():
    import subprocess
    import sys
    r = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "benchmark", "tools", "step_levels.py"),
         "--workload", CELL, "--seeds", "2", "--turns", "1",
         "--rehearse-cpu"],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(x) for x in r.stdout.splitlines()
             if x.startswith("{")]
    head, seeds = lines[0], [x for x in lines if "seed" in x]
    pins = [x for x in lines if "pinned" in x]
    ring = harness.load_cell(CELL)["traffic"]["ring"]
    assert head["cores"] and head["setup_s"] > 0
    assert len(seeds) == 2 and seeds[0]["seed"] != seeds[1]["seed"]
    for s in seeds:
        assert len(s["passes"]) == ring and min(s["passes"]) >= 1
        assert s["step_median_s"] > s["solve_median_s"] > 0
        assert set(map(int, s["solve_median_s_by_passes"])) \
            == set(s["passes"])
    # one line a core, each run where it was pinned, and one unpinned
    assert [x["pinned"] for x in pins] == head["cores"] + [None]
    assert all(x["cpu"] == x["pinned"] for x in pins[:-1])
