"""The cell `lap3d_k48.step` on the CPU: that its configuration is
`lap3d_k30`'s at grid 48 and sets no option of the program; that the
plan of the matrix as the cell runs it (k = 48, plan and schedule
only) has more groups than the library's rule allows one program, so
that `staged_enabled` sends it through the staged dispatch with no
variable set; the cell's own arithmetic on that route at a small grid
(f32 factors, f64 residual and answer, the configuration's drift over
a ring of seeded value sets, `factorize(plan=...)` + `solve` against
the host oracle `ops/ref_multifrontal.py` and scipy `splu`, the
answers through `reference.Checker.judge`, both controls failing); its
rehearsal, traced and untraced; and that it is declared by name."""

import json
import os

import numpy as np
import pytest
import scipy.sparse.linalg as spla

import harness
from conftest import ROOT
from reference import Checker, systems, value_sets
from test_correct import drive, rehearsal_run
from test_rehearsal import command

CELL = "lap3d_k48.step"
LISTED = ("plan_s", "compile_s", "window_compiles.step", "step_median_s",
          "factor_s", "solve_s.step", "refine_steps.step",
          "factor_roofline", "pack_s.step", "residual_s.step",
          "sweep_device_s.step", "idle_attributed.step",
          "factor_named_share", "extend_add_s", "ea_row_share")
# the readers the staged route brought: files the harness finds, whose
# entries in `per_layer` wait for a `benchmark` PR (PERF.md section 7)
NEW = ("staged_dispatch_s.step", "staged_wait_s.step",
       "staged_segments.step", "pallas_lu_share", "pallas_lu_roofline")
STAGED_VARS = ("SLU_STAGED", "SLU_STAGED_MIN_GROUPS", "SLU_TPU_PALLAS",
               "SLU_FACTOR_MERGE_CELLS", "SLU_FACTOR_SEG_CELLS")


def gen():
    return harness.load_module("gen_lap3d", "configs", "gen_lap3d.py")


def test_the_configuration_is_lap3d_k30s_at_grid_48():
    cfg = harness.load_cell(CELL)["config"]
    k30 = harness.load_cell("lap3d_k30.step")["config"]
    assert cfg["matrix"] == {"generator": "lap3d", "args": {"k": 48}}
    assert cfg["n"] == 48 ** 3 and cfg["architecture"] is None
    assert cfg["rehearsal_matrix_args"] == {"k": 6}
    # nothing of the numerics is loosened, and nothing is set: no
    # option, flag or variable names the route
    for key in ("value_drift", "options", "precision", "guarantees",
                "controls", "grid"):
        assert cfg[key] == k30[key], key
    assert cfg["options"] == {"factor_dtype": "float32",
                              "refine_dtype": "float64",
                              "iter_refine": "SLU_DOUBLE"}
    assert cfg["reduced"] == ["n"] and set(cfg["reduced_why"]) == {"n"}
    assert len(cfg["assumed"]) >= 2 and "109 groups" in cfg["dispatch"]
    assert not any(v in json.dumps(cfg) for v in STAGED_VARS[:3])
    a = gen().generate(**cfg["matrix"]["args"])
    assert a.shape == (110592, 110592) and a.nnz == 760320
    assert (a.diagonal() == 6.0).all() and a.min() == -1.0


def test_the_librarys_rule_sends_the_cell_through_the_staged_route(
        monkeypatch):
    """Plan and schedule of the matrix at k = 48 (no factorization at
    that size in the suite): more than 96 groups, so `staged_enabled`
    is true by the rule alone, and the route's lists are there."""
    for v in STAGED_VARS:
        monkeypatch.delenv(v, raising=False)
    run = rehearsal_run(CELL)
    from superlu_dist_tpu.ops import batched, pallas_lu, trisolve

    def plan_of(cell):
        """The plan of a cell's matrix as the cell runs it."""
        cfg = harness.load_cell(cell)["config"]
        a = gen().generate(**cfg["matrix"]["args"])
        return run.slu.plan_factorization(run.slu.csr_from_scipy(a),
                                          run.options())

    plan = plan_of(CELL)
    sched = batched.get_schedule(plan, 1)
    assert len(sched.groups) > 96
    assert batched.staged_enabled(sched) is True
    segs = batched.get_factor_segments(sched)
    assert sorted(i for s in segs for i in s) == list(
        range(len(sched.groups)))
    assert 96 < len(segs) <= len(sched.groups)
    ts = trisolve.get_trisolve(sched)
    assert 1 < len(ts.segments) <= len(sched.groups)
    # the buckets the Pallas panel LU would take on a TPU: the leaves
    dt = np.dtype("float32")
    small = [g for g in sched.groups if g.wb <= 8 and g.mb <= 16]
    assert small and all(pallas_lu.usable(g.mb, dt) for g in small)
    assert sum(g.n_loc for g in small) >= 4096
    # on the CPU nothing takes it, and the one-program cells stay so
    assert not any(pallas_lu.merged_eligible(g.wb, g.mb, dt)
                   for g in sched.groups)
    s30 = batched.get_schedule(plan_of("lap3d_k30.step"), 1)
    assert len(s30.groups) <= 96 and not batched.staged_enabled(s30)


@pytest.fixture
def staged(monkeypatch):
    """The route the chip takes at k = 48, at a size a test can hold."""
    monkeypatch.setenv("SLU_STAGED", "1")


def test_the_cells_arithmetic_on_the_staged_route(staged):
    """k = 12: 19 groups in 7 factor segments and 2 sweep segments.
    Each value set of the ring is refactored on the held plan and
    solved refined; every answer is held to the host oracle, to scipy
    `splu` and to the cell's own limits."""
    run = rehearsal_run(CELL)
    slu, cfg = run.slu, run.config
    from superlu_dist_tpu.ops import batched
    a0 = gen().generate(k=12)
    ring, seed = 3, 2147483777
    mats = value_sets(a0, cfg["value_drift"], seed, ring)
    syss = systems(mats, seed, ring)
    opts = run.options()
    plan = slu.plan_factorization(slu.csr_from_scipy(a0), opts)
    answers = []
    for j, m in enumerate(mats):
        xtrue, b = syss[j]
        csr = slu.csr_from_scipy(m)
        st = slu.Stats()
        lu = slu.factorize(csr, opts, plan=plan, stats=st)
        assert isinstance(lu.device_lu, batched.StagedLU)
        assert lu.device_lu.dtype == np.float32
        x = np.asarray(slu.solve(lu, b, stats=st))
        assert x.dtype == np.float64 and 1 <= st.refine_steps <= 4
        assert st.dispatch["dispatch"] == "staged"
        assert st.dispatch["segments"] > 1
        assert st.dispatch["sweep_segments"] > 2
        # the host oracle (ops/ref_multifrontal.py) on the same plan
        # and options, and scipy's own LU in float64
        oracle = slu.factorize(csr, opts, plan=plan, backend="host")
        xo = np.asarray(slu.solve(oracle, b))
        xs = spla.splu(m.tocsc()).solve(b)
        for ref in (xo, xs):
            assert np.linalg.norm(x - ref) / np.linalg.norm(ref) < 1e-9
        answers.append((j, b, xtrue, x))
    verdict = Checker(mats, cfg["guarantees"]).judge(answers)
    assert verdict["failed"] == 0 and verdict["attempted"] == ring
    assert verdict["splu_compared"] == 1
    worst = {c["name"]: c["value"] for c in verdict["compared"]}
    assert worst["berr_max"] < 4 * np.finfo(np.float64).eps
    assert worst["relerr_max"] < 1e-11 and worst["vs_splu_max"] < 1e-9


def test_sound_rehearsal_on_the_staged_route(staged):
    """The step kind's own loop with the route forced: `correct`, and
    the ring says every factorization of the window was staged."""
    run = rehearsal_run(CELL)
    line = drive(run)
    assert line["correct"] is True and line["attempted"] > 0
    worst = {c["name"]: c["value"] for c in line["compared"]}
    assert worst["berr_max"] < 4 * np.finfo(np.float64).eps
    steps = len(run.readings["refine_steps"][-64:])
    events = run.slu.obs.HEALTH.snapshot()["factor_events"][-steps:]
    assert {e["dispatch"] for e in events} == {"staged"}
    reader = harness.metric_reader("staged_segments.step")
    assert reader.segments(run) == events[-1]["segments"] >= 1
    assert run.notes["route"]["sweep_segments"] >= 2
    assert reader.read(run) is None         # a rehearsal reports none


@pytest.mark.parametrize("control", ["refine_float32", "no_refine"])
def test_control_is_not_correct_on_the_staged_route(staged, control):
    line = drive(rehearsal_run(CELL, control))
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    worst = {c["name"]: c for c in line["compared"]}
    assert worst["berr_max"]["value"] > worst["berr_max"]["limit"]


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
    assert set(line["metric_names"]) == expects
    listed = {m["name"] for m in harness.load_cell(CELL)["per_layer"]}
    assert expects - {"step_s", "setup_s"} <= listed


def test_the_cell_is_declared_and_every_reader_is_there():
    """By name, never by place: a later PR appends to these lists."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lap3d_k48", "step", 1)
    assert len(cell["why"]) <= 200
    entry = {c["name"]: c for c in b["configs"]}["lap3d_k48"]
    assert entry["file"] == "benchmark/configs/lap3d_k48.json"
    assert entry["reduced"] == ["n"] and len(entry["source"]) <= 200
    with open(os.path.join(ROOT, entry["file"])) as f:
        assert json.load(f)["source"] == entry["source"]
    # no other configuration names this file or this pair
    assert sum(c["file"] == entry["file"] for c in b["configs"]) == 1
    assert sum((w["config"], w["traffic"]) == ("lap3d_k48", "step")
               for w in b["workloads"]) == 1
    spec = harness.load_cell(CELL)
    assert {m["name"] for m in spec["end_to_end"]} == {"step_s",
                                                       "setup_s"}
    assert {m["name"] for m in spec["per_layer"]} == set(LISTED)
    for m in spec["per_layer"]:
        assert m["moves"] in ("step_s", "setup_s")
        assert hasattr(harness.metric_reader(m["name"]), "read")
    declared = {m["name"] for m in b["per_layer"]}
    for name in NEW:
        assert hasattr(harness.metric_reader(name), "read")
        assert name not in declared
    # the traffic is the step cells' own, as it is
    assert spec["traffic"] == harness.load_cell("lap3d_k30.step")["traffic"]
