"""The cell `helm2d_grid2x2.zstep` on the CPU: that its configuration
is `helm2d_n512`'s on `lap3d_k30_grid2x2`'s grid, key by key; its
rehearsal at -n 12 on four virtual devices, traced and untraced, and
its two controls, in the pair lowering a TPU mesh takes (forced here
by the tests' hook, SLU_COMPLEX_PAIR=1); the two readers the mesh's
pair storage brought (`dist_gather_s.grid`, `pair_codec_s.step`) on a
hand-made trace; and that the cell is declared by name.

The rehearsals are processes of their own, as test_rehearsal.py's:
the grid needs four devices, which `python3 -m pytest benchmark/tests`
does not force.  They run the pair lowering only: native complex on a
forced-multi-device XLA:CPU client is the miscompile lottery of
tests/lottery_util.py, and it is not what the cell's chips run.  The
mesh path against the reference in one process, at -n 12 to -n 16, is
tests/test_pair_mesh.py."""

import copy
import json
import os
import subprocess

import pytest

import harness
import progspans
from conftest import ROOT
from test_progspans import HAND_MADE, MAIN, US, _read, _run, op, span
from test_rehearsal import RUN

CELL = "helm2d_grid2x2.zstep"
ONE_CHIP = "helm2d_n512.zstep"
GRID_CELL = "lap3d_k30_grid2x2.step"
NEW = ("dist_gather_s.grid", "pair_codec_s.step")
# the readers in the tree, undeclared, that resolve for the cell
RESOLVING = ("factor_roofline_z", "pair_front_roofline",
             "pair_lowering_share")
LISTED = ("plan_s", "compile_s", "window_compiles.step", "step_median_s",
          "factor_s", "solve_s.step", "refine_steps.step",
          "residual_s.step", "sweep_device_s.step",
          "idle_attributed.step", "factor_named_share", "extend_add_s",
          "ea_row_share", "collective_ms.grid")
SEED = 2147483659


def command(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", SLU_COMPLEX_PAIR="1")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    return subprocess.run(
        RUN + ["--workload", CELL, "--seed", str(SEED), "--seconds",
               "2", "--rehearse-cpu", *extra],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)


def test_the_configuration_is_helm2d_n512s_on_the_grid():
    cfg = harness.load_cell(CELL)["config"]
    one = harness.load_cell(ONE_CHIP)["config"]
    grid = harness.load_cell(GRID_CELL)["config"]
    # the matrix, the options, the guarantees and the controls are
    # the one-chip configuration's, letter for letter
    same = ("n", "matrix", "rehearsal_matrix_args", "value_drift",
            "options", "amalgamation", "precision", "guarantees",
            "controls", "shapes", "reduced")
    for key in same:
        assert cfg[key] == one[key], key
    differ = {"name", "source", "deployment", "grid", "reduced_why",
              "assumed", "matrix_source", "size", "dispatch"}
    assert set(cfg) == set(same) | differ
    assert set(one) == set(cfg) - {"dispatch"}
    assert cfg["name"] == "helm2d_grid2x2"
    assert cfg["grid"] == grid["grid"] == [2, 2, 1]
    assert one["grid"] is None
    assert cfg["n"] == cfg["matrix"]["args"]["n"] ** 2 == 65536
    assert cfg["reduced"] == ["n"] and set(cfg["reduced_why"]) == {"n"}
    # everything assumed of ex11, and the two grid option names
    assert cfg["assumed"][:len(one["assumed"])] == one["assumed"]
    extra = " ".join(cfg["assumed"][len(one["assumed"]):])
    assert "-mat_superlu_dist_r" in extra
    assert "-mat_superlu_dist_c" in extra
    for word in ("-n 256", "mpiexec -n 4", "-mat_superlu_dist_r 2",
                 "-mat_superlu_dist_c 2", "pzdrive3.c"):
        assert word in cfg["source"], word
    assert len(cfg["source"]) <= 200
    # no option, flag or variable of the program names the lowering
    # or the cooperative chain
    text = json.dumps(cfg)
    for var in ("SLU_COMPLEX_PAIR", "SLU_COMPLEX_TPU", "SLU_COOP_MB",
                "SLU_COOP_SHARDED"):
        assert var not in text
    assert "coop" in cfg["dispatch"]


@pytest.mark.parametrize("trace,expects", [
    ("0", {"step_s", "setup_s"}),
    ("1", {"factor_s", "solve_s.step", "plan_s", "compile_s",
           "window_compiles.step", "refine_steps.step",
           "step_median_s"}),
])
def test_rehearsal(trace, expects):
    r = command("--trace", trace)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and "metrics" not in line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["count"] == 4
    assert set(line["metric_names"]) == expects
    listed = {m["name"] for m in harness.load_cell(CELL)["per_layer"]}
    assert expects - {"step_s", "setup_s"} <= listed


@pytest.mark.parametrize("control", ["refine_complex64", "no_refine"])
def test_control_is_not_correct(control):
    r = command("--trace", "0", "--control", control)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    worst = {c["name"]: c for c in line["compared"]}
    assert worst["berr_max"]["value"] > worst["berr_max"]["limit"]


# -- the readers ------------------------------------------------------

def mesh_trace():
    """test_progspans' hand-made trace as a pair-stored mesh step
    writes it: the value set's planes encoded inside `slu.FACT`, the
    right-hand side's before each of the two sweeps and the answer's
    decoded after each fetch; in the factor program a panel psum of
    the cooperative chain inside the loop (36-39 us) and the slab's
    gather in place of the trailing copy (75-90 us)."""
    loaded = copy.deepcopy(HAND_MADE)
    loaded["host"] += [span(MAIN, "slu.pair.encode", 12, 3),
                       span(MAIN, "slu.pair.encode", 215, 2),
                       span(MAIN, "slu.pair.decode", 292, 4),
                       span(MAIN, "slu.pair.encode", 345, 1),
                       span(MAIN, "slu.pair.decode", 402, 2)]
    loaded["ops"][3] = op(
        "%all-gather.1 all-gather", 75, 15, progspans.scope_of(
            "jit(slu_dist_factor)/slu.dist.gather/all_gather"))
    loaded["ops"].insert(2, op(
        "%all-reduce.1 all-reduce", 36, 3, progspans.scope_of(
            "jit(slu_dist_factor)/slu.partial_lu/slu.coop.psum/psum")))
    return loaded


def test_the_collectives_scopes_of_the_factor_program():
    run = _run("zstep", mesh_trace(), steps=2)
    # the gather's 15 us and the psum's 3, over two traced steps
    assert _read("dist_gather_s.grid", run) == pytest.approx(18 * US / 2)
    note = run.notes["dist_gather_s.grid"]
    assert sum(note.values()) == pytest.approx(9 * US)
    assert len(note) == 2 and all(k.startswith(("slu.dist", "slu.coop"))
                                  for k in note)
    # the psum is the loop's no longer, and no kernel's
    scopes = run.readings["progspans"]["factor_scopes"]
    assert scopes["slu.extend_add"] == pytest.approx(17 * US)
    assert scopes["slu.partial_lu"] == pytest.approx(10 * US)
    # what the sweeps run is not the factorization's
    plain = _run("zstep", HAND_MADE, steps=2)
    assert _read("dist_gather_s.grid", plain) is None and not plain.notes


def test_the_codecs_spans():
    run = _run("zstep", mesh_trace(), steps=2)
    assert _read("pair_codec_s.step", run) == pytest.approx(12 * US / 2)
    assert run.notes["pair_codec_s.step"] == {
        "slu.pair.encode": pytest.approx(6 * US / 2),
        "slu.pair.decode": pytest.approx(6 * US / 2)}
    # they lie inside the spans the step has: those keep their
    # seconds, and give their self time to the codec
    host = run.readings["progspans"]["host_s"]
    plain = _run("zstep", HAND_MADE, steps=2).readings["progspans"]
    for name in ("slu.FACT", "slu.SOLVE", "slu.REFINE_STEP"):
        assert host[name][0] == plain["host_s"][name][0]
    assert host["slu.FACT"][1] == pytest.approx(
        plain["host_s"]["slu.FACT"][1] - 3 * US)


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_where_nothing_is(name):
    """No TPU plane, a real system or one device (no such span, no
    such scope), a program without scopes: None, no error, no note."""
    bare = {"host": [h for h in HAND_MADE["host"]
                     if h[1].startswith("bench.")],
            "modules": HAND_MADE["modules"], "inflight": [],
            "ops": [o[:3] + [None] for o in HAND_MADE["ops"]]}
    for loaded in (None, HAND_MADE, bare):
        run = _run("zstep", loaded, steps=2)
        assert _read(name, run) is None and not run.notes
    # a trace whose steps were not counted
    run = _run("zstep", mesh_trace(), steps=None)
    assert _read(name, run) is None and not run.notes


def test_the_cell_is_declared_and_every_reader_is_there():
    """By name, never by place: a later PR appends to these lists.
    The two readers this configuration brought and the three it
    resolves are files the harness finds; their entries in `per_layer`
    wait for a `benchmark` PR (PERF.md section 7)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "helm2d_grid2x2", "zstep", 4)
    centry = {c["name"]: c for c in b["configs"]}["helm2d_grid2x2"]
    assert centry["file"] == "benchmark/configs/helm2d_grid2x2.json"
    assert centry["reduced"] == ["n"]
    per_layer = {m["name"]: m for m in b["per_layer"]}
    for name in LISTED:
        assert CELL in per_layer[name]["workloads"], name
    spec = harness.load_cell(CELL)
    assert {m["name"] for m in spec["per_layer"]} == set(LISTED)
    assert {m["name"] for m in spec["end_to_end"]} == {"step_s",
                                                       "setup_s"}
    # the dist path does not pack, and real flops are not this cell's
    for name in ("pack_s.step", "factor_roofline"):
        assert CELL not in per_layer[name]["workloads"]
    for name in NEW + RESOLVING:
        assert hasattr(harness.metric_reader(name), "read")
        assert name not in per_layer
