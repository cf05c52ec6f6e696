"""The cell `elas3d_q1.step` rehearsed on the CPU at ne=3, traced and
untraced; its controls; and the three readers this configuration
brought (`factor_useful_flops`, `dense_front_share`,
`dense_front_roofline`) on the hand-made trace of test_progspans.py
and on the excerpt recorded on the chip."""

import json
import os
import types

import numpy as np
import pytest

import harness
import roofline
from conftest import HERE
from test_correct import drive, rehearsal_run
from test_progspans import HAND_MADE, US, _read, _run
from test_rehearsal import command

CELL = "elas3d_q1.step"
NEW = ("factor_useful_flops", "dense_front_share",
       "dense_front_roofline")


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
    assert set(NEW) <= listed


def test_sound_run_is_correct():
    line = drive(rehearsal_run(CELL))
    assert line["correct"] is True and line["attempted"] > 0


@pytest.mark.parametrize("control", ["refine_float32", "no_refine"])
def test_control_is_not_correct(control):
    line = drive(rehearsal_run(CELL, control))
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    worst = {c["name"]: c for c in line["compared"]}
    assert worst["berr_max"]["value"] > worst["berr_max"]["limit"]


def test_the_matrix_is_the_configurations():
    run = rehearsal_run(CELL)
    a = run.matrix()
    assert a.shape == (3 * 4 ** 3,) * 2
    cfg = harness.load_cell(CELL)["config"]
    assert cfg["n"] == 3 * (cfg["matrix"]["args"]["ne"] + 1) ** 3
    assert cfg["reduced"] == ["n"] and cfg["grid"] is None


# -- the readers ------------------------------------------------------

FRONTS = {"w": np.array([8, 16]), "r": np.array([24, 0]), "nnz": 100}
PEAKS = {"flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}


def traced(loaded, steps=2):
    run = _run("step", loaded, steps=steps)
    run.readings["fronts"] = FRONTS
    run.peaks, run.devices = PEAKS, [object()]
    return run


def test_dense_front_readers_on_the_hand_made_trace():
    run = traced(HAND_MADE)
    # the factor program's scopes: extend_add 20, partial_lu 10,
    # schur 20, unnamed 15 us
    assert _read("dense_front_share", run) == pytest.approx(
        100 * 30 / 65)
    flops = roofline.factor_flops(FRONTS["w"], FRONTS["r"])
    # two traced steps: 15 us of dense kernels a factorization
    assert _read("dense_front_roofline", run) == pytest.approx(
        100 * (flops / 1e9) / (15 * US))
    assert run.notes["dense_front_roofline"]["flops"] == flops


def test_dense_front_readers_on_the_chips_excerpt():
    """The excerpt holds the factor program's last stores only: no
    dense kernel, so a share of 0 and no roofline."""
    with open(os.path.join(HERE, "data", "prog_excerpt.json")) as f:
        run = traced(json.load(f), steps=1)
    assert _read("dense_front_share", run) == 0.0
    assert _read("dense_front_roofline", run) is None


@pytest.mark.parametrize("name", NEW[1:])
def test_trace_readers_read_nothing_where_nothing_is(name):
    """No TPU plane, or a program without scopes: None, no error."""
    assert _read(name, traced(None)) is None
    bare = {"host": [h for h in HAND_MADE["host"]
                     if h[1].startswith("bench.")],
            "modules": HAND_MADE["modules"], "inflight": [],
            "ops": [o[:3] + [None] for o in HAND_MADE["ops"]]}
    run = traced(bare)
    assert _read(name, run) is None and not run.notes


def health(last):
    return types.SimpleNamespace(obs=types.SimpleNamespace(
        HEALTH=types.SimpleNamespace(
            snapshot=lambda: {"last_factor": last})))


def test_factor_useful_flops_reads_the_programs_counter():
    run = types.SimpleNamespace(
        rehearse=False, notes={},
        slu=health({"flops": {"useful": 3.0, "executed": 4.0}}))
    assert _read("factor_useful_flops", run) == 75.0
    assert run.notes["factor_flops"] == {"useful": 3.0, "executed": 4.0}
    # the parent of the PR that brought the counters: no such key
    for last in (None, {"mem": None}, {"flops": None}):
        run = types.SimpleNamespace(rehearse=False, notes={},
                                    slu=health(last))
        assert _read("factor_useful_flops", run) is None
    # a real factorization's record, through the program
    reader = harness.metric_reader("factor_useful_flops")
    real = rehearsal_run(CELL)
    real.slu.factorize(real.slu.csr_from_scipy(real.matrix()),
                       real.options())
    assert 0.0 < reader.useful_share(real) <= 100.0
    assert reader.read(real) is None        # a rehearsal reports none


def test_the_new_metrics_are_declared_for_both_step_cells():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == ["lap3d_k30.step", CELL]
        assert per_layer[name]["moves"] == "step_s"
