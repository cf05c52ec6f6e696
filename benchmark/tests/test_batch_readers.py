"""The four readers the batch configuration brought
(`batch_factor_roofline`, `sweep_member_parallel_share.bstep`,
`stage_s.bstep`, `member_us.bstep`) and the count they rest on
(`roofline_batch.py`): on hand-made runs, on a parent that has no such
span or counter, and through the program on the CPU rehearsal of
`xgc_coll992_b2048.bstep` (a rehearsal reports none of the program's
numbers, so the counters are read by their own functions)."""

import copy
import types

import numpy as np
import pytest

import harness
import roofline
import roofline_batch
from test_correct import drive, rehearsal_run
from test_progspans import HAND_MADE, MAIN, US, _read, _run, span

CELL = "xgc_coll992_b2048.bstep"
PEAKS = {"flops_per_s": 100e12, "hbm_bytes_per_s": 800e9}


def test_the_batch_count_is_the_members_times_one_members():
    w, r = np.array([4, 8, 3]), np.array([10, 0, 5])
    one_f = roofline.factor_flops(w, r)
    one_b = roofline.factor_bytes(w, r, 77, 4)
    assert roofline_batch.batch_factor_flops(w, r, 1) == one_f
    assert roofline_batch.batch_factor_flops(w, r, 2048) == 2048 * one_f
    assert roofline_batch.batch_factor_bytes(w, r, 77, 4, 2048) \
        == 2048 * one_b


def traced(members=2048, device_s=0.2, steps=2, peaks=PEAKS):
    fronts = {"w": np.array([16] * 40), "r": np.array([30] * 40),
              "nnz": 8554}
    return types.SimpleNamespace(
        readings={"trace": {"span_device_s":
                            {"bench.factorize": device_s}},
                  "fronts": fronts, "traced_steps": steps,
                  "batch_members": members},
        config={"options": {"factor_dtype": "float32"}},
        peaks=peaks, notes={})


def test_batch_factor_roofline_on_a_made_up_trace():
    read = harness.metric_reader("batch_factor_roofline").read
    run = traced()
    f = run.readings["fronts"]
    flops = 2048 * roofline.factor_flops(f["w"], f["r"])
    nbytes = 2048 * roofline.factor_bytes(f["w"], f["r"], 8554, 4)
    least = max(flops / 100e12, nbytes / 800e9)
    assert read(run) == pytest.approx(100 * least / 0.1)
    note = run.notes["batch_factor_roofline"]
    assert note["members"] == 2048 and note["bound"] == "bytes"
    assert note["device_s_per_factorization"] == pytest.approx(0.1)
    assert 0 < read(run) < 100
    # twice the members in the same device time: twice the share
    assert read(traced(members=4096)) == pytest.approx(2 * read(run))
    # no batch, no traced factorization, no trace, no peaks: None
    for run in (traced(members=None), traced(device_s=0.0),
                traced(peaks=None)):
        assert read(run) is None and not run.notes
    bare = traced()
    bare.readings["trace"] = None
    assert read(bare) is None
    # a one-system cell's run has no `batch_members` at all
    del bare.readings["batch_members"]
    bare.readings["trace"] = {"span_device_s": {"bench.factorize": 1.0}}
    assert read(bare) is None


def ring(recent, steps=0):
    snap = {} if recent is None else {"recent_solves": recent}
    return types.SimpleNamespace(
        rehearse=False, notes={}, readings={"refine_steps": [3] * steps},
        slu=types.SimpleNamespace(obs=types.SimpleNamespace(
            HEALTH=types.SimpleNamespace(snapshot=lambda: snap))))


def test_sweep_member_parallel_share_reads_the_programs_ring():
    read = harness.metric_reader(
        "sweep_member_parallel_share.bstep").read

    def rec(arm, n=4, members=2048):
        return {"steps": n - 1, "sweeps": {"float32": n},
                "sweep_arm": arm, "members": members,
                "members_stalled": 0}

    run = ring([rec("vmap")] * 5)
    assert read(run) == 100.0
    assert run.notes["batch_sweeps_by_arm"] == {"vmap": 20}
    assert read(ring([rec("scan")] * 3)) == 0.0
    assert read(ring([rec("scan", 4), rec("vmap", 3), rec("vmap", 1)])) \
        == 50.0
    # only the window's steps count: older records are warm-up's
    mixed = [rec("scan")] * 2 + [rec("vmap")] * 3
    assert read(ring(mixed, steps=3)) == 100.0
    assert read(ring(mixed)) == 60.0
    # a one-system solve's record (no `members`; a mesh's names its
    # own arm) counts nothing; a parent without the ring, or without
    # the counter: None, no error, no note
    one = {"steps": 3, "sweeps": {"float32": 4}, "sweep_arm": "merged"}
    for recent in (None, [], [one], [{"steps": 3, "berr": 1e-16}],
                   [dict(rec("vmap"), sweeps={})]):
        run = ring(recent)
        assert read(run) is None and not run.notes
    assert read(ring([one, rec("vmap")])) == 100.0


def staged_trace():
    """test_progspans' hand-made trace with a step's `slu.batch.stage`
    inside `slu.FACT` (10-90 us): 14 us before the program starts at
    20."""
    loaded = copy.deepcopy(HAND_MADE)
    loaded["host"] += [span(MAIN, "slu.batch.stage", 11, 8),
                       span(MAIN, "slu.batch.stage", 60, 6)]
    return loaded


def test_stage_s_reads_the_programs_span():
    run = _run("bstep", staged_trace(), steps=2)
    assert _read("stage_s.bstep", run) == pytest.approx(14 * US / 2)
    # a program without the span (the parent), or no TPU plane: None
    assert _read("stage_s.bstep", _run("bstep", HAND_MADE, steps=2)) \
        is None
    assert _read("stage_s.bstep", _run("bstep", None)) is None


def test_member_us_is_the_median_step_over_the_members():
    read = harness.metric_reader("member_us.bstep").read

    def run(walls, members):
        return types.SimpleNamespace(readings={
            "step_walls": walls, "batch_members": members})

    assert read(run([0.4, 0.5, 0.9], 2048)) == pytest.approx(
        1e6 * 0.5 / 2048)
    assert read(run([0.25], 1000)) == pytest.approx(250.0)
    for r in (run([], 2048), run([0.4], None), run(None, 8)):
        assert read(r) is None
    assert read(types.SimpleNamespace(readings={})) is None


def test_through_the_program_on_the_rehearsal():
    """The rehearsal's ring and readings resolve every one of the
    four that a CPU run can: the arm it names is XLA:CPU's (`scan`),
    so the share reads 0 here, by its own function."""
    run = rehearsal_run(CELL)
    line = drive(run)
    assert line["correct"] is True and line["attempted"] > 0
    arm = harness.metric_reader("sweep_member_parallel_share.bstep")
    assert arm.share(run) == 0.0
    by = run.notes["batch_sweeps_by_arm"]
    steps = run.readings["refine_steps"][-64:]
    assert by == {"scan": len(steps) + sum(steps)}
    assert arm.read(run) is None            # a rehearsal reports none
    members = run.config["rehearsal_batch"]
    assert run.readings["batch_members"] == members == 8
    us = harness.metric_reader("member_us.bstep").read(run)
    assert us == pytest.approx(
        1e6 * np.median(run.readings["step_walls"]) / members)
    # the ring's records are batched solves', one a step
    recent = run.slu.obs.HEALTH.snapshot()["recent_solves"]
    assert recent[-1]["members"] == members
    assert 0 <= recent[-1]["members_stalled"] <= members
    assert recent[-1]["berr"] <= 2.0 ** -51     # astride eps at worst
    # the trace-borne two need the chip's trace: None without one
    assert harness.metric_reader("batch_factor_roofline").read(run) \
        is None
