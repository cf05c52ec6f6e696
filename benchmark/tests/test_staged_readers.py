"""The five readers the staged route brought (`staged_dispatch_s.step`,
`staged_wait_s.step`, `staged_segments.step`, `pallas_lu_share`,
`pallas_lu_roofline`) and the kernel's count (`roofline_pallas_lu.py`):
on a hand-made trace, on made-up health rings, and on what a parent
without the spans, the scope or the fields gives (None, no error, no
note).  The readers are files the harness finds; their entries in
`per_layer` wait for a `benchmark` PR (PERF.md section 7)."""

import copy
import json
import os
import types

import pytest

import harness
import progspans
import roofline_pallas_lu
from conftest import ROOT
from test_progspans import HAND_MADE, MAIN, US, _read, _run, op, span

NEW = ("staged_dispatch_s.step", "staged_wait_s.step",
       "staged_segments.step", "pallas_lu_share", "pallas_lu_roofline")
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LEAVES = [[4096, 16, 8]]        # lap3d_k48's one Pallas bucket


def staged_trace():
    """test_progspans' hand-made trace with a staged factorization's
    spans inside `slu.FACT` (10-90 us): the dispatch loop 14-60, the
    wait 60-86 (the device's idle gap 70-75 lies under it), and the
    factor program's first operation run by the Pallas kernel."""
    loaded = copy.deepcopy(HAND_MADE)
    loaded["host"] += [span(MAIN, "slu.fact.scale", 11, 2),
                       span(MAIN, "slu.fact.dispatch", 14, 46),
                       span(MAIN, "slu.fact.wait", 60, 26)]
    loaded["ops"][1] = op("%custom-call.1 custom-call", 25, 10,
                          "slu.pallas_lu")
    return loaded


def ring(last=None, solve=None):
    snap = {"last_factor": last, "last_solve": solve}
    return types.SimpleNamespace(
        rehearse=False, notes={}, readings={},
        slu=types.SimpleNamespace(obs=types.SimpleNamespace(
            HEALTH=types.SimpleNamespace(snapshot=lambda: snap))))


STAGED = {"tiny_pivots": 0, "dispatch": "staged", "segments": 109,
          "groups": 109, "pallas_buckets": 1, "pallas_shapes": LEAVES}


def test_the_spans_of_the_staged_run():
    run = _run("step", staged_trace(), steps=2)
    assert _read("staged_dispatch_s.step", run) == pytest.approx(
        46 * US / 2)
    assert _read("staged_wait_s.step", run) == pytest.approx(26 * US / 2)
    assert _read("scale_s.step", run) == pytest.approx(2 * US / 2)
    # the idle seconds inside a factorization carry the new names
    by = dict(progspans.idle_by_span(staged_trace())["by_span"])
    assert by["slu.fact.wait"] == pytest.approx(5 * US)
    assert "slu.FACT" not in by and "slu.fact.dispatch" not in by


def test_the_scope_of_the_kernel_is_its_own():
    assert progspans.scope_of(
        "jit(_staged_factor_segment)/slu.partial_lu/slu.pallas_lu/"
        "pallas_call") == "slu.pallas_lu"
    run = _run("step", staged_trace(), steps=2)
    # 10 of the factor program's 65 us of operations
    assert _read("pallas_lu_share", run) == pytest.approx(100 * 10 / 65)
    scopes = run.readings["progspans"]["factor_scopes"]
    assert scopes["slu.pallas_lu"] == pytest.approx(10 * US)
    assert "slu.partial_lu" not in scopes


def test_the_kernels_count():
    # one front of 16 x 16 with 8 columns eliminated: 2/3 8^3 +
    # 2 8^2 8 + 2 8 8^2 = 2389.33 operations; read once, written once
    assert roofline_pallas_lu.panel_lu_flops([[1, 16, 8]]) \
        == pytest.approx(2.0 / 3.0 * 512 + 1024 + 1024)
    assert roofline_pallas_lu.panel_lu_bytes([[1, 16, 8]], 4) == 2048
    assert roofline_pallas_lu.panel_lu_flops(LEAVES) == pytest.approx(
        4096 * 2389.3333333)
    assert roofline_pallas_lu.panel_lu_bytes(LEAVES, 4) == 8388608
    # a root front (r = 0) has no panels and no update
    assert roofline_pallas_lu.panel_lu_flops([[2, 8, 8]]) \
        == pytest.approx(2 * 2.0 / 3.0 * 512)
    assert roofline_pallas_lu.panel_lu_flops([]) == 0.0


def roofline_run(last, loaded=None):
    run = _run("step", loaded or staged_trace(), steps=2)
    run.peaks, run.rehearse = PEAKS, False
    run.config = {"options": {"factor_dtype": "float32"}}
    run.slu = ring(last).slu
    return run


def test_the_kernels_roofline_share():
    run = roofline_run(STAGED)
    # bytes bind: 8,388,608 B at 819 GB/s = 10.24 us against the 5 us
    # a factorization of this made-up trace spends in the kernel
    share = _read("pallas_lu_roofline", run)
    assert share == pytest.approx(100 * (8388608 / 819e9) / (5 * US))
    note = run.notes["pallas_lu_roofline"]
    assert note["bound"] == "bytes" and note["shapes"] == LEAVES
    assert note["device_s_per_factorization"] == pytest.approx(5 * US)
    assert note["flops"] == pytest.approx(9786709.33)


def test_segments_reads_the_programs_ring():
    reader = harness.metric_reader("staged_segments.step")
    run = ring(STAGED, {"sweep_segments": 192})
    assert reader.read(run) == 109.0
    assert run.notes["route"] == {
        "dispatch": "staged", "segments": 109, "groups": 109,
        "pallas_buckets": 1, "pallas_shapes": LEAVES,
        "sweep_segments": 192}
    # a factorization that is one program says so
    one = ring({"dispatch": "program", "segments": 1, "groups": 56,
                "pallas_buckets": 0, "pallas_shapes": []},
               {"sweep_segments": 1})
    assert reader.read(one) == 1.0
    assert one.notes["route"]["dispatch"] == "program"
    # a rehearsal reports none
    run.rehearse = True
    assert reader.read(run) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_where_nothing_is(name):
    """No TPU plane (a CPU rehearsal), a program without the spans and
    the scope (this PR's parent, traced with these files), a ring
    whose records say nothing of the route, or no ring at all: None,
    no error, no note."""
    read = harness.metric_reader(name).read
    for loaded in (None, HAND_MADE):
        for last in (None, {}, {"tiny_pivots": 0, "pack": "at_factor"}):
            run = roofline_run(last, loaded)
            if loaded is None:
                run.readings["progspans"] = None
            assert read(run) is None and not run.notes
    bare = roofline_run(None)
    bare.slu.obs.HEALTH.snapshot = lambda: {}
    if name in ("staged_segments.step", "pallas_lu_roofline"):
        assert read(bare) is None and not bare.notes
    # the scope without the shapes, and the shapes without the scope
    if name == "pallas_lu_roofline":
        assert read(roofline_run(dict(STAGED, pallas_shapes=[]))) is None
        assert read(roofline_run(STAGED, HAND_MADE)) is None
        no_peaks = roofline_run(STAGED)
        no_peaks.peaks = None
        assert read(no_peaks) is None


def test_the_readers_are_files_and_not_yet_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    for name in NEW:
        assert hasattr(harness.metric_reader(name), "read")
        assert name not in names
