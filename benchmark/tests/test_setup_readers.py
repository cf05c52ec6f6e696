"""The four readers of the program's start-up ledger
(`trace_lower_s`, `cache_load_s`, `cold_programs`,
`setup_named_share`; `benchmark/startup.py`): on a hand-made ledger
(overlapping intervals, a record before the process's start, one born
in the window), on a program that has no ledger, in a rehearsal, and
through the program on the CPU.  The readers are files the harness
finds by name; where `BENCHMARK.json` declares one, by name and
wherever it stands, it is a `compile / warm-up` metric that moves
`setup_s` (PERF.md section 7 says why this PR declares none)."""

import json
import os
import time
import types

import pytest

import harness
import startup
from conftest import ROOT
from test_correct import drive, rehearsal_run

NAMES = ("trace_lower_s", "cache_load_s", "cold_programs",
         "setup_named_share")
CELLS = ["lap3d_k30.step", "lap3d_k30.serve", "lap3d_k30_grid2x2.step",
         "elas3d_q1.step", "helm2d_n512.zstep"]
T0, SETUP_S = 1000.0, 100.0


def prog(name, t0, *, trace=0.0, lower=0.0, compile_=0.0, load=0.0,
         watched=None, cache="hit", nested=()):
    """A row as `COMPILE_WATCH.ledger()` gives it: its kinds follow
    one another from `t0`; `nested` adds spans inside the trace."""
    spans, t = [], t0
    for kind, s in (("trace", trace), ("lower", lower),
                    ("compile", compile_), ("load", load)):
        if s:
            spans.append([kind, t, t + s])
            t += s
    spans += [["compile", a, b] for a, b in nested]
    row = {"name": name, "watched": watched, "t0": t0, "thread": 1,
           "cache": cache, "saved_s": 0.0, "spans": spans,
           "trace_s": trace, "lower_s": lower,
           "compile_s": compile_ + sum(b - a for a, b in nested),
           "load_s": load}
    if watched:
        row["wall_s"] = t - t0 + 0.5
        row["first_call_other_s"] = 0.5
    return row


PROGRAMS = [
    # before the process's start: another run's, never read
    prog("stale", T0 - 50.0, trace=9.0, lower=9.0, load=9.0),
    # the factor program, warm: an eager conversion compiled cold
    # while it was traced lies inside its trace
    prog("slu_factor", T0 + 10.0, trace=30.0, lower=20.0, load=8.0,
         watched="factor", nested=[(T0 + 12.0, T0 + 13.0)],
         cache="miss"),
    # another thread lowers while the first traces: overlap
    prog("slu_solve_packed", T0 + 35.0, trace=4.0, lower=6.0, load=2.0,
         watched="solve"),
    prog("add", T0 + 70.0, trace=0.5, lower=0.5, load=0.25),
    prog("add", T0 + 72.0, trace=0.5, lower=0.5, compile_=1.0,
         cache="off"),
    prog("looked_at", T0 + 80.0, trace=1.0, cache=None),
    # born in the window: not set-up's
    prog("late_one", T0 + SETUP_S + 5.0, trace=1.0, lower=1.0,
         compile_=3.0, cache="miss"),
]
PHASES = [
    {"name": "EQUIL", "t0": T0 - 20.0, "seconds": 5.0},
    {"name": "ETREE", "t0": T0 + 2.0, "seconds": 1.0},
    {"name": "SYMBFACT", "t0": T0 + 3.0, "seconds": 2.0},
    # the schedule is built inside the factor program's first call
    {"name": "SCHEDULE", "t0": T0 + 9.0, "seconds": 3.0},
]


def fake_ledger(since=None, until=None):
    def inside(t):
        return ((since is None or t >= since)
                and (until is None or t < until))
    return {"header": {"cache_dir": "/c", "ledger_self_s": 0.01,
                       "overflowed": False},
            "programs": [p for p in PROGRAMS if inside(p["t0"])],
            "phases": [p for p in PHASES if inside(p["t0"])],
            "folded": {}}


def made_up(ledger=fake_ledger, rehearse=False, setup_s=SETUP_S):
    watch = types.SimpleNamespace()
    if ledger is not None:
        watch.ledger = ledger
    totals = {"bench.plan": 4.0, "bench.warmup": 60.0}
    return types.SimpleNamespace(
        rehearse=rehearse, t_start=T0,
        notes={"setup_gc_collect_s": 0.5},
        readings={} if setup_s is None else {"setup_s": setup_s},
        spans=types.SimpleNamespace(total=totals.get),
        slu=types.SimpleNamespace(obs=types.SimpleNamespace(
            COMPILE_WATCH=watch)))


def read(name, run):
    return harness.metric_reader(name).read(run)


def test_trace_lower_s_is_a_union_over_set_up():
    run = made_up()
    # factor 10..60, packed 35..45 (inside it), add 70..71 and 72..73,
    # looked_at 80..81: 50 + 1 + 1 + 1; `stale` and `late_one` are out
    assert read("trace_lower_s", run) == pytest.approx(53.0)
    top = run.notes["startup_programs"]
    assert [p["name"] for p in top[:2]] == ["slu_factor",
                                            "slu_solve_packed"]
    assert top[0] == {"name": "slu_factor", "watched": "factor",
                      "cache": "miss", "trace_s": 30.0, "lower_s": 20.0,
                      "compile_s": 1.0, "load_s": 8.0,
                      "first_call_other_s": 0.5}
    assert len(top) == 5 and "late_one" not in {p["name"] for p in top}
    small = run.notes["startup_small"]
    assert small["count"] == 3 and small["trace_s"] == 2.0
    assert small["compile_s"] == 1.0 and small["load_s"] == 0.25
    assert list(small["largest"]) == ["add", "looked_at"]
    assert small["largest"]["add"] == {"count": 2, "seconds": 3.25}
    assert run.notes["startup_header"]["ledger_self_s"] == 0.01
    assert run.notes["startup_header"]["programs"] == 5


def test_cache_load_s_sums_the_loads_of_set_up():
    assert read("cache_load_s", made_up()) == pytest.approx(10.25)


def test_cold_programs_counts_misses_and_names_the_late():
    run = made_up()
    assert read("cold_programs", run) == 2      # looked_at is neither
    assert run.notes["cold_programs"] == {
        "slu_factor": {"count": 1, "seconds": 59.0},
        "add": {"count": 1, "seconds": 2.0}}
    assert run.notes["window_programs"] == ["late_one"]


def test_setup_named_share_unions_phases_and_programs():
    run = made_up()
    # phases 2..5 and 9..12; programs 10..68 (the factor program to
    # 68, the packed one inside), 70..71.25, 72..74, 80..81
    named = 3.0 + (68.0 - 9.0) + 1.25 + 2.0 + 1.0
    assert read("setup_named_share", run) == pytest.approx(
        100.0 * named / SETUP_S)
    parts = run.notes["setup_by_part"]
    assert parts == {
        "plan.ETREE": 1.0, "plan.SYMBFACT": 2.0, "schedule": 3.0,
        "trace": pytest.approx(32.0), "lower": pytest.approx(22.0),
        "compile": pytest.approx(2.0), "load": pytest.approx(10.25),
        "bench.plan": 4.0, "bench.warmup": 60.0,
        "setup_gc_collect_s": 0.5, "setup_s": SETUP_S}
    # a program that runs past the end of set-up is cut there
    run = made_up(setup_s=40.0)
    assert read("setup_named_share", run) == pytest.approx(
        100.0 * (3.0 + 31.0) / 40.0)
    assert read("trace_lower_s", run) == pytest.approx(30.0)


@pytest.mark.parametrize("name", NAMES)
def test_none_in_a_rehearsal_and_without_a_ledger(name):
    """A rehearsal prints no number of the program's; the parent of
    the PR that brought the ledger has none; the tests' own drive of a
    kind times no set-up.  None each time, no error, no note."""
    for run in (made_up(rehearse=True), made_up(ledger=None),
                made_up(setup_s=None)):
        notes = dict(run.notes)
        assert read(name, run) is None and run.notes == notes
    run = made_up()
    del run.slu.obs.COMPILE_WATCH
    assert read(name, run) is None


def test_union_s_cuts_and_counts_overlaps_once():
    assert startup.union_s([(0, 2), (1, 3), (5, 6)]) == 4.0
    assert startup.union_s([(0, 2), (1, 3), (5, 6)], 1.5, 5.5) == 2.0
    assert startup.union_s([(0, 10), (2, 3)], 4, 8) == 4.0
    assert startup.union_s([], 0, 1) == 0.0
    assert startup.union_s([(3, 4)], 5, 6) == 0.0


def test_through_the_program_on_the_rehearsal():
    """The real ledger of a CPU drive of a step cell, read as a chip
    run reads it (set-up timed by hand; a rehearsal itself reports
    none of the four)."""
    run = rehearsal_run("lap3d_k30.step")

    def end_of_setup(run, _state):
        run.readings["setup_s"] = time.perf_counter() - run.t_start

    line = drive(run, end_of_setup)
    assert line["correct"] is True
    assert all(read(n, run) is None for n in NAMES)
    run.rehearse = False
    setup_s = run.readings["setup_s"]
    trace_lower = read("trace_lower_s", run)
    load = read("cache_load_s", run)
    cold = read("cold_programs", run)
    share = read("setup_named_share", run)
    assert 0.0 < trace_lower <= setup_s and 0.0 <= load <= setup_s
    assert 0.0 < share <= 100.0 and cold >= 0
    parts = run.notes["setup_by_part"]
    assert {"plan.ETREE", "plan.SYMBFACT", "plan.DIST", "schedule",
            "trace", "lower", "compile", "load", "bench.plan",
            "bench.warmup"} <= set(parts)
    assert parts["plan.SYMBFACT"] <= parts["bench.plan"]
    # the whole-phase programs of warm-up are among set-up's largest
    watched = {p["watched"] for p in run.notes["startup_programs"]}
    assert {"factor", "solve"} <= watched
    # (how many eager programs are new depends on what the process
    # ran before: none after another rehearsal)
    assert run.notes["startup_small"]["count"] >= 0
    # every program the window drives ran in warm-up first
    assert run.notes["window_programs"] == []
    assert run.notes["startup_header"]["ledger_self_s"] < 0.1


def test_the_readers_are_files_found_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NAMES:
        assert hasattr(harness.metric_reader(name), "read")
        entry = per_layer.get(name)
        if entry is not None:       # by name, wherever it stands
            assert (entry["layer"], entry["moves"], entry["source"]) \
                == ("compile / warm-up", "setup_s", "program_counter")
            assert set(entry["workloads"]) >= set(CELLS)
