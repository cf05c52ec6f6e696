"""The cell `helm2d_n512.zstep` on the CPU: its rehearsal at -n 12,
traced and untraced, in the native lowering and in the pair lowering
a TPU takes (forced here by the tests' hook, SLU_COMPLEX_PAIR=1); its
two controls; the normal path (`plan_factorization` ->
`factorize(plan=...)` -> `solve`) against `reference_z` on seeded data
at -n 24; that kind `zstep` runs kind `step`'s own loop; and the three
readers this configuration brought (`pair_lowering_share`,
`factor_roofline_z`, `pair_front_roofline`) on hand-made runs."""

import json
import os
import types

import numpy as np
import pytest

import harness
import reference
import reference_z
import roofline
import roofline_z
from conftest import BENCH, ROOT
from test_correct import drive, rehearsal_run
from test_progspans import HAND_MADE, US, _read, _run
from test_rehearsal import RUN

CELL = "helm2d_n512.zstep"
NEW = ("pair_lowering_share", "factor_roofline_z", "pair_front_roofline")
SEED = 2147483659
LOWERINGS = [("native", "0"), ("pair", "1")]


def command(*extra, pair):
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu", SLU_COMPLEX_PAIR=pair)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    return subprocess.run(
        RUN + ["--workload", CELL, "--seed", str(SEED), "--seconds",
               "2", *extra],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)


@pytest.mark.parametrize("lowering,pair", LOWERINGS)
@pytest.mark.parametrize("trace,expects", [
    ("0", {"step_s", "setup_s"}),
    ("1", {"factor_s", "solve_s.step", "plan_s", "compile_s",
           "window_compiles.step", "refine_steps.step",
           "step_median_s"}),
])
def test_rehearsal(trace, expects, lowering, pair):
    r = command("--trace", trace, "--rehearse-cpu", pair=pair)
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


def test_refuses_without_a_tpu():
    r = command("--trace", "0", pair="0")
    assert r.returncode != 0 and r.stdout.strip() == ""


@pytest.mark.parametrize("lowering,pair", LOWERINGS)
def test_sound_run_is_correct(lowering, pair, monkeypatch):
    monkeypatch.setenv("SLU_COMPLEX_PAIR", pair)
    run = rehearsal_run(CELL)
    line = drive(run)
    assert line["correct"] is True and line["attempted"] > 0
    # the program says which lowering every factorization and every
    # sweep of the window took
    reader = harness.metric_reader("pair_lowering_share")
    reader.share(run)
    assert set(run.notes["complex_lowering"]) == {lowering}
    assert reader.read(run) is None         # a rehearsal reports none
    # and the sweeps ran in the factor's precision
    sweeps = harness.metric_reader("sweep_factor_dtype_share.step")
    assert sweeps.share(run) == 100.0
    assert set(run.notes["sweeps_by_dtype"]) == {"complex64"}


@pytest.mark.parametrize("lowering,pair", LOWERINGS)
@pytest.mark.parametrize("control", ["refine_complex64", "no_refine"])
def test_control_is_not_correct(control, lowering, pair, monkeypatch):
    monkeypatch.setenv("SLU_COMPLEX_PAIR", pair)
    line = drive(rehearsal_run(CELL, control))
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    worst = {c["name"]: c for c in line["compared"]}
    assert worst["berr_max"]["value"] > worst["berr_max"]["limit"]


def test_the_matrix_is_the_configurations():
    cfg = harness.load_cell(CELL)["config"]
    args = cfg["matrix"]["args"]
    assert cfg["n"] == args["n"] ** 2 == 65536
    assert (args["sigma1"], args["sigma2_imag"]) == (100.0, 10.0)
    assert cfg["reduced"] == ["n"] and cfg["grid"] is None
    assert cfg["options"] == {"factor_dtype": "complex64",
                              "refine_dtype": "complex128",
                              "iter_refine": "SLU_DOUBLE"}
    a = rehearsal_run(CELL).matrix()
    n = cfg["rehearsal_matrix_args"]["n"]
    assert a.shape == (n * n,) * 2 and a.dtype == np.complex128
    assert a.has_sorted_indices and a.nnz == 5 * n * n - 4 * n
    h2 = 1.0 / (n + 1) ** 2
    assert np.allclose(a.diagonal(), 4.0 - 100.0 * h2 + 10j * h2)
    off = a.tolil()
    off.setdiag(0)
    assert set(np.unique(off.tocsr().data)) == {-1.0 + 0j}
    assert abs(a - a.T).max() == 0          # complex symmetric
    # indefinite: eigenvalues on both sides of the imaginary axis,
    # none nearer the origin than the imaginary shift
    ev = np.linalg.eigvals(a.toarray())
    assert (ev.real < 0).any() and (ev.real > 0).any()
    assert abs(ev).min() >= 10.0 * h2 * (1 - 1e-9)


# -- the normal path against the reference ----------------------------

@pytest.mark.parametrize("lowering,pair", LOWERINGS)
def test_normal_path_against_the_reference(lowering, pair, monkeypatch):
    """plan_factorization -> factorize(plan=...) -> solve, NOTRANS,
    on two value sets of one held plan, at -n 24."""
    monkeypatch.setenv("SLU_COMPLEX_PAIR", pair)
    run = rehearsal_run(CELL)
    slu, cfg = run.slu, run.config
    gen = harness.load_module("gen_helm2d", "configs", "gen_helm2d.py")
    a0 = gen.generate(**dict(cfg["matrix"]["args"], n=24))
    mats = reference_z.value_sets(a0, cfg["value_drift"], SEED, 2)
    systems = reference_z.systems(mats, SEED, 2)
    checker = reference_z.Checker(mats, cfg["guarantees"])
    opts = run.options()
    plan = slu.plan_factorization(slu.csr_from_scipy(a0), opts)
    answers = []
    for j, (a, (xtrue, b)) in enumerate(zip(mats, systems)):
        st = slu.Stats()
        lu = slu.factorize(slu.csr_from_scipy(a), opts, plan=plan,
                           stats=st)
        x = np.asarray(slu.solve(lu, b, stats=st))
        assert x.dtype == np.complex128
        assert st.complex_lowering == {"FACT": lowering,
                                       "SOLVE": lowering}
        assert st.sweeps == {"complex64": 1 + st.refine_steps}
        answers.append((j, b, xtrue, x))
    verdict = checker.judge(answers)
    assert verdict["failed"] == 0 and verdict["splu_compared"] == 1
    worst = {c["name"]: c["value"] for c in verdict["compared"]}
    assert worst["berr_max"] < 4 * np.finfo(np.float64).eps
    assert worst["relerr_max"] < 1e-12 and worst["vs_splu_max"] < 1e-12


def test_the_references_data_and_comparison():
    gen = harness.load_module("gen_helm2d", "configs", "gen_helm2d.py")
    a0 = gen.generate(8, 100.0, 10.0)
    drift = {"kind": "row_rescale_uniform", "low": 0.5, "high": 1.5}
    mats = reference_z.value_sets(a0, drift, SEED, 3)
    again = reference_z.value_sets(a0, drift, SEED, 3)
    assert all((m != n).nnz == 0 for m, n in zip(mats, again))
    # the real row factors of reference.value_sets, on complex values
    real = reference.value_sets(a0.real.tocsr(), drift, SEED, 3)
    for m, r in zip(mats, real):
        assert m.dtype == np.complex128
        assert np.array_equal(m.real.toarray(), r.toarray())
    with pytest.raises(ValueError):
        reference_z.value_sets(a0.real.tocsr(), drift, SEED, 1)
    systems = reference_z.systems(mats, SEED, 3)
    for (xtrue, b), m in zip(systems, mats):
        assert xtrue.dtype == b.dtype == np.complex128
        assert np.array_equal(b, m @ xtrue)
    assert abs(np.mean(np.abs(systems[0][0]) ** 2) - 2.0) < 0.6
    g = {"berr_max_in_eps_float64": 64, "relerr_max": 1e-9,
         "vs_splu_max": 1e-9}
    chk = reference_z.Checker(mats, g)
    xtrue, b = systems[0]
    good = chk.judge([(0, b, xtrue, xtrue.copy())])
    assert good["failed"] == 0 and good["splu_compared"] == 1
    bad = xtrue.copy()
    bad[3] *= 1 + 1e-6j                     # a phase error of 1e-6
    nan = xtrue.copy()
    nan[0] = np.nan
    for x in (None, bad, xtrue.real, xtrue[:-1], nan,
              xtrue.astype(np.complex64)):
        assert chk.judge([(0, b, xtrue, x)])["failed"] == 1


def test_zstep_runs_steps_own_loop():
    """Kind `zstep` is one more instance of kinds/step.py with the
    yardstick's three names taken from reference_z: the same code,
    not a copy of it."""
    step = harness.load_module("kind_step", "kinds", "step.py")
    zstep = harness.load_module("kind_zstep", "kinds", "zstep.py")
    # zstep's `setup` asks first whether the program keeps the cell on
    # the chip (the test below), then hands over to step's own
    inner = zstep.setup.__globals__["_step"]
    for name in ("setup", "reseed", "warm", "window", "check", "close"):
        theirs = getattr(step, name)
        ours = getattr(inner if name == "setup" else zstep, name)
        assert ours.__code__.co_filename == theirs.__code__.co_filename
        assert ours.__code__.co_code == theirs.__code__.co_code
        assert ours.__globals__["_step"].__code__.co_code \
            == step._step.__code__.co_code
        g = ours.__globals__
        assert (g["value_sets"], g["systems"], g["Checker"]) == (
            reference_z.value_sets, reference_z.systems,
            reference_z.Checker)
    assert step.value_sets is reference.value_sets   # untouched
    with open(os.path.join(BENCH, "traffic", "zstep.json")) as f:
        z = json.load(f)
    with open(os.path.join(BENCH, "traffic", "step.json")) as f:
        s = json.load(f)
    assert z["kind"] == "zstep"
    assert all(z[k] == s[k] for k in ("ring", "warmup_steps",
                                      "trace_steps"))


def test_a_program_that_leaves_the_chip_is_refused(monkeypatch, capsys):
    """The tree before PR 32 gates every complex program to the host
    CPU backend when it is started on a TPU: such a run fails cleanly
    (a code other than 0, no result line) before any set-up, where it
    would have timed the host's cores and traced no device
    operation."""
    from superlu_dist_tpu.utils import platform
    asked = []

    def gate(dtype, pair_capable=True):
        asked.append(np.dtype(dtype).name)
        return True

    monkeypatch.setattr(platform, "complex_needs_cpu", gate)
    run = rehearsal_run(CELL)
    zstep = harness.load_module("kind_zstep", "kinds", "zstep.py")
    with pytest.raises(harness.Refused, match="host CPU"):
        zstep.setup(run)
    assert asked == ["complex64"]
    assert "fronts" not in run.readings         # nothing was planned
    rc = harness.main(["--workload", CELL, "--seed", str(SEED),
                       "--seconds", "1", "--rehearse-cpu"],
                      __import__("time").perf_counter())
    out = capsys.readouterr()
    assert rc != 0 and out.out.strip() == ""
    assert "No result" in out.err
    # and a program that keeps the cell where it was started is let in
    monkeypatch.undo()
    assert platform.complex_needs_cpu("complex64") is False


# -- the readers ------------------------------------------------------

FRONTS = {"w": np.array([8, 16]), "r": np.array([24, 0]), "nnz": 100}
PEAKS = {"flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}


def traced(loaded, steps=2):
    run = _run("zstep", loaded, steps=steps)
    run.readings["fronts"] = FRONTS
    run.readings["trace"] = {"span_device_s": {
        "bench.factorize": 130 * US}}
    run.config = {"options": {"factor_dtype": "complex64"}}
    run.peaks, run.devices = PEAKS, [object()]
    return run


def test_the_complex_count_is_four_times_the_real_one():
    w, r = FRONTS["w"], FRONTS["r"]
    assert roofline_z.factor_flops(w, r) == 4 * roofline.factor_flops(w, r)
    assert roofline_z.factor_bytes(w, r, 100, 8) \
        == roofline.factor_bytes(w, r, 100, 8) \
        == 2 * roofline.factor_bytes(w, r, 100, 4)


def test_roofline_readers_on_the_hand_made_trace():
    run = traced(HAND_MADE)
    flops = roofline_z.factor_flops(FRONTS["w"], FRONTS["r"])
    nbytes = roofline_z.factor_bytes(FRONTS["w"], FRONTS["r"], 100, 8)
    # two traced steps: 65 us of factor programs a factorization
    assert _read("factor_roofline_z", run) == pytest.approx(
        100 * max(flops, nbytes) / 1e9 / (65 * US))
    note = run.notes["factor_roofline_z"]
    assert note["bound"] == ("flops" if flops >= nbytes else "bytes")
    assert note["flops"] == flops and note["bytes"] == nbytes
    # the dense scopes: partial_lu 10 + schur 20 us over two steps
    assert _read("pair_front_roofline", run) == pytest.approx(
        100 * (flops / 1e9) / (15 * US))
    assert run.notes["pair_front_roofline"]["flops"] == flops
    # a kind the reduction does not know is a step kind: the .step
    # readers the cell lists resolve and read
    assert _read("sweep_device_s.step", run) == pytest.approx(85 * US)
    assert _read("pack_s.step", run) == pytest.approx(50 * US)


@pytest.mark.parametrize("name", NEW[1:])
def test_trace_readers_read_nothing_where_nothing_is(name):
    """No TPU plane, or a program without scopes: None, no error."""
    run = traced(None)
    run.readings["trace"] = None
    assert _read(name, run) is None
    bare = {"host": [h for h in HAND_MADE["host"]
                     if h[1].startswith("bench.")],
            "modules": HAND_MADE["modules"], "inflight": [],
            "ops": [o[:3] + [None] for o in HAND_MADE["ops"]]}
    run = traced(bare)
    run.readings["trace"] = {"span_device_s": {}}
    assert _read(name, run) is None and not run.notes


def ring(factors, solves, steps=0, platform="tpu"):
    snap = {"factor_events": factors, "recent_solves": solves}
    return types.SimpleNamespace(
        rehearse=False, notes={}, device={"platform": platform},
        readings={"refine_steps": [4] * steps},
        slu=types.SimpleNamespace(obs=types.SimpleNamespace(
            HEALTH=types.SimpleNamespace(snapshot=lambda: snap))))


def test_pair_lowering_share_reads_the_programs_ring():
    read = harness.metric_reader("pair_lowering_share").read

    def f(how):
        return {"dtype": "complex64", "complex_lowering": how}

    def s(how, n=5):
        return {"sweeps": {"complex64": n}, "complex_lowering": how}

    run = ring([f("pair")] * 3, [s("pair")] * 3)
    assert read(run) == 100.0
    assert run.notes["complex_lowering"] == {"pair": 18}
    # a gated run: every program placed on the host CPU
    run = ring([f("cpu")] * 3, [s("cpu")] * 3)
    assert read(run) == 0.0
    assert run.notes["complex_lowering"] == {"cpu": 18}
    # native on the accelerator is not pair either
    assert read(ring([f("native")], [s("native")])) == 0.0
    # pair that did not run on the accelerator counts for nothing
    assert read(ring([f("pair")], [s("pair")], platform="cpu")) == 0.0
    # mixed: one factorization and its five sweeps of two were gated
    assert read(ring([f("pair"), f("cpu")],
                     [s("pair"), s("cpu")])) == 50.0
    # only the window's steps count: older records are warm-up's
    run = ring([f("cpu")] * 2 + [f("pair")] * 3,
               [s("cpu")] * 2 + [s("pair")] * 3, steps=3)
    assert read(run) == 100.0
    # a real system's records carry None
    assert read(ring([f(None)], [s(None)])) == 0.0


def test_pair_lowering_share_reads_nothing_where_nothing_is():
    """The parent of the PR that brought the field: records without
    it, or no ring at all: None, no error."""
    read = harness.metric_reader("pair_lowering_share").read
    old_f, old_s = {"dtype": "complex64"}, {"sweeps": {"complex64": 5}}
    for factors, solves in (([], []), ([old_f], [old_s]),
                            ([old_f], []), ([], [old_s])):
        run = ring(factors, solves)
        assert read(run) is None and not run.notes
    bare = ring([], [])
    bare.slu.obs.HEALTH.snapshot = lambda: {}
    assert read(bare) is None


def test_the_cell_is_declared_and_every_reader_is_there():
    """By name, never by place: a later PR appends to these lists.
    The three readers this configuration brought are files the
    harness finds; their entries in `per_layer` wait for a `benchmark`
    PR (PERF.md section 7), so nothing here says where they stand."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "helm2d_n512", "zstep", 1)
    assert "helm2d_n512" in {c["name"] for c in b["configs"]}
    per_layer = {m["name"]: m for m in b["per_layer"]}
    for name in NEW:
        assert hasattr(harness.metric_reader(name), "read")
    # real flops are not this cell's: the real rooflines stay off it
    for name in ("factor_roofline", "dense_front_roofline"):
        assert CELL not in per_layer[name]["workloads"]
    spec = harness.load_cell(CELL)
    assert {m["name"] for m in spec["end_to_end"]} == {"step_s",
                                                       "setup_s"}
    for m in spec["per_layer"]:
        assert hasattr(harness.metric_reader(m["name"]), "read")
