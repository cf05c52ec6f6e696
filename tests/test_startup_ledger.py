"""The start-up ledger (`obs/compile_watch.py`): one row a new program
of the process, split where jax does the work (trace, lower, compile
or persistent-cache load), the plan's and the schedule's phases beside
them, and nothing on the path of a warm call.

Two kinds of test: through jax itself (small jitted programs of this
file, so nothing depends on what the suite compiled before), and on a
private `CompileWatch` fed hand-made events, where the arithmetic
(unions, the cache's events tied by thread and order, the fold past the
cap) is exact."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

import superlu_dist_tpu as slu
from superlu_dist_tpu import flags, obs
from superlu_dist_tpu.obs import compile_watch as cw

WATCH = obs.COMPILE_WATCH
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"
SAVED = "/jax/compilation_cache/compile_time_saved_sec"
# the suite's own directory is placed once, by utils/cache's helper
# (tests/test_cache_place.py pins that); these tests move it for
# their own length and put it back
CACHE_DIR = "jax_compilation_cache_dir"


@pytest.fixture
def cache_dir(tmp_path):
    """jax's persistent cache in a directory of this test's own, every
    program written however small; the suite's own put back after."""
    from jax.experimental.compilation_cache import compilation_cache
    names = (CACHE_DIR,
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in names}
    compilation_cache.reset_cache()
    jax.config.update(names[0], str(tmp_path / "xla"))
    jax.config.update(names[1], 0)
    jax.config.update(names[2], -1)
    try:
        yield str(tmp_path / "xla")
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


@pytest.fixture
def no_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache
    before = getattr(jax.config, CACHE_DIR)
    compilation_cache.reset_cache()
    jax.config.update(CACHE_DIR, None)
    try:
        yield
    finally:
        jax.config.update(CACHE_DIR, before)
        compilation_cache.reset_cache()


def rows(since, name=None):
    return [r for r in WATCH.ledger(since=since)["programs"]
            if name is None or r["name"] == name]


def fresh(tag):
    """A jitted program no other test has compiled: `tag` is in its
    name (the ledger's key) and in its arithmetic (jax's)."""
    def body(x):
        return jnp.tanh(x * float(len(tag))).sum() + len(tag)
    body.__name__ = "ledger_" + tag
    return jax.jit(body)


# -- through jax ------------------------------------------------------

def test_watched_first_call_misses_then_hits_the_cache(cache_dir):
    since = time.perf_counter()
    fn = fresh("persist")
    x = jnp.ones((32, 32))
    obs.watch_jit("ledger_probe", fn)(x).block_until_ready()
    (row,) = rows(since, "ledger_persist")
    assert row["watched"] == "ledger_probe" and row["cache"] == "miss"
    assert row["trace_s"] > 0 and row["lower_s"] > 0
    assert row["compile_s"] > 0 and row["load_s"] == 0.0
    assert row["t0"] >= since and row["thread"] == threading.get_ident()
    assert row["wall_s"] >= row["first_call_other_s"] >= 0.0
    header = WATCH.ledger()["header"]
    assert set(header) >= {
        "cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes", "clock",
        "ledger_self_s", "overflowed"}
    # a second start of the same program: jax forgets, the cache serves
    jax.clear_caches()
    obs.watch_jit("ledger_probe", fn)(x).block_until_ready()
    first, second = rows(since, "ledger_persist")
    assert second["cache"] == "hit" and second["load_s"] > 0
    assert second["compile_s"] == 0.0 and second["trace_s"] > 0
    assert second["t0"] > first["t0"]


def test_without_a_cache_directory_the_cache_is_off(no_cache_dir):
    since = time.perf_counter()
    obs.watch_jit("ledger_probe", fresh("nodir"))(jnp.ones(8))
    (row,) = rows(since, "ledger_nodir")
    assert row["cache"] == "off" and row["compile_s"] > 0
    assert row["load_s"] == 0.0 and row["saved_s"] == 0.0


def test_nested_traces_are_a_union_not_a_sum():
    """Every jitted call inside a traced body fires its own trace
    event inside the outer one's."""
    @jax.jit
    def leaf(x):
        return jnp.sin(x) @ x

    def ledger_nest(x):
        for _ in range(12):
            x = leaf(x) + jnp.linalg.norm(x)
        return x

    spans = []

    def listen(event, start, end, **_kw):
        if event == TRACE:
            spans.append(end - start)

    jax.monitoring.register_event_time_span_listener(listen)
    since = time.perf_counter()
    try:
        t0 = time.perf_counter()
        obs.watch_jit("ledger_probe", jax.jit(ledger_nest))(
            jnp.ones((16, 16)))
        wall = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_time_span_listener(listen)
    (row,) = rows(since, "ledger_nest")
    assert len(spans) > 3
    assert 0 < row["trace_s"] <= wall < sum(spans) + wall
    assert row["trace_s"] < sum(spans)          # the sum counts twice
    assert row["trace_s"] == pytest.approx(max(spans), rel=0.05)
    total = (row["trace_s"] + row["lower_s"] + row["compile_s"]
             + row["load_s"] + row["first_call_other_s"])
    assert total == pytest.approx(row["wall_s"], rel=0.02)


def test_an_eager_operation_leaves_an_unwatched_row():
    since = time.perf_counter()
    x = jnp.arange(7.0)
    jnp.arctan2(x, x + 0.37)            # nothing of the suite runs it
    new = rows(since)
    assert new and all(r["watched"] is None for r in new)
    (row,) = [r for r in new if r["name"] == "arctan2"]
    assert row["compile_s"] + row["load_s"] > 0
    assert "wall_s" not in row


def test_two_threads_compiling_at_once_keep_their_events_apart():
    since = time.perf_counter()
    gate = threading.Barrier(2)
    idents = {}

    def work(tag):
        fn = obs.watch_jit("ledger_" + tag, fresh("thr_" + tag))
        gate.wait(timeout=60)
        idents[tag] = threading.get_ident()
        fn(jnp.ones((64, 64))).block_until_ready()

    threads = [threading.Thread(target=work, args=(t,))
               for t in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    for tag in ("a", "b"):
        (row,) = rows(since, "ledger_thr_" + tag)
        assert row["watched"] == "ledger_" + tag
        assert row["thread"] == idents[tag]
        assert row["trace_s"] > 0 and row["compile_s"] + row["load_s"] > 0
        # its own compile alone: one interval of its kind, inside its
        # own first call
        kinds = [k for k, _a, _b in row["spans"]]
        assert kinds.count("compile") + kinds.count("load") == 1
        for _k, a, b in row["spans"]:
            assert row["t0"] - 0.05 <= a <= b \
                <= row["t0"] + row["wall_s"] + 0.05


def test_warm_calls_add_no_record_and_invoke_no_listener():
    fn = obs.watch_jit("ledger_probe", fresh("warm"))
    x = jnp.ones(16)
    fn(x).block_until_ready()
    before = WATCH.ledger()
    calls = WATCH.snapshot()["calls"]
    for _ in range(100):
        fn(x)
    after = WATCH.ledger()
    assert len(after["programs"]) == len(before["programs"])
    assert after["header"]["listener_calls"] \
        == before["header"]["listener_calls"]
    assert after["header"]["ledger_self_s"] \
        == before["header"]["ledger_self_s"]
    assert WATCH.snapshot()["calls"] == calls + 100


def _lap2d(k=9):
    t = sp.diags([-1.0, 2.3, -1.1], [-1, 0, 1], shape=(k, k))
    return sp.kronsum(t, t, format="csr")


def test_plan_and_schedule_write_their_phases_once_and_a_step_none():
    a = slu.csr_from_scipy(_lap2d())
    since = time.perf_counter()
    st = slu.Stats()
    plan = slu.plan_factorization(a, slu.Options(), stats=st)
    names = [p["name"] for p in WATCH.ledger(since=since)["phases"]]
    assert sorted(names) == sorted(
        p for p in ("EQUIL", "ROWPERM", "COLPERM", "ETREE", "SYMBFACT",
                    "DIST") if st.utime[p] > 0)
    assert {"ETREE", "SYMBFACT", "DIST"} <= set(names)
    for p in WATCH.ledger(since=since)["phases"]:
        assert p["seconds"] == pytest.approx(st.utime[p["name"]])
        assert since <= p["t0"] <= time.perf_counter()
    # the first factorization builds the schedule: one record
    b = np.ones(a.n)
    lu = slu.factorize(a, slu.Options(), plan=plan)
    slu.solve(lu, b)
    names = [p["name"] for p in WATCH.ledger(since=since)["phases"]]
    assert names.count("SCHEDULE") == 1
    # a step on the held plan: no phase, no program
    mark = time.perf_counter()
    lu = slu.factorize(a, slu.Options(), plan=plan)
    x = slu.solve(lu, b)
    step = WATCH.ledger(since=mark)
    assert step["phases"] == [] and step["programs"] == []
    assert np.allclose(_lap2d() @ x, b)


def test_prefactor_writes_its_phase():
    from superlu_dist_tpu.serve import ServeConfig, SolveService
    since = time.perf_counter()
    svc = SolveService(ServeConfig(ladder=(1,)))
    try:
        svc.prefactor(slu.csr_from_scipy(_lap2d(7)), slu.Options())
    finally:
        svc.close()
    phases = WATCH.ledger(since=since)["phases"]
    (pre,) = [p for p in phases if p["name"] == "PREFACTOR"]
    # the key's plan and schedule wrote theirs inside it
    inner = [p for p in phases if p["name"] != "PREFACTOR"]
    assert {"SYMBFACT", "SCHEDULE"} <= {p["name"] for p in inner}
    for p in inner:
        assert pre["t0"] <= p["t0"] \
            and p["t0"] + p["seconds"] <= pre["t0"] + pre["seconds"] + 1e-3


def test_ledger_cuts_by_t0():
    t_a = time.perf_counter()
    obs.watch_jit("ledger_probe", fresh("cut_one"))(jnp.ones(4))
    t_b = time.perf_counter()
    obs.watch_jit("ledger_probe", fresh("cut_two"))(jnp.ones(4))
    t_c = time.perf_counter()
    WATCH.record_phases(t_b, {"LEDGER_CUT": 1e-3})

    def names(**kw):
        led = WATCH.ledger(**kw)
        return ({r["name"] for r in led["programs"]
                 if r["name"].startswith("ledger_cut")},
                {p["name"] for p in led["phases"]})

    assert names(since=t_a, until=t_b) == ({"ledger_cut_one"}, set())
    assert names(since=t_b, until=t_c) == ({"ledger_cut_two"},
                                           {"LEDGER_CUT"})
    assert names(since=t_c) == (set(), set())
    assert names(until=t_a)[0] == set()
    assert names()[0] == {"ledger_cut_one", "ledger_cut_two"}


def test_the_tracers_compile_event_carries_the_split():
    t = obs.configure(enabled=True)
    try:
        obs.watch_jit("ledger_probe", fresh("traced"))(jnp.ones(4))
        (ev,) = [e for e in t.events()
                 if e["name"] == "xla_compile:ledger_probe"]
    finally:
        obs.configure(enabled=False)
    args = ev["args"]
    assert args["trace_s"] > 0 and args["lower_s"] > 0
    assert args["compile_s"] + args["load_s"] > 0
    assert args["cache"] in ("hit", "miss", "off")
    assert args["first_call_other_s"] >= 0 and args["dtypes"]


def test_snapshot_and_report_carry_the_totals():
    obs.watch_jit("ledger_probe", fresh("report"))(jnp.ones(4))
    snap = WATCH.snapshot()
    assert set(snap) == {"calls", "misses", "by_phase", "startup"}
    su = snap["startup"]
    assert su["programs"] >= 1 and su["trace_s"] > 0
    assert su["programs"] >= (su["cache_hits"] + su["cache_misses"]
                              + su["cache_off"])
    # the chip's reading is PERF.md's; here only that the ledger's
    # own seconds are small against what it timed, workers and all
    assert su["ledger_self_s"] < 0.05 * (
        su["trace_s"] + su["lower_s"] + su["compile_s"] + su["load_s"])
    line = [ln for ln in slu.Stats().report().splitlines()
            if "start-up (process)" in ln]
    assert len(line) == 1 and "new programs" in line[0]
    assert "cache load" in line[0]


def test_the_cost_arm_is_gone():
    assert "SLU_OBS_COST" not in flags.FLAGS
    fn = obs.watch_jit("ledger_probe", fresh("nocost"))
    assert not hasattr(cw._WatchedFn, "cost_of")
    with pytest.raises(TypeError):
        obs.watch_jit("ledger_probe", fn, cost_phase="FACT")
    st = slu.Stats()
    assert not hasattr(st, "ops_measured")
    assert "ops_measured" not in st.snapshot()
    st.utime["FACT"] = 2.0
    st.add_ops("FACT", 4e9)
    assert st.gflops("FACT") == pytest.approx(2.0)


# -- on hand-made events ----------------------------------------------

def feed(watch, name, t, *, trace=(), lower=0.0, compile_=0.0,
         cache=None, saved=None):
    """One program's events as jax fires them: nested traces first,
    the outer trace, the lowering, the cache's nameless events inside
    the backend-compile span, the span's end."""
    off = watch._to_perf
    for a, b in trace:
        watch._on_span(TRACE, a - off, b - off, fun_name=name)
    end = max((b for _a, b in trace), default=t)
    if lower:
        watch._on_span(LOWER, end - off, end + lower - off,
                       fun_name=f"jit({name})")
        end += lower
    if cache is not None:
        watch._on_event(REQUEST)
        if cache == "hit":
            watch._on_event(HIT)
            watch._on_duration(SAVED, saved)
    watch._on_span(COMPILE, end - off, end + compile_ - off,
                   fun_name=f"jit({name})")
    return end + compile_


@pytest.fixture
def private(cache_dir):
    """A watch of the test's own, deaf to jax; a cache directory is
    configured, so what a row says of the cache is what it was fed."""
    return cw.CompileWatch()


def test_union_and_the_split_on_hand_made_events(private):
    feed(private, "prog", 10.0,
         trace=[(10.0, 10.4), (10.5, 10.7), (10.0, 11.0)],   # nested
         lower=0.5, compile_=2.0, cache="miss")
    (row,) = private.ledger()["programs"]
    assert row["name"] == "prog" and row["watched"] is None
    assert row["t0"] == pytest.approx(10.0)
    assert row["trace_s"] == pytest.approx(1.0)         # not 1.6
    assert row["lower_s"] == pytest.approx(0.5)
    assert row["compile_s"] == pytest.approx(2.0)
    assert row["load_s"] == 0.0 and row["cache"] == "miss"
    assert [k for k, _a, _b in row["spans"]] == ["trace", "lower",
                                                 "compile"]
    su = private.snapshot()["startup"]
    assert (su["programs"], su["cache_misses"]) == (1, 1)
    assert su["trace_s"] == pytest.approx(1.0)


def test_cache_events_are_tied_by_thread_and_order(private):
    feed(private, "served", 1.0, trace=[(1.0, 1.1)], lower=0.1,
         compile_=0.3, cache="hit", saved=4.5)
    feed(private, "built", 2.0, trace=[(2.0, 2.1)], lower=0.1,
         compile_=0.3, cache="miss")
    feed(private, "uncached", 3.0, trace=[(3.0, 3.1)], lower=0.1,
         compile_=0.3)
    served, built, uncached = private.ledger()["programs"]
    assert (served["cache"], served["load_s"], served["compile_s"],
            served["saved_s"]) == ("hit", pytest.approx(0.3), 0.0, 4.5)
    assert (built["cache"], built["compile_s"], built["load_s"],
            built["saved_s"]) == ("miss", pytest.approx(0.3), 0.0, 0.0)
    assert uncached["cache"] == "off"
    su = private.snapshot()["startup"]
    assert (su["cache_hits"], su["cache_misses"], su["cache_off"]) \
        == (1, 1, 1)
    assert su["load_s"] == pytest.approx(0.3)
    assert su["compile_s"] == pytest.approx(0.6)


def test_a_watched_row_takes_every_event_of_its_call(private):
    """An eager conversion compiled while the body is traced, then the
    program itself: one row, named by its longest lowering or compile,
    cold if any of it was."""
    # an orphan (traced, never compiled) closes as a row of its own
    private._on_span(TRACE, 0.0 - private._to_perf,
                     0.5 - private._to_perf, fun_name="looked_at")
    row = private.open_row("factor")
    feed(private, "convert_element_type", 5.1, trace=[(5.1, 5.2)],
         lower=0.1, compile_=0.1, cache="hit", saved=0.0)
    feed(private, "slu_factor", 5.0, trace=[(5.0, 6.0)], lower=1.0,
         compile_=3.0, cache="miss")
    split = private.close_row(row, 5.0, 5.5)
    assert split["first_call_other_s"] == pytest.approx(0.5)
    orphan, factor = private.ledger()["programs"]
    assert (orphan["name"], orphan["cache"], orphan["watched"]) \
        == ("looked_at", None, None)
    assert orphan["trace_s"] == pytest.approx(0.5)
    assert factor["name"] == "slu_factor"
    assert factor["watched"] == "factor" and factor["cache"] == "miss"
    assert factor["trace_s"] == pytest.approx(1.0)
    assert factor["lower_s"] == pytest.approx(1.1)
    assert factor["compile_s"] == pytest.approx(3.0)
    assert factor["load_s"] == pytest.approx(0.1)
    assert factor["wall_s"] == 5.5
    assert factor["first_call_other_s"] == pytest.approx(0.5)
    assert private._open == {}


def test_rows_past_the_cap_fold_by_name(private, monkeypatch):
    monkeypatch.setattr(cw, "_ROW_CAP", 3)
    t = 0.0
    for i in range(7):
        t = feed(private, "small" if i % 2 else "tiny", t + 1.0,
                 trace=[(t + 1.0, t + 1.25)], lower=0.25,
                 compile_=0.5, cache="miss")
    led = private.ledger()
    assert len(led["programs"]) == 3
    assert led["header"]["overflowed"] is True
    assert led["folded"]["small"]["count"] == 2     # rows 3 and 5
    assert led["folded"]["tiny"]["count"] == 2      # rows 4 and 6
    assert led["folded"]["tiny"]["compile_s"] == pytest.approx(1.0)
    assert private.snapshot()["startup"]["programs"] == 7


def test_phases_are_laid_end_to_end_from_the_builders_start(private):
    private.record_phases(100.0, {"EQUIL": 0.5, "ROWPERM": 0.0,
                                  "COLPERM": 1.5})
    assert private.ledger()["phases"] == [
        {"name": "EQUIL", "t0": 100.0, "seconds": 0.5},
        {"name": "COLPERM", "t0": 100.5, "seconds": 1.5}]
    assert private.ledger(since=100.25)["phases"] == [
        {"name": "COLPERM", "t0": 100.5, "seconds": 1.5}]
