"""obs/ contract tests: span nesting, the Chrome trace-event schema
(ph/ts/dur/pid/tid — Perfetto's loading contract), thread-safety
under the serve micro-batcher, the jit recompile counter's exactly-
one-miss-per-new-signature attribution, and the SLU_OBS=0 no-tax
regression pin (the tracer must be a shared no-op singleton when
off)."""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest
import scipy.sparse as sp

from superlu_dist_tpu import Options, factorize, gssvx, obs, solve
from superlu_dist_tpu.sparse import csr_from_scipy

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))
import trace_export  # noqa: E402


def _testmat(m=12):
    t = sp.diags([-1.0, 2.4, -1.1], [-1, 0, 1], shape=(m, m))
    return csr_from_scipy(sp.kronsum(t, t, format="csr").tocsr())


@pytest.fixture
def traced():
    """Tracer on for the test, off (the ambient default) after."""
    t = obs.configure(enabled=True)
    t.clear()
    yield t
    obs.configure(enabled=False)


def test_span_nesting_and_depth(traced):
    with obs.span("outer"):
        with obs.span("middle"):
            with obs.span("inner"):
                time.sleep(0.001)
    evs = {e["name"]: e for e in traced.events()}
    assert evs["inner"]["args"]["depth"] == 2
    assert evs["middle"]["args"]["depth"] == 1
    assert evs["outer"]["args"]["depth"] == 0
    # X-event nesting is by ts/dur containment per tid (how Perfetto
    # reconstructs the stack): inner ⊆ middle ⊆ outer, same thread
    for child, parent in (("inner", "middle"), ("middle", "outer")):
        c, p = evs[child], evs[parent]
        assert c["tid"] == p["tid"] == threading.get_ident()
        assert p["ts"] <= c["ts"]
        assert c["ts"] + c["dur"] <= p["ts"] + p["dur"]


def test_gssvx_trace_chrome_schema(traced, tmp_path):
    """One traced gssvx solve produces a schema-valid Chrome trace
    with nested spans for every numeric phase and ≥1 compile event
    carrying shape/dtype attribution — the PR's acceptance shape."""
    a = _testmat()
    rng = np.random.default_rng(0)
    xt = rng.standard_normal(a.n)
    gssvx(Options(factor_dtype="float32"), a, a.to_scipy() @ xt)
    path = str(tmp_path / "gssvx.trace.json")
    traced.export_chrome(path)
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    trace_export.validate_events(evs)     # ph/ts/dur/pid/tid pinned
    names = {e["name"] for e in evs}
    for phase in ("EQUIL", "ROWPERM", "COLPERM", "ETREE", "SYMBFACT",
                  "DIST", "FACT", "SOLVE", "REFINE", "gssvx"):
        assert phase in names, phase
    # numeric phases nest INSIDE the gssvx root span
    root = next(e for e in evs if e["name"] == "gssvx")
    fact = next(e for e in evs if e["name"] == "FACT")
    assert fact["args"]["depth"] >= 1
    assert root["ts"] <= fact["ts"]
    assert fact["ts"] + fact["dur"] <= root["ts"] + root["dur"]
    # compile events with attribution (the fresh plan's factor+solve
    # programs are first-called under this trace)
    comp = [e for e in evs if e.get("cat") == "compile"]
    assert comp, "expected >=1 xla_compile event"
    for e in comp:
        assert e["args"]["shapes"], e
        assert e["args"]["dtypes"], e
    # the tool's summary agrees
    s = trace_export.summarize(evs)
    assert s["compile_events"] == len(comp)


def test_trace_export_jsonl_roundtrip(tmp_path):
    """SLU_TRACE_JSONL event log converts to a valid Chrome trace via
    the CLI (`python -m tools.trace_export events.jsonl -o out`)."""
    jl = str(tmp_path / "events.jsonl")
    t = obs.configure(enabled=True, jsonl_path=jl)
    try:
        with obs.span("alpha", args={"k": 1}):
            pass
        obs.instant("beta")
    finally:
        obs.configure(enabled=False)    # closes the jsonl file
    assert t is not None
    out = str(tmp_path / "out.trace.json")
    assert trace_export.main([jl, "-o", out]) == 0
    evs = trace_export.load(out)
    trace_export.validate_events(evs)
    assert {"alpha", "beta"} <= {e["name"] for e in evs}


def test_jsonl_sink_failure_never_throws(tmp_path):
    """Observability must never throw into the instrumented path: a
    broken JSONL sink (unwritable path) disables itself, records the
    error in the snapshot, and the in-memory buffer keeps going."""
    bad = str(tmp_path / "no" / "such" / "dir" / "ev.jsonl")
    t = obs.configure(enabled=True, jsonl_path=bad)
    try:
        with obs.span("gamma"):        # must not raise
            pass
        with obs.span("delta"):
            pass
        snap = t.snapshot()
        assert snap["jsonl_error"] is not None
        assert {"gamma", "delta"} <= set(snap["spans"])
    finally:
        obs.configure(enabled=False)


def test_recompile_counter_nrhs_bucket_jump():
    """The unified compile counter: a repeated signature is a cache
    hit (zero new misses); an nrhs bucket jump is EXACTLY one miss,
    attributed to the new (n, 8) float64 RHS shape."""
    a = _testmat()
    lu = factorize(a, Options(factor_dtype="float64"), backend="jax")
    solve(lu, np.zeros((a.n, 1)))
    before = obs.COMPILE_WATCH.misses("solve")
    solve(lu, np.zeros((a.n, 1)))         # warm signature: no miss
    assert obs.COMPILE_WATCH.misses("solve") == before
    solve(lu, np.zeros((a.n, 8)))         # bucket jump: one miss
    assert obs.COMPILE_WATCH.misses("solve") == before + 1
    ev = [e for e in obs.COMPILE_WATCH.events()
          if e["phase"] == "solve"][-1]
    assert [a.n, 8] in ev["shapes"], ev
    assert "float64" in ev["dtypes"], ev


def test_batcher_spans_thread_safe(traced):
    """Concurrent submits through the serve micro-batcher: the
    queue/assemble/batch_solve stages land in the trace from the
    flusher thread with no torn events (schema stays valid)."""
    from superlu_dist_tpu.serve import MicroBatcher
    a = _testmat(8)
    lu = factorize(a, Options(factor_dtype="float64"), backend="jax")
    mb = MicroBatcher(lu, max_linger_s=0.001, ladder=(1, 4))
    rng = np.random.default_rng(0)
    bs = [rng.standard_normal(a.n) for _ in range(12)]
    futures = []
    fut_lock = threading.Lock()

    def client(b):
        f = mb.submit(b)
        with fut_lock:
            futures.append((b, f))

    threads = [threading.Thread(target=client, args=(b,)) for b in bs]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    for b, f in futures:
        x = f.result(timeout=60)
        r = b - a.to_scipy() @ x
        assert np.linalg.norm(r) / np.linalg.norm(b) < 1e-10
    mb.close()
    evs = traced.events()
    trace_export.validate_events(evs)
    names = {e["name"] for e in evs}
    assert {"serve.queue", "serve.assemble",
            "serve.batch_solve"} <= names
    # serve-stage events come from the flusher thread, not the
    # submitting clients — at least two distinct tids in the trace
    assert len({e["tid"] for e in evs}) >= 2


def test_obs_off_no_tracing_tax():
    """SLU_OBS=0 contract: the disabled path hands back ONE shared
    no-op context manager (no allocation, no lock), so a gssvx solve
    crosses ~10 span sites at sub-µs each — structurally incapable of
    a measurable wall tax.  Pinned by identity, by a generous
    microbench bound, and by a traced-events-stay-empty gssvx run."""
    obs.configure(enabled=False)
    assert obs.get_tracer() is None
    assert obs.span("x") is obs.NULL_SPAN
    assert obs.span("y", args={"k": 1}) is obs.NULL_SPAN
    t0 = time.perf_counter()
    for _ in range(200_000):
        with obs.span("phase"):
            pass
    wall = time.perf_counter() - t0
    assert wall < 2.0, f"disabled span path too slow: {wall:.3f}s"
    # instant/complete are no-ops too
    obs.instant("nothing")
    obs.complete("nothing", 1.0)
    # and a full solve records nothing anywhere
    a = _testmat(8)
    rng = np.random.default_rng(1)
    xt = rng.standard_normal(a.n)
    gssvx(Options(), a, a.to_scipy() @ xt)
    assert obs.get_tracer() is None


def test_registry_snapshot_and_dump(traced):
    """One Registry: stats + serve metrics + compile + health all
    snapshot through obs.snapshot() and flatten into the
    Prometheus-style text dump."""
    reg = obs.Registry()

    class P:
        @staticmethod
        def snapshot():
            return {"a": 1, "b": {"c": 2.5, "flag": True}}

    reg.register("x", P())
    assert reg.snapshot()["x"]["a"] == 1
    txt = reg.dump_text()
    assert "slu_x_a 1" in txt
    assert "slu_x_b_c 2.5" in txt
    assert "slu_x_b_flag 1" in txt
    with pytest.raises(TypeError):
        reg.register("bad", object())

    # the global registry: a solve registers its Stats, the serve
    # Metrics registers/unregisters compare-and-remove
    a = _testmat(8)
    rng = np.random.default_rng(2)
    xt = rng.standard_normal(a.n)
    gssvx(Options(), a, a.to_scipy() @ xt)
    snap = obs.snapshot()
    assert snap["stats"]["utime"]["FACT"] > 0
    assert snap["compile"]["misses"] >= 1
    assert snap["health"]["solves"] >= 1
    assert snap["trace"]["events"] >= 1
    from superlu_dist_tpu.serve import Metrics
    m = Metrics().register_obs("serve_probe")
    m.inc("serve.test_counter")
    assert obs.snapshot()["serve_probe"]["counters"][
        "serve.test_counter"] == 1
    m2 = Metrics().register_obs("serve_probe")   # last wins
    m.unregister_obs("serve_probe")              # not the owner: no-op
    assert obs.REGISTRY.get("serve_probe") is m2
    m2.unregister_obs("serve_probe")
    assert obs.REGISTRY.get("serve_probe") is None


def test_health_monitor_trajectories(traced):
    """Every refined solve leaves a berr trajectory — and, with
    observability on (the ferr norms are two full-array reductions
    per step, gated like the pivot-growth probe), a ferr trajectory —
    and the escalation event fires through gssvx's contract rung."""
    before = obs.HEALTH.snapshot()
    a = _testmat(8)
    rng = np.random.default_rng(3)
    xt = rng.standard_normal(a.n)
    gssvx(Options(factor_dtype="float32"), a, a.to_scipy() @ xt)
    snap = obs.HEALTH.snapshot()
    assert snap["solves"] == before["solves"] + 1
    last = snap["last_solve"]
    assert last is not None
    assert len(last["berr_trajectory"]) == last["steps"] + 1
    assert len(last["ferr_trajectory"]) == last["steps"]
    assert last["berr"] == pytest.approx(snap["last_berr"])
    # trajectories are monotone-improving for this well-conditioned
    # system (the loop keeps only improving iterates)
    bt = last["berr_trajectory"]
    assert bt[-1] <= bt[0]


# --------------------------------------------------------------------
# the profiler sink: a live jax.profiler session is the switch
# --------------------------------------------------------------------

def _slu_events(trace_dir):
    """(line, name, start_ns, end_ns, stats) of every `slu.*` host
    event in the session's .xplane.pb; a line is one thread."""
    import glob
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                     "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("slu."):
                    out.append(((plane.name, li), ev.name,
                                int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns),
                                dict(ev.stats)))
    return out


def _session(tmp_path_factory, name):
    import jax
    d = str(tmp_path_factory.mktemp(name))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    return d


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One profiler session on the CPU over a refactorization, two
    solves and a served batch; and the same solve outside it."""
    import jax
    from superlu_dist_tpu import Stats, plan_factorization
    from superlu_dist_tpu.serve import MicroBatcher
    obs.configure(enabled=False)
    a = _testmat()
    opts = Options(factor_dtype="float32")
    plan = plan_factorization(a, opts)
    b = a.to_scipy() @ np.random.default_rng(5).standard_normal(a.n)
    x_plain = solve(factorize(a, opts, plan=plan), b)
    assert obs.span("x") is obs.NULL_SPAN
    d = _session(tmp_path_factory, "prof")
    try:
        st = Stats()
        lu = factorize(a, opts, plan=plan, stats=st)
        xs = [solve(lu, b, stats=st), solve(lu, b, stats=st)]
        steps = st.refine_steps     # the batcher's solve counts on
        with obs.span("test.caller"):
            mb = MicroBatcher(lu, max_linger_s=0.001, ladder=(1, 4))
            x_served = mb.submit(b).result(timeout=60)
            mb.close()
    finally:
        jax.profiler.stop_trace()
    return {"events": _slu_events(d), "refine_steps": steps, "xs": xs,
            "x_plain": x_plain, "x_served": x_served}


def _inside(events, child, parent):
    """Every `child` event lies inside a `parent` event of its line."""
    kids = [e for e in events if e[1] == child]
    assert kids, child
    for line, _, s, e, _ in kids:
        assert any(pl == line and ps <= s and e <= pe
                   for pl, pn, ps, pe, _ in events if pn == parent), \
            (child, parent)


def test_profiler_session_solve_spans(profiled):
    """(a) factorize + two solves under a session: the phases and the
    new leaves are in the .xplane.pb, the pack exactly once and
    inside the factorization (both solves hit), one REFINE_STEP a
    counted step, children inside their parents."""
    evs = profiled["events"]
    count = {}
    for _, name, *_ in evs:
        count[name] = count.get(name, 0) + 1
    for name in ("slu.FACT", "slu.SOLVE", "slu.REFINE",
                 "slu.solve.sweep", "slu.solve.fetch",
                 "slu.REFINE_STEP", "slu.refine.residual"):
        assert count.get(name), name
    assert count["slu.solve.pack"] == 1
    steps = profiled["refine_steps"]
    assert steps > 0
    # the caller's two solves; the batcher's are not theirs
    caller = next(e[0] for e in evs if e[1] == "slu.test.caller")
    mine = [e for e in evs if e[0] == caller
            and not any(c[1] == "slu.test.caller"
                        and c[2] <= e[2] and e[3] <= c[3]
                        for c in evs)]
    assert sum(e[1] == "slu.REFINE_STEP" for e in mine) == steps
    # a residual before the loop and one a step, for each solve
    assert sum(e[1] == "slu.refine.residual" for e in mine) == steps + 2
    _inside(evs, "slu.solve.pack", "slu.FACT")
    _inside(evs, "slu.solve.fetch", "slu.solve.sweep")
    _inside(evs, "slu.REFINE_STEP", "slu.REFINE")
    _inside(evs, "slu.refine.residual", "slu.REFINE")
    # the pack is dispatched by the factorization, before any sweep
    (pack,) = [e for e in evs if e[1] == "slu.solve.pack"]
    assert pack[4]["at"] == "factor"
    for e in evs:
        if e[1] == "slu.solve.sweep" and e[0] == pack[0]:
            assert e[2] >= pack[3]
    sweep = next(e for e in evs if e[1] == "slu.solve.sweep")
    assert sweep[4]["nrhs"] == 1 and sweep[4]["trans"] == 0
    assert pack[4]["groups"] >= 1


def test_profiler_session_flusher_spans(profiled):
    """(b) a served request: wait, batch (with its `batch` stat) and
    its three stages on ONE thread that is not the caller's."""
    evs = profiled["events"]
    caller = next(e[0] for e in evs if e[1] == "slu.test.caller")
    names = ("slu.serve.wait", "slu.serve.batch", "slu.serve.assemble",
             "slu.serve.batch_solve", "slu.serve.fanout")
    lines = {e[0] for e in evs if e[1] in names}
    assert {e[1] for e in evs} >= set(names)
    assert len(lines) == 1 and caller not in lines
    batch = next(e for e in evs if e[1] == "slu.serve.batch")
    assert batch[4]["live"] == 1 and batch[4]["bucket"] == 1
    assert isinstance(batch[4]["batch"], int)
    for stage in names[2:]:
        _inside(evs, stage, "slu.serve.batch")
    # the solve the flusher runs names its own phases there too
    (flusher,) = lines
    assert any(e[0] == flusher and e[1] == "slu.solve.sweep"
               for e in evs)


def test_profiler_session_is_the_switch(tmp_path_factory):
    """(c) the session is the only switch: inside it a span is the
    profiler's annotation, before and after it the shared no-op, and
    the no-op path keeps the bound this file gives it."""
    import jax
    obs.configure(enabled=False)
    assert obs.span("x") is obs.NULL_SPAN
    _session(tmp_path_factory, "switch")
    try:
        assert obs.span("x") is not obs.NULL_SPAN
        assert not obs.enabled()        # SLU_OBS gates stay off
    finally:
        jax.profiler.stop_trace()
    assert obs.span("x") is obs.NULL_SPAN
    assert obs.span("y", args={"k": 1}) is obs.NULL_SPAN
    t0 = time.perf_counter()
    for _ in range(200_000):
        with obs.span("phase"):
            pass
    wall = time.perf_counter() - t0
    assert wall < 2.0, f"disabled span path too slow: {wall:.3f}s"


def test_profiler_session_both_sinks(tmp_path_factory):
    """SLU_OBS tracer and a session together: one call site writes
    both, with the tracer's own names."""
    import jax
    t = obs.configure(enabled=True)
    d = _session(tmp_path_factory, "both")
    try:
        with obs.span("outer", args={"k": 2}):
            obs.instant("mark")
            obs.complete("late", 0.001)
    finally:
        jax.profiler.stop_trace()
        obs.configure(enabled=False)
    assert {e["name"] for e in t.events()} == {"outer", "mark", "late"}
    prof = {e[1]: e for e in _slu_events(d)}
    # a retrospective span cannot be written into the profiler
    assert set(prof) == {"slu.outer", "slu.mark"}
    assert prof["slu.outer"][4]["k"] == 2


def test_profiler_session_changes_no_answer(profiled):
    """(d) bit-identical answers with and without a session (the
    ferr trajectory, which costs work, stays SLU_OBS's)."""
    for x in profiled["xs"]:
        assert np.array_equal(x, profiled["x_plain"])
    assert np.array_equal(np.asarray(profiled["x_served"]),
                          profiled["x_plain"])


_SCOPES = {"factor": ("slu.assemble", "slu.extend_add",
                      "slu.partial_lu", "slu.tri_inverse",
                      "slu.schur", "slu.store"),
           "solve": ("slu.fwd", "slu.bwd", "slu.lsum"),
           "resid": ("slu.resid",)}


@pytest.fixture(scope="module")
def lowered_op_names():
    """op_name metadata of the lowered factor, packed-solve, pack
    and device-SpMV programs, by program."""
    import re
    import jax
    import jax.numpy as jnp
    from superlu_dist_tpu.ops import batched, spmv, trisolve
    from superlu_dist_tpu.utils.testmat import laplacian_3d
    a = laplacian_3d(6)
    d = factorize(a, Options(factor_dtype="float32"),
                  backend="jax").device_lu
    factor_fn, _ = batched._phase_fns(
        d.schedule, d.dtype, batched._thresh_for(d.plan, d.dtype))
    solve_fn = trisolve._solve_packed_fn(d.schedule, d.dtype, False)[0]
    texts = {
        "factor": factor_fn.lower(
            jnp.zeros(len(d.plan.coo_rows), jnp.float32)),
        "solve": solve_fn.lower(trisolve.get_packs(d),
                                jnp.zeros((a.n, 1), jnp.float32)),
        "pack": trisolve._pack_fn(d.schedule).lower(
            (d.L_flat, d.U_flat, d.Li_flat, d.Ui_flat)),
        "resid": jax.jit(spmv.ell_spmv).lower(
            jnp.zeros((8, 3), jnp.int32), jnp.zeros((8, 3)),
            jnp.zeros(8)),
    }
    return {k: (set(re.findall(r"slu\.[a-z_]+",
                               low.as_text(debug_info=True))),
                re.search(r"module @(\w+)", low.as_text()).group(1))
            for k, low in texts.items()}


@pytest.mark.parametrize(
    "program,scope",
    [(p, s) for p, scopes in _SCOPES.items() for s in scopes])
def test_kernel_scope_in_lowered_program(lowered_op_names, program,
                                         scope):
    """(e) every name of the kernels' vocabulary is in the op_name
    metadata of the program that runs it — and nothing outside the
    vocabulary is."""
    names, _ = lowered_op_names[program]
    assert scope in names
    assert names <= {s for v in _SCOPES.values() for s in v}


def test_watched_programs_are_named(lowered_op_names):
    """The programs' own names key the persistent compile cache,
    which the scopes do not: a stable `slu_*` name each."""
    assert lowered_op_names["factor"][1] == "jit_slu_factor"
    assert lowered_op_names["solve"][1] == "jit_slu_solve_packed"
    assert lowered_op_names["pack"][1] == "jit_slu_pack"
