"""The pack program (`jit_slu_pack`): the miss path of
`ops/trisolve.get_packs` dispatches ONE device program a
factorization, whatever the handle's storage form.

Pinned here: its PackSet equals the op-by-op reference
(`pack_panels` / `pack_panels_staged` called outside any trace) leaf
for leaf in value, shape and dtype; a solve through it is
bit-identical to one through the reference packs; a refactorization
on a held plan hits the compiled program; `slu.solve.pack` opens on
the miss only and says how many programs it dispatched and where.

Under the merged sweep the miss is the factorization's own
(`ops/batched.factorize_device` dispatches the pack on the factor
program's output futures, before its blocking read): the handle comes
back holding its packs, the span lies inside `FACT` with `at ==
"factor"`, the first solve finds a hit, the packs and every answer
are bitwise those of the lazy path (a handle stripped of its packs,
which packs at its first solve), the legacy sweep dispatches nothing,
a singular input still raises and leaves nothing behind, and
`Stats.packs`, `Stats.report()` and the health ring say where."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from superlu_dist_tpu import Options, Stats, YesNo, factorize, obs, solve
from superlu_dist_tpu.obs.compile_watch import COMPILE_WATCH
from superlu_dist_tpu.ops import batched, trisolve
from superlu_dist_tpu.plan.plan import plan_factorization
from superlu_dist_tpu.utils.testmat import helmholtz_2d, laplacian_3d

from test_review_fixes import _singular_matrix

# storage form -> (matrix, factor dtype, environment)
_FORMS = {
    "flats_f32": (lambda: laplacian_3d(6), "float32", {}),
    "pair_planes": (lambda: helmholtz_2d(6), "complex128",
                    {"SLU_COMPLEX_PAIR": "1"}),
    "staged_panels": (lambda: laplacian_3d(6), "float32",
                      {"SLU_STAGED": "1"}),
}


def _factorize(monkeypatch, form, stats=None, arm="merged"):
    mk, dtype, env = _FORMS[form]
    monkeypatch.setenv("SLU_TRISOLVE", arm)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    a = mk()
    return a, factorize(a, Options(factor_dtype=dtype), stats=stats,
                        backend="jax")


def _handle(monkeypatch, form):
    a, lu = _factorize(monkeypatch, form)
    d = lu.device_lu
    staged = isinstance(d, batched.StagedLU)
    assert staged == (form == "staged_panels")
    assert batched._lu_is_pair(d) == (form == "pair_planes")
    return a, d


def _store(d):
    if isinstance(d, batched.StagedLU):
        return d.panels
    return (d.L_flat, d.U_flat, d.Li_flat, d.Ui_flat)


def _rhs(a, form, seed=3):
    rng = np.random.default_rng(seed)
    b = rng.standard_normal(a.n)
    if form == "pair_planes":
        b = b + 1j * rng.standard_normal(a.n)
    return b


def _inside(e, parent):
    """A tracer span within another, on one thread."""
    return (e["tid"] == parent["tid"] and parent["ts"] <= e["ts"]
            and e["ts"] + e["dur"] <= parent["ts"] + parent["dur"])


def _reference_packs(d):
    """The packs sliced op by op, outside any trace."""
    ts = trisolve.get_trisolve(d.schedule)
    cut = (trisolve.pack_panels_staged if isinstance(d, batched.StagedLU)
           else trisolve.pack_panels)
    return trisolve.PackSet(cut(ts, _store(d)))


@pytest.mark.parametrize("form", list(_FORMS))
def test_pack_program_equals_reference(monkeypatch, form):
    _, d = _handle(monkeypatch, form)
    packs, ref = trisolve.get_packs(d), _reference_packs(d)
    assert isinstance(packs, trisolve.PackSet)
    assert (jax.tree_util.tree_structure(packs)
            == jax.tree_util.tree_structure(ref))
    got, want = (jax.tree_util.tree_leaves(p) for p in (packs, ref))
    assert len(got) == len(want) >= 4 * len(d.schedule.groups)
    for x, y in zip(got, want):
        assert x.shape == y.shape and x.dtype == y.dtype
        assert np.array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("form", list(_FORMS))
def test_solve_through_pack_program_bit_identical(monkeypatch, form):
    a, d = _handle(monkeypatch, form)
    pair = batched._lu_is_pair(d)
    rng = np.random.default_rng(7)
    b = rng.standard_normal((a.n, 2))
    if pair:
        b = batched._pair_encode_rhs(
            (b + 1j * rng.standard_normal((a.n, 2)))
            .astype(np.complex128))
    b = jnp.asarray(b)
    if isinstance(d, batched.StagedLU):
        ts = trisolve.get_trisolve(d.schedule)

        def run(packs):
            return trisolve.staged_sweeps(ts, packs, b, d.dtype,
                                          False, pair=pair)
    else:
        fn = trisolve._solve_packed_fn(d.schedule, d.dtype, pair)[0]

        def run(packs):
            return fn(packs, b)
    x_ref = np.asarray(run(_reference_packs(d)))
    x = np.asarray(run(trisolve.get_packs(d)))
    assert np.isfinite(x).all() and np.array_equal(x, x_ref)


def test_refactorization_on_held_plan_compiles_pack_once(monkeypatch):
    monkeypatch.setenv("SLU_TRISOLVE", "merged")
    a = laplacian_3d(6)
    opts = Options(factor_dtype="float32")
    plan = plan_factorization(a, opts)
    b = np.random.default_rng(1).standard_normal(a.n)
    before = COMPILE_WATCH.misses("pack")
    handles = []
    for _ in range(2):
        lu = factorize(a, opts, plan=plan, backend="jax")
        # the factorization took the miss: the solve finds a hit
        assert COMPILE_WATCH.misses("pack") - before == 1
        solve(lu, b)
        handles.append(lu.device_lu)
    d0, d1 = handles
    assert d0 is not d1 and d0.schedule is d1.schedule
    assert trisolve.get_packs(d0) is not trisolve.get_packs(d1)
    assert COMPILE_WATCH.misses("pack") - before == 1
    fn = trisolve._pack_fn(d0.schedule)
    assert fn._cache_size() == 1


def test_pack_span_opens_on_the_miss_only(monkeypatch):
    monkeypatch.setenv("SLU_TRISOLVE", "merged")
    a = laplacian_3d(6)
    opts = Options(factor_dtype="float32")
    plan = plan_factorization(a, opts)
    b = np.random.default_rng(2).standard_normal(a.n)
    t = obs.configure(enabled=True)
    t.clear()
    try:
        for _ in range(2):                  # two factorizations
            lu = factorize(a, opts, plan=plan, backend="jax")   # miss
            solve(lu, b)                    # hit
            solve(lu, b)                    # hit
            trisolve.get_packs(lu.device_lu)    # hit
        events = t.events()
    finally:
        obs.configure(enabled=False)
    spans = [e for e in events if e["name"] == "solve.pack"]
    facts = [e for e in events if e["name"] == "FACT"]
    assert len(spans) == len(facts) == 2
    for e, f in zip(spans, facts):
        assert e["args"]["programs"] == 1
        assert e["args"]["groups"] == len(lu.device_lu.schedule.groups)
        assert e["args"]["at"] == "factor" and _inside(e, f)
    assert not any(_inside(e, p) for e in spans for p in events
                   if p["name"] == "SOLVE")


# -- the pack is the factorization's own ------------------------------

@pytest.mark.parametrize("form", list(_FORMS))
def test_factorization_hands_back_its_packs(monkeypatch, form):
    """The handle holds its packs when `factorize` returns; the span
    opens once a factorization, inside FACT, and no solve opens one."""
    t = obs.configure(enabled=True)
    t.clear()
    try:
        a, lu = _factorize(monkeypatch, form)
        d = lu.device_lu
        held = getattr(d, "_trisolve_packs", None)
        assert held is not None and isinstance(held[1], trisolve.PackSet)
        assert type(d.tiny_pivots) is int
        after_fact = list(t.events())
        for _ in range(2):
            solve(lu, _rhs(a, form))
        assert trisolve.get_packs(d) is held[1]
        events = t.events()
    finally:
        obs.configure(enabled=False)
    (pack,) = [e for e in events if e["name"] == "solve.pack"]
    (fact,) = [e for e in events if e["name"] == "FACT"]
    assert pack in after_fact and _inside(pack, fact)
    assert pack["args"]["at"] == "factor"
    assert pack["args"]["programs"] == 1
    assert sum(e["name"] == "SOLVE" for e in events) == 2


@pytest.mark.parametrize("form", list(_FORMS))
def test_packs_at_factorization_equal_pack_device_after(monkeypatch, form):
    """Dispatched on the factor program's futures or on its finished
    arrays, the pack is the same program on the same values."""
    _, lu = _factorize(monkeypatch, form)
    d = lu.device_lu
    early = d._trisolve_packs[1]
    late = trisolve.pack_device(d.schedule, _store(d))
    assert late is not early
    assert (jax.tree_util.tree_structure(early)
            == jax.tree_util.tree_structure(late))
    got, want = (jax.tree_util.tree_leaves(p) for p in (early, late))
    assert len(got) == len(want) >= 4 * len(d.schedule.groups)
    for x, y in zip(got, want):
        assert x.shape == y.shape and x.dtype == y.dtype
        assert np.array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("form", list(_FORMS))
def test_answers_bitwise_the_lazy_paths(monkeypatch, form):
    """A handle stripped of its packs is the parent's: its first solve
    takes the miss (`at_solve`, in its Stats and in its record of the
    health ring) and answers bit for bit what the eager handle does."""
    a, eager = _factorize(monkeypatch, form)
    _, lazy = _factorize(monkeypatch, form)
    del lazy.device_lu._trisolve_packs
    b = _rhs(a, form)
    st_e, st_l = Stats(), Stats()
    t = obs.configure(enabled=True)
    t.clear()
    try:
        x_e = solve(eager, b, stats=st_e)
        x_l = solve(lazy, b, stats=st_l)
        solve(lazy, b, stats=st_l)          # a hit by now
        (pack,) = [e for e in t.events() if e["name"] == "solve.pack"]
        solves = [e for e in t.events() if e["name"] == "SOLVE"]
    finally:
        obs.configure(enabled=False)
    assert np.isfinite(x_e).all() and np.array_equal(x_e, x_l)
    assert pack["args"]["at"] == "solve" and _inside(pack, solves[1])
    assert st_e.packs == {"at_factor": 0, "at_solve": 0}
    assert st_l.packs == {"at_factor": 0, "at_solve": 1}
    assert eager.factor_record["pack"] == "at_factor"
    assert lazy.factor_record["pack"] == "at_solve"
    # `lazy` was the newest factorization: the ring's own copy says so
    assert obs.HEALTH.snapshot()["last_factor"]["pack"] == "at_solve"


@pytest.mark.parametrize("form", ["flats_f32", "staged_panels"])
def test_legacy_sweep_dispatches_no_pack(monkeypatch, form):
    st = Stats()
    t = obs.configure(enabled=True)
    t.clear()
    try:
        a, lu = _factorize(monkeypatch, form, stats=st, arm="legacy")
        assert not hasattr(lu.device_lu, "_trisolve_packs")
        x = solve(lu, _rhs(a, form), stats=st)
        assert not hasattr(lu.device_lu, "_trisolve_packs")
        spans = [e for e in t.events() if e["name"] == "solve.pack"]
    finally:
        obs.configure(enabled=False)
    assert np.isfinite(x).all() and not spans
    assert st.packs == {"at_factor": 0, "at_solve": 0}
    assert "packs dispatched" not in st.report()
    assert lu.factor_record["pack"] == "none"
    assert obs.HEALTH.snapshot()["last_factor"]["pack"] == "none"


@pytest.mark.parametrize("staged", ["0", "1"], ids=["flats", "staged"])
def test_singular_input_raises_and_leaves_nothing(monkeypatch, staged):
    """Two identical rows, pivot replacement off: an exactly-zero
    pivot.  `nzero` is read after the pack was dispatched: the raise drops
    the handle with its packs, the thread's stamp, and writes no
    record."""
    monkeypatch.setenv("SLU_TRISOLVE", "merged")
    monkeypatch.setenv("SLU_STAGED", staged)
    opts = Options(replace_tiny_pivot=YesNo.NO, equil=YesNo.NO)
    st = Stats()
    n_before = obs.HEALTH.snapshot()["factorizations"]
    with pytest.raises(ZeroDivisionError):
        factorize(_singular_matrix(), opts, stats=st, backend="jax")
    assert obs.take_cost("pack") is None
    assert st.packs == {"at_factor": 0, "at_solve": 0}
    assert not st.factor_events
    assert obs.HEALTH.snapshot()["factorizations"] == n_before


@pytest.mark.parametrize("form", ["flats_f32", "staged_panels"])
def test_counters_say_at_factor(monkeypatch, form):
    st = Stats()
    a, lu = _factorize(monkeypatch, form, stats=st)
    for _ in range(2):
        solve(lu, _rhs(a, form), stats=st)
    assert st.packs == {"at_factor": 1, "at_solve": 0}
    assert st.snapshot()["packs"] == st.packs
    assert "packs dispatched:     1 at factor, 0 at solve" in st.report()
    snap = obs.HEALTH.snapshot()
    assert snap["last_factor"]["pack"] == "at_factor"
    assert snap["factor_events"][-1]["pack"] == "at_factor"
    # a second factorization under the same Stats counts once more
    _, lu2 = _factorize(monkeypatch, form, stats=st)
    assert st.packs == {"at_factor": 2, "at_solve": 0}
    assert lu2.factor_record is not lu.factor_record


@pytest.fixture(scope="module")
def v5e_host():
    """One described (not attached) host of four v5e chips: the TPU's
    compiler runs here without the devices."""
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def v5e_chip(v5e_host):
    """One chip of it."""
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_host.devices[0])


def test_pack_program_compiles_small_for_v5e(v5e_chip, monkeypatch):
    """At the benchmark's size (n=27,000, 62 groups) the TPU's
    compiler keeps the pack a program of slices and copies: no
    scratch, and code far under the flats it reads.  Without the
    barrier in `_pack_fn` it reshapes the whole flat once a group
    (1.1 GB of scratch, 220 MB of code, 75 s)."""
    monkeypatch.setenv("SLU_TRISOLVE", "merged")
    plan = plan_factorization(laplacian_3d(30),
                              Options(factor_dtype="float32"))
    sched = batched.get_schedule(plan, 1)
    flats = tuple(
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=v5e_chip)
        for n in (sched.L_total, sched.U_total, sched.Li_total,
                  sched.Ui_total))
    fn = trisolve._pack_fn(sched)
    # an executable for a described chip cannot be read back from the
    # persistent cache: keep it out
    on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        mem = fn.lower(flats).compile().memory_analysis()
    finally:
        jax.config.update("jax_enable_compilation_cache", on)
    assert mem.temp_size_in_bytes < 2 ** 20
    assert mem.generated_code_size_in_bytes < 32 * 2 ** 20
    assert mem.output_size_in_bytes <= mem.argument_size_in_bytes


def test_row_lane_body_compiles_small_for_v5e(v5e_chip):
    """The extend-add row lane (`_ea_add_rows`) at the benchmark's
    largest bucket: two children of 3,072 padded rows, each the one
    slot of a slab whose stride is no multiple of a tile (two
    sources, so two waves of one child), into a front of
    6,144 x 6,144 (wb 512).  The TPU's compiler keeps it slices, row
    gathers and transposes: scratch of a few fronts, code of a
    megabyte or two.
    (The element lane's body for this bucket compiles in 20 s to
    7 MB of code; a reshape hoisted over the slice, as in the pack
    program's trap above, would show as scratch of the slab's size.)"""
    rc_b, mb, K, strides = 3072, 6144, 2, (2816, 3584)
    meta = ((rc_b, rc_b, K, 0,
             tuple((1, 1, (i * 10_000_000, 1, s, s))
                   for i, s in enumerate(strides))),)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)

    blocks = ((sds((K,), jnp.int32), sds((K,), jnp.int32),
               sds((K,), jnp.int32), sds((K, mb), jnp.int32),
               sds((K, mb), jnp.int32)),)
    fn = jax.jit(lambda F, u, b: batched._ea_add(
        F, u, b, meta, mb=mb, n_pad=1), donate_argnums=0)
    on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        mem = fn.lower(sds((mb * mb,), jnp.float32),
                       sds((150_000_000,), jnp.float32),
                       blocks).compile().memory_analysis()
    finally:
        jax.config.update("jax_enable_compilation_cache", on)
    front = 4 * mb * mb
    assert mem.temp_size_in_bytes < 3 * front
    assert mem.generated_code_size_in_bytes < 4 * 2 ** 20


def test_mesh_sweep_compiles_small_for_v5e_host(v5e_host):
    """The mesh's narrow-rhs sweep (`make_dist_solve_merged`) at the
    grid cell's size (n=27,000, 62 groups on a 2x2 grid) cuts its
    panels behind the pack program's fence (`trisolve.pack_flats`):
    scratch of about one copy of a device's factors, where the cut
    inside the trace (`pack_panels`) reshapes the whole flat once a
    group (2.5 GB of scratch, 113 MB of code, 27 s); and its sync
    points all-reduce each slot at most once (1.76 MB a sweep where
    whole buffers at every boundary are 65 MB)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from superlu_dist_tpu.parallel import factor_dist
    from superlu_dist_tpu.utils.stats import hlo_collective_stats
    mesh = Mesh(np.array(v5e_host.devices).reshape(2, 2, 1),
                ("r", "c", "z"))
    plan = plan_factorization(laplacian_3d(30),
                              Options(factor_dtype="float32"))
    sched = batched.get_schedule(plan, 4)
    ts = trisolve.get_trisolve(sched)
    sharded = NamedSharding(mesh, P(("r", "c", "z")))
    flats = tuple(
        jax.ShapeDtypeStruct((4 * n,), jnp.float32, sharding=sharded)
        for n in (sched.L_total, sched.U_total, sched.Li_total,
                  sched.Ui_total))
    b = jax.ShapeDtypeStruct((plan.n, 1), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    fn = factor_dist.make_dist_solve_merged(plan, mesh,
                                            dtype=np.float32)
    on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = fn.lower(*flats, b).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", on)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.25 * mem.argument_size_in_bytes
    assert mem.generated_code_size_in_bytes < 80 * 2 ** 20
    ar = hlo_collective_stats(compiled.as_text())["all-reduce"]
    assert ar["count"] == trisolve.mesh_sync_count(ts)
    assert ar["bytes"] <= (ts.u_total + ts.y_total) * 4
