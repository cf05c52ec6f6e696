"""The pack program (`jit_slu_pack`): the miss path of
`ops/trisolve.get_packs` dispatches ONE device program a
factorization, whatever the handle's storage form.

Pinned here: its PackSet equals the op-by-op reference
(`pack_panels` / `pack_panels_staged` called outside any trace) leaf
for leaf in value, shape and dtype; a solve through it is
bit-identical to one through the reference packs; a refactorization
on a held plan hits the compiled program; `slu.solve.pack` opens on
the miss only and says how many programs it dispatched."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from superlu_dist_tpu import Options, factorize, obs, solve
from superlu_dist_tpu.obs.compile_watch import COMPILE_WATCH
from superlu_dist_tpu.ops import batched, trisolve
from superlu_dist_tpu.plan.plan import plan_factorization
from superlu_dist_tpu.utils.testmat import helmholtz_2d, laplacian_3d

# storage form -> (matrix, factor dtype, environment)
_FORMS = {
    "flats_f32": (lambda: laplacian_3d(6), "float32", {}),
    "pair_planes": (lambda: helmholtz_2d(6), "complex128",
                    {"SLU_COMPLEX_PAIR": "1"}),
    "staged_panels": (lambda: laplacian_3d(6), "float32",
                      {"SLU_STAGED": "1"}),
}


def _handle(monkeypatch, form):
    mk, dtype, env = _FORMS[form]
    monkeypatch.setenv("SLU_TRISOLVE", "merged")
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    a = mk()
    d = factorize(a, Options(factor_dtype=dtype),
                  backend="jax").device_lu
    staged = isinstance(d, batched.StagedLU)
    assert staged == (form == "staged_panels")
    assert batched._lu_is_pair(d) == (form == "pair_planes")
    return a, d


def _reference_packs(d):
    """The packs sliced op by op, outside any trace."""
    ts = trisolve.get_trisolve(d.schedule)
    if isinstance(d, batched.StagedLU):
        return trisolve.PackSet(
            trisolve.pack_panels_staged(ts, d.panels))
    return trisolve.PackSet(trisolve.pack_panels(
        ts, (d.L_flat, d.U_flat, d.Li_flat, d.Ui_flat)))


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
            lu = factorize(a, opts, plan=plan, backend="jax")
            solve(lu, b)                    # miss
            solve(lu, b)                    # hit
            trisolve.get_packs(lu.device_lu)    # hit
        spans = [e for e in t.events() if e["name"] == "solve.pack"]
    finally:
        obs.configure(enabled=False)
    assert len(spans) == 2
    for e in spans:
        assert e["args"]["programs"] == 1
        assert e["args"]["groups"] == len(lu.device_lu.schedule.groups)


@pytest.fixture(scope="module")
def v5e_chip():
    """One described (not attached) v5e chip: the TPU's compiler runs
    here without the device."""
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


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
    largest bucket: two children of 3,072 padded rows, read at a slab
    stride that is no multiple of a tile (one child at another, so
    the read is a switch), into a front of 6,144 x 6,144 (wb 512).
    The TPU's compiler keeps it a loop of slices, row gathers and
    transposes: scratch of a few fronts, code of a megabyte or two.
    (The element lane's body for this bucket compiles in 20 s to
    7 MB of code; a reshape hoisted over the slice, as in the pack
    program's trap above, would show as scratch of the slab's size.)"""
    rc_b, mb, K, strides = 3072, 6144, 2, (2816, 3584)
    meta = ((rc_b, rc_b, K, 0, strides),)

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
