"""AOT executable persistence (resilience/aot.py, ISSUE 12 and 39):
the whole-phase jits serialize via jax.export keyed by the package's
sources + a layout + dtype + merge-flag fingerprint; a fresh process
deserializes instead of re-tracing and its backend compile rides the
persistent compilation cache.  The store is on exactly where such a
cache is kept, in its `slu_aot` sub-directory.  Pinned here: the rule,
the save/load verification envelope (sha frame, fingerprint refusal
with the TYPED AotMismatch, quarantine), what the key sees, bitwise
identity of AOT-served programs, that a served program keeps its name
and its `slu.` scopes and builds nothing only a trace needs, and the
start-up ledger's `aot` field."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

import superlu_dist_tpu as slu
from superlu_dist_tpu import Options
from superlu_dist_tpu.obs import COMPILE_WATCH
from superlu_dist_tpu.ops import batched as B
from superlu_dist_tpu.ops import trisolve as T
from superlu_dist_tpu.plan.plan import plan_factorization
from superlu_dist_tpu.resilience import aot
from superlu_dist_tpu.sparse import csr_from_scipy


@pytest.fixture(autouse=True)
def _fresh_stats():
    aot.reset_stats()
    yield
    aot.reset_stats()


@pytest.fixture
def store(aot_store):
    return aot_store


@pytest.fixture
def no_cache():
    """No persistent cache in force: jax's own switch (a process with
    no cache DIRECTORY is `test_a_process_follows_its_cache`'s)."""
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def _testmat(m=30):
    t = sp.diags([-1.0, 2.3, -1.1], [-1, 0, 1], shape=(m, m))
    return csr_from_scipy(sp.kronsum(t, t, format="csr").tocsr())


def _export_of(fn, *avals):
    from jax import export as jax_export
    return jax_export.export(jax.jit(fn))(*avals)


# --------------------------------------------------------------------
# store discipline
# --------------------------------------------------------------------

def test_the_rule_is_the_compile_cache(store, tmp_path):
    """On exactly when a persistent compile cache is in force, in its
    `slu_aot` sub-directory (which jax's eviction, a glob of `*-cache`
    in the directory itself, neither counts nor clears)."""
    assert aot.enabled()
    assert store == os.path.join(str(tmp_path), "slu_aot") \
        == os.path.join(jax.config.jax_compilation_cache_dir, aot.SUBDIR)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        assert not aot.enabled()        # a cache switched off keeps none
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


_PROBE = """
import jax, numpy as np, scipy.sparse as sp
from superlu_dist_tpu import Options
from superlu_dist_tpu.ops import batched as B, trisolve as T
from superlu_dist_tpu.plan.plan import plan_factorization
from superlu_dist_tpu.resilience import aot
from superlu_dist_tpu.sparse import csr_from_scipy
t = sp.diags([-1.0, 2.3, -1.1], [-1, 0, 1], shape=(6, 6))
a = csr_from_scipy(sp.kronsum(t, t, format="csr").tocsr())
plan = plan_factorization(a, Options(factor_dtype="float64"))
sched = B.get_schedule(plan, 1)
f64 = np.dtype("float64")
fns = (B._phase_fns(sched, f64, B._thresh_for(plan, f64))[0],
       *T._solve_packed_fn(sched, f64, False))
print(repr(jax.config.jax_compilation_cache_dir), repr(aot.aot_dir()),
      [type(f._fn).__name__ for f in fns])
"""


@pytest.mark.parametrize("kept", [False, True])
def test_a_process_follows_its_cache(tmp_path, kept):
    """A process of its own, with no conftest: with no cache directory
    the store is off and the builders return the plain jits, as ever;
    with `JAX_COMPILATION_CACHE_DIR` set (jax reads it into its
    config, the harness's other way of placing the cache) they return
    the store's proxies and the store lies beside the cache."""
    import subprocess
    import sys
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    if kept:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         cwd=str(tmp_path), capture_output=True,
                         text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    last = out.stdout.strip().splitlines()[-1]
    if kept:
        assert last == (f"{str(tmp_path)!r} "
                        f"{os.path.join(str(tmp_path), 'slu_aot')!r} "
                        "['AotJit', 'AotJit', 'AotJit']")
    else:
        assert last == f"None None {['PjitFunction'] * 3}"


def test_disabled_is_inert(no_cache):
    assert not aot.enabled()
    f = jax.jit(lambda x: x + 1)
    assert aot.wrap_jit("t", f, "fp") is f          # unchanged object
    assert aot.save("t", "fp", None) is None
    assert aot.load("t", "fp") is None


def test_save_load_roundtrip(store):
    exp = _export_of(lambda x: x * 2 + 1,
                     jax.ShapeDtypeStruct((4,), np.float32))
    fp = "a" * 64
    path = aot.save("prog", fp, exp)
    assert path and os.path.exists(path)
    got = aot.load("prog", fp)
    x = jnp.arange(4, dtype=np.float32)
    assert np.array_equal(jax.jit(got.call)(x), exp.call(x))
    st = aot.stats()
    assert st["saves"] == 1 and st["hits"] == 1 and st["misses"] == 0


def test_absent_entry_is_a_miss(store):
    assert aot.load("nope", "b" * 64) is None
    assert aot.stats()["misses"] == 1


def test_fingerprint_mismatch_refused_typed(store):
    """The loader must REFUSE a fingerprint mismatch with the typed
    AotMismatch (never dispatch a program exported for a different
    layout/dtype/flag world) and quarantine the entry."""
    exp = _export_of(lambda x: x + 1,
                     jax.ShapeDtypeStruct((2,), np.float32))
    fp1, fp2 = "c" * 64, "d" * 64
    path = aot.save("prog", fp1, exp)
    # same filename, different expected fingerprint: rewrite the
    # entry under fp2's name with fp1's content (the renamed/copied
    # file scenario)
    os.replace(path, aot._entry_path("prog", fp2))
    with pytest.raises(aot.AotMismatch):
        aot.load("prog", fp2)
    st = aot.stats()
    assert st["rejected"] == 1 and st["hits"] == 0
    assert any(p.endswith(".quarantined") for p in os.listdir(store))
    # quarantined: the next load is a plain miss, never a crash
    assert aot.load("prog", fp2) is None


def test_corrupt_entry_refused_and_quarantined(store):
    exp = _export_of(lambda x: x + 1,
                     jax.ShapeDtypeStruct((2,), np.float32))
    fp = "e" * 64
    path = aot.save("prog", fp, exp)
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF                   # flip one byte
    open(path, "wb").write(bytes(blob))
    with pytest.raises(aot.AotMismatch):
        aot.load("prog", fp)
    assert aot.stats()["rejected"] == 1
    assert any(p.endswith(".quarantined") for p in os.listdir(store))


def test_jax_version_drift_refused(store):
    exp = _export_of(lambda x: x + 1,
                     jax.ShapeDtypeStruct((2,), np.float32))
    fp = "f" * 64
    path = aot.save("prog", fp, exp)
    raw = open(path, "rb").read()
    blob = raw[len(aot._MAGIC) + 32:]
    head, _, payload = blob.partition(b"\n")
    meta = json.loads(head)
    meta["jax"] = "0.0.1"
    blob2 = json.dumps(meta, sort_keys=True).encode() + b"\n" + payload
    import hashlib
    open(path, "wb").write(
        aot._MAGIC + hashlib.sha256(blob2).digest() + blob2)
    with pytest.raises(aot.AotMismatch, match="0.0.1"):
        aot.load("prog", fp)


def test_fingerprint_tracks_merge_flags(monkeypatch):
    """A merge-flag flip changes the program, so it must change the
    key — a stale executable must never be served for a different
    dispatch world."""
    a = _testmat(20)
    sched = B.get_schedule(
        plan_factorization(a, Options(factor_dtype="float64")), 1)
    monkeypatch.setenv("SLU_FACTOR_MERGE_CELLS", "65536")
    fp1 = aot.schedule_fingerprint(sched, np.float64)
    monkeypatch.setenv("SLU_FACTOR_MERGE_CELLS", "0")
    fp2 = aot.schedule_fingerprint(sched, np.float64)
    assert fp1 != fp2
    assert aot.schedule_fingerprint(sched, np.float32) != fp2
    monkeypatch.setenv("SLU_TRISOLVE", "legacy")
    assert aot.schedule_fingerprint(sched, np.float64) != fp2


# --------------------------------------------------------------------
# integration: the wrapped whole-phase programs
# --------------------------------------------------------------------

def test_aot_served_solve_bitwise_and_corrupt_fallback(store):
    """factor + packed solve through the AOT layer, one scenario end
    to end: (1) first build exports write-through; (2) a rebuilt
    world (fresh plan objects, the fresh-process stand-in) LOADS and
    serves bitwise-identical results to the unwrapped programs;
    (3) with every entry then corrupted, the dispatch path refuses +
    quarantines and REBUILDS — cold, correct, never wrong — and
    re-exports fresh entries."""
    a = _testmat(16)
    b = np.random.default_rng(0).standard_normal((a.n, 2))

    def run():
        plan = plan_factorization(a, Options(factor_dtype="float64"))
        lu = B.factorize_device(plan, plan.scaled_values(a),
                                np.float64)
        return B.solve_device(lu, b)

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        x_ref = run()                              # no cache kept: off
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert aot.stats() == dict.fromkeys(aot.stats(), 0)
    x1 = run()                                     # export write-through
    s1 = aot.stats()
    assert s1["saves"] >= 2                        # factor + solve
    x2 = run()                                     # read-through
    s2 = aot.stats()
    assert s2["hits"] >= 2 and s2["rejected"] == 0
    assert np.array_equal(x_ref, x1)
    assert np.array_equal(x_ref, x2)
    for name in os.listdir(store):                 # corrupt every entry
        if name.endswith(aot.SUFFIX):
            p = os.path.join(store, name)
            blob = bytearray(open(p, "rb").read())
            blob[-1] ^= 0xFF
            open(p, "wb").write(bytes(blob))
    x3 = run()
    s3 = aot.stats()
    assert s3["rejected"] >= 1
    assert np.array_equal(x_ref, x3)
    # the rebuild re-exported fresh entries beside the quarantined
    assert any(p.endswith(aot.SUFFIX) for p in os.listdir(store))
    assert any(p.endswith(".quarantined") for p in os.listdir(store))


# --------------------------------------------------------------------
# ISSUE 39: the rule, the key, the hit
# --------------------------------------------------------------------

def test_source_fingerprint_sees_one_byte(tmp_path, monkeypatch):
    """The key's leading leg is a sha256 over the bytes of every .py
    file of the package: one byte of one file re-keys every entry,
    nothing else does."""
    (tmp_path / "sub").mkdir()
    (tmp_path / "a.py").write_bytes(b"x = 1\n")
    (tmp_path / "sub" / "b.py").write_bytes(b"y = 2\n")
    (tmp_path / "notes.txt").write_bytes(b"not code\n")
    fp = aot.tree_fingerprint(str(tmp_path))
    assert aot.tree_fingerprint(str(tmp_path)) == fp
    (tmp_path / "notes.txt").write_bytes(b"still not code\n")
    (tmp_path / "sub" / "b.pyc").write_bytes(b"\0")
    assert aot.tree_fingerprint(str(tmp_path)) == fp
    (tmp_path / "sub" / "b.py").write_bytes(b"y = 3\n")
    assert aot.tree_fingerprint(str(tmp_path)) != fp
    (tmp_path / "sub" / "b.py").write_bytes(b"y = 2\n")
    assert aot.tree_fingerprint(str(tmp_path)) == fp
    os.rename(tmp_path / "a.py", tmp_path / "c.py")    # a path is a leg
    assert aot.tree_fingerprint(str(tmp_path)) != fp
    # the package's own, once a process, and every schedule key
    # starts from it
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(aot.__file__)))
    assert aot.source_fingerprint() == aot.tree_fingerprint(pkg)
    sched = B.get_schedule(
        plan_factorization(_testmat(8), Options(factor_dtype="float64")), 1)
    fp1 = aot.schedule_fingerprint(sched, np.float64)
    assert aot.schedule_fingerprint(sched, np.float64) == fp1
    monkeypatch.setattr(aot, "source_fingerprint", lambda: "edited")
    assert aot.schedule_fingerprint(sched, np.float64) != fp1


def test_cache_switched_off_builders_return_plain_jits(no_cache):
    a = _testmat(8)
    plan = plan_factorization(a, Options(factor_dtype="float64"))
    sched = B.get_schedule(plan, 1)
    f64 = np.dtype(np.float64)
    factor_fn, _ = B._phase_fns(sched, f64, B._thresh_for(plan, f64))
    sweeps = T._solve_packed_fn(sched, f64, False)
    for watched in (factor_fn, *sweeps):
        assert not isinstance(watched._fn, aot.AotJit)
        assert hasattr(watched._fn, "lower")        # the jit itself


def _fresh_world(a, opts, b):
    """One process-like build: fresh plan and schedule objects (same
    pattern), a factorization, a refined solve and a transposed one.
    Returns the flats, the answers, this build's ledger rows and the
    schedule."""
    t0 = time.perf_counter()
    plan = slu.plan_factorization(a, opts)
    lu = slu.factorize(a, opts, plan=plan)
    x = np.asarray(slu.solve(lu, b))
    d = lu.device_lu
    xt = np.asarray(B.solve_device_trans(d, b.astype(d.dtype)))
    flats = [np.asarray(f) for f in (d.L_flat, d.U_flat, d.Li_flat,
                                     d.Ui_flat)]
    rows = COMPILE_WATCH.ledger(since=t0)["programs"]
    return flats, (x, xt), rows, d.schedule


def _row(rows, name):
    got = [r for r in rows if r["name"] == name]
    assert got, (name, [r["name"] for r in rows])
    return got


@pytest.mark.parametrize("fdt", ["float32", "complex64"])
def test_second_build_on_a_kept_store_hits_and_builds_nothing(
        store, monkeypatch, fdt):
    """A second process-like build on a kept store hits for the factor
    program and both packed sweeps (N and T), uploads none of
    `GroupSpec.dev`'s index constants and dispatches none of its
    programs, and returns L, U, Li, Ui and refined answers bitwise
    equal to the miss's.  complex64 runs in pair storage (the chip's
    lowering, forced here): an all-real program, admitted like one."""
    a = _testmat(12)
    if fdt == "complex64":
        monkeypatch.setenv("SLU_COMPLEX_PAIR", "1")
        a = csr_from_scipy(sp.csr_matrix(
            (np.asarray(a.data) * (1 + 0.25j), np.asarray(a.indices),
             np.asarray(a.indptr)), shape=(a.n, a.n)))
    opts = Options(factor_dtype=fdt)
    b = np.random.default_rng(1).standard_normal(a.n).astype(
        np.complex128 if fdt == "complex64" else np.float64)

    flats1, xs1, rows1, _ = _fresh_world(a, opts, b)
    miss = aot.stats()
    assert miss["saves"] >= 3 and miss["hits"] == 0, miss
    aot.reset_stats()
    flats2, xs2, rows2, sched2 = _fresh_world(a, opts, b)
    hit = aot.stats()
    assert hit["hits"] >= 3 and hit["misses"] == 0 \
        and hit["rejected"] == 0 and hit["unexportable"] == 0, hit

    for f1, f2 in zip(flats1, flats2):
        assert np.array_equal(f1, f2)
    for x1, x2 in zip(xs1, xs2):
        assert np.array_equal(x1, x2)

    # the ledger's rows say so: `aot` on the watched rows, and on a
    # hit the wrapper's trace and lowering only
    assert [r["aot"] for r in _row(rows1, "slu_factor")] == ["miss"]
    assert [r["aot"] for r in _row(rows2, "slu_factor")] == ["hit"]
    assert {r["aot"] for r in _row(rows1, "slu_solve_packed")} == {"miss"}
    sweeps = _row(rows2, "slu_solve_packed")
    assert len(sweeps) >= 2 and {r["aot"] for r in sweeps} == {"hit"}
    assert {r["aot"] for r in _row(rows2, "slu_pack")} == {"off"}
    f_miss, f_hit = _row(rows1, "slu_factor")[0], _row(rows2, "slu_factor")[0]
    assert f_hit["trace_s"] < 0.1 * f_miss["trace_s"]
    # nothing that only a trace needs: no index constant was uploaded
    # for the fresh schedule, and no one-operation program of
    # GroupSpec.dev ran (none does on a miss either, since the casts
    # and the squeeze are numpy's)
    assert all(g._dev is None for g in sched2.groups)
    for rows in (rows1, rows2):
        assert not [r["name"] for r in rows if r["watched"] is None
                    and r["name"] in ("dynamic_slice", "squeeze",
                                      "convert_element_type")]


def test_hit_is_the_same_program_to_every_reader(store):
    """A served export compiles as `jit_slu_factor`, not `jit_call`,
    and its operations keep the `slu.` scopes: `factor_named_share`,
    `dense_front_share`, `dense_front_roofline` and `extend_add_s`
    are read from them (a longer prefix is harmless, a stripped one
    is not)."""
    a = _testmat(12)
    opts = Options(factor_dtype="float32")
    for _ in range(2):
        plan = slu.plan_factorization(a, opts)
        lu = slu.factorize(a, opts, plan=plan)
    assert aot.stats()["hits"] >= 1
    sched = lu.device_lu.schedule
    f32 = np.dtype(np.float32)
    factor_fn, _ = B._phase_fns(sched, f32, B._thresh_for(plan, f32))
    proxy = factor_fn._fn
    assert isinstance(proxy, aot.AotJit)
    (served,) = proxy._table.values()
    vals = jnp.asarray(np.asarray(plan.scaled_values(a), np.float32))
    lowered = served.lower(vals)
    text = lowered.as_text(debug_info=True)
    assert "module @jit_slu_factor" in text
    assert "call_exported" in text
    for scope in ("slu.assemble", "slu.extend_add", "slu.partial_lu",
                  "slu.tri_inverse", "slu.store"):
        assert scope in text, scope
    hlo = lowered.compile().as_text()
    assert "jit_slu_factor" in hlo and "slu.extend_add" in hlo


def test_save_keeps_two_generations(store):
    """`save` removes what it supersedes: of the same program and
    signature under other fingerprints only the most recently used
    one stays (a parent and a change alternate in one kept directory
    without evicting each other), so a directory does not grow by a
    set of programs a PR."""
    exp = _export_of(lambda x: x + 1,
                     jax.ShapeDtypeStruct((2,), np.float32))
    fps = ["1" * 64, "2" * 64, "3" * 64]
    p1 = aot.save("prog.sigabc", fps[0], exp)
    p2 = aot.save("prog.sigabc", fps[1], exp)
    other = aot.save("prog.sigabd", fps[0], exp)    # another signature
    assert os.path.exists(p1) and os.path.exists(p2)
    os.utime(p2, (1, 1))
    os.utime(p1, (2, 2))
    assert aot.load("prog.sigabc", fps[1]) is not None  # a hit touches
    p3 = aot.save("prog.sigabc", fps[2], exp)
    assert not os.path.exists(p1)                   # superseded
    assert os.path.exists(p2) and os.path.exists(p3)
    assert os.path.exists(other)


def test_startup_totals_carry_the_store(store):
    a = _testmat(8)
    opts = Options(factor_dtype="float64")
    lu = slu.factorize(a, opts)
    st = slu.Stats()
    slu.solve(lu, np.ones(a.n), stats=st)
    su = COMPILE_WATCH.snapshot()["startup"]
    assert su["aot"] == aot.stats()
    assert set(su["aot"]) == {"hits", "misses", "saves", "rejected",
                              "unexportable"}
    assert su["aot"]["misses"] >= 2
    assert "exported store" in st.report()
