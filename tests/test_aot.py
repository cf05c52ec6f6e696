"""AOT executable persistence (resilience/aot.py, ISSUE 12): the
whole-phase jits serialize via jax.export keyed by a layout + dtype +
merge-flag fingerprint; a fresh process deserializes instead of
re-tracing and its backend compile rides the persistent compilation
cache.  Pinned here: the save/load verification envelope (sha frame,
fingerprint refusal with the TYPED AotMismatch, quarantine), bitwise
identity of AOT-served programs, and the off-path being a no-op."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from superlu_dist_tpu import Options
from superlu_dist_tpu.ops import batched as B
from superlu_dist_tpu.plan.plan import plan_factorization
from superlu_dist_tpu.resilience import aot
from superlu_dist_tpu.sparse import csr_from_scipy


@pytest.fixture(autouse=True)
def _fresh_stats():
    aot.reset_stats()
    yield
    aot.reset_stats()


def _testmat(m=30):
    t = sp.diags([-1.0, 2.3, -1.1], [-1, 0, 1], shape=(m, m))
    return csr_from_scipy(sp.kronsum(t, t, format="csr").tocsr())


def _export_of(fn, *avals):
    from jax import export as jax_export
    return jax_export.export(jax.jit(fn))(*avals)


# --------------------------------------------------------------------
# store discipline
# --------------------------------------------------------------------

def test_disabled_is_inert(monkeypatch):
    monkeypatch.delenv("SLU_AOT_CACHE", raising=False)
    assert not aot.enabled()
    f = jax.jit(lambda x: x + 1)
    assert aot.wrap_jit("t", f, "fp") is f          # unchanged object
    assert aot.save("t", "fp", None) is None
    assert aot.load("t", "fp") is None
    monkeypatch.setenv("SLU_AOT_CACHE", "0")
    assert not aot.enabled()


def test_save_load_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("SLU_AOT_CACHE", str(tmp_path))
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


def test_absent_entry_is_a_miss(tmp_path, monkeypatch):
    monkeypatch.setenv("SLU_AOT_CACHE", str(tmp_path))
    assert aot.load("nope", "b" * 64) is None
    assert aot.stats()["misses"] == 1


def test_fingerprint_mismatch_refused_typed(tmp_path, monkeypatch):
    """The loader must REFUSE a fingerprint mismatch with the typed
    AotMismatch (never dispatch a program exported for a different
    layout/dtype/flag world) and quarantine the entry."""
    monkeypatch.setenv("SLU_AOT_CACHE", str(tmp_path))
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
    assert any(p.endswith(".quarantined") for p in os.listdir(tmp_path))
    # quarantined: the next load is a plain miss, never a crash
    assert aot.load("prog", fp2) is None


def test_corrupt_entry_refused_and_quarantined(tmp_path, monkeypatch):
    monkeypatch.setenv("SLU_AOT_CACHE", str(tmp_path))
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
    assert any(p.endswith(".quarantined") for p in os.listdir(tmp_path))


def test_jax_version_drift_refused(tmp_path, monkeypatch):
    monkeypatch.setenv("SLU_AOT_CACHE", str(tmp_path))
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

def test_aot_served_solve_bitwise_and_corrupt_fallback(
        tmp_path, monkeypatch):
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

    monkeypatch.setenv("SLU_AOT_CACHE", "0")       # explicit off (the
    x_ref = run()                                  # conftest default
    aot.reset_stats()                              # is a shared dir)
    monkeypatch.setenv("SLU_AOT_CACHE", str(tmp_path))
    x1 = run()                                     # export write-through
    s1 = aot.stats()
    assert s1["saves"] >= 2                        # factor + solve
    x2 = run()                                     # read-through
    s2 = aot.stats()
    assert s2["hits"] >= 2 and s2["rejected"] == 0
    assert np.array_equal(x_ref, x1)
    assert np.array_equal(x_ref, x2)
    for name in os.listdir(tmp_path):              # corrupt every entry
        if name.endswith(aot.SUFFIX):
            p = os.path.join(tmp_path, name)
            blob = bytearray(open(p, "rb").read())
            blob[-1] ^= 0xFF
            open(p, "wb").write(bytes(blob))
    x3 = run()
    s3 = aot.stats()
    assert s3["rejected"] >= 1
    assert np.array_equal(x_ref, x3)
    # the rebuild re-exported fresh entries beside the quarantined
    assert any(p.endswith(aot.SUFFIX) for p in os.listdir(tmp_path))
    assert any(p.endswith(".quarantined")
               for p in os.listdir(tmp_path))
