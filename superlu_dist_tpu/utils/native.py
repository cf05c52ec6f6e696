"""ctypes bindings for the native host library (csrc/slu_host.cpp).

The reference implements its sequential preprocessing passes in C
(SRC/etree.c, SRC/mmd.c, SRC/mc64ad_dist.c, SRC/symbfact.c); this build
keeps them native too, compiled once into `_slu_host.so` and loaded via
ctypes.  Every entry point has a pure-Python twin in
superlu_dist_tpu/plan/ that serves as the test oracle.

The shared object is git-ignored and built lazily on first use
(g++ -O3 -shared) from csrc/.  A build or load failure is remembered
and the plan layer runs its Python twins — correct, but a Python
ordering at n=27,000 turns a 4 s plan into minutes — so the failure
is a RuntimeWarning carrying the compiler's message, never silent,
and chip_smoke.py fails outright when `available()` is False.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import warnings

import numpy as np

from .. import flags

_I64 = ctypes.POINTER(ctypes.c_int64)
_F64 = ctypes.POINTER(ctypes.c_double)
_F32 = ctypes.POINTER(ctypes.c_float)

_lock = threading.Lock()
_lib = None
_failed = False


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _so_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "_slu_host.so")


def _newer_than_sources(out: str, srcs) -> bool:
    try:
        if not os.path.exists(out):
            return False
        mt = os.path.getmtime(out)
        return all(not os.path.exists(s)
                   or mt >= os.path.getmtime(s) for s in srcs)
    except OSError:
        return False


def so_is_current() -> bool:
    """True when the built .so exists and is at least as new as its
    sources (the single freshness rule; also used by utils/cache.py to
    decide whether CPUID can be read without triggering a build)."""
    csrc = os.path.join(_repo_root(), "csrc")
    return _newer_than_sources(_so_path(), [
        os.path.join(csrc, "slu_host.cpp"),
        os.path.join(csrc, "slu_cpuid.h")])


def _compile_so(src: str, out: str, timeout: int = 300) -> bool:
    """g++ -shared `src` into `out` via a pid-unique tmp file
    (concurrent builds race); the single build recipe for both the
    full host library and the standalone CPUID helper."""
    tmp = f"{out}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-fPIC", "-pthread",
             "-shared", src, "-o", tmp],
            check=True, capture_output=True, timeout=timeout)
        os.replace(tmp, out)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        stderr = getattr(e, "stderr", b"") or b""
        warnings.warn(
            f"superlu_dist_tpu: building {os.path.basename(out)} from "
            f"{src} failed ({e!r}); the pure-Python twins take over. "
            + stderr.decode("utf-8", "replace")[-800:],
            RuntimeWarning, stacklevel=2)
        return False


def _read_cpuid(lib) -> np.ndarray:
    """Bind and call slu_cpuid_words on `lib` — the single ctypes
    contract for the CPUID export, shared by both libraries."""
    lib.slu_cpuid_words.argtypes = [_I64, ctypes.c_int64]
    lib.slu_cpuid_words.restype = ctypes.c_int64
    buf = np.zeros(64, dtype=np.int64)
    k = lib.slu_cpuid_words(buf.ctypes.data_as(_I64), 64)
    return buf[:k]


def _build() -> str | None:
    src = os.path.join(_repo_root(), "csrc", "slu_host.cpp")
    out = _so_path()
    if not os.path.exists(src):
        return None
    if so_is_current():
        return out
    return out if _compile_so(src, out) else None


def _load():
    global _lib, _failed
    if _lib is not None or _failed:
        return _lib
    with _lock:
        if _lib is not None or _failed:
            return _lib
        if flags.env_opt("SLU_TPU_NO_NATIVE"):
            _failed = True
            return None
        path = _build()
        if path is None:
            _failed = True
            return None
        try:
            lib = ctypes.CDLL(path)
            lib.slu_etree.argtypes = [ctypes.c_int64, _I64, _I64, _I64]
            lib.slu_postorder.argtypes = [ctypes.c_int64, _I64, _I64]
            lib.slu_colcounts.argtypes = [ctypes.c_int64, _I64, _I64,
                                          _I64, _I64]
            lib.slu_mdorder.argtypes = [ctypes.c_int64, _I64, _I64, _I64]
            lib.slu_mdorder.restype = ctypes.c_int64
            lib.slu_mc64.argtypes = [ctypes.c_int64, _I64, _I64, _F64,
                                     _I64, _F64, _F64]
            lib.slu_mc64.restype = ctypes.c_int64
            lib.slu_mc64_counted.argtypes = [
                ctypes.c_int64, _I64, _I64, _F64, _I64, _F64, _F64, _I64]
            lib.slu_mc64_counted.restype = ctypes.c_int64
            lib.slu_hwpm.argtypes = [ctypes.c_int64, _I64, _I64, _F64,
                                     ctypes.c_int64, _I64]
            lib.slu_hwpm.restype = ctypes.c_int64
            lib.slu_symbfact_create.argtypes = [
                ctypes.c_int64, _I64, _I64, ctypes.c_int64, _I64, _I64]
            lib.slu_symbfact_create.restype = ctypes.c_void_p
            lib.slu_symbfact_create_par.argtypes = [
                ctypes.c_int64, _I64, _I64, ctypes.c_int64, _I64, _I64,
                ctypes.c_int64]
            lib.slu_symbfact_create_par.restype = ctypes.c_void_p
            lib.slu_symbfact_total.argtypes = [ctypes.c_void_p]
            lib.slu_symbfact_total.restype = ctypes.c_int64
            lib.slu_symbfact_sizes.argtypes = [ctypes.c_void_p, _I64]
            lib.slu_symbfact_fill.argtypes = [ctypes.c_void_p, _I64]
            lib.slu_symbfact_free.argtypes = [ctypes.c_void_p]
            lib.slu_ndorder.argtypes = [ctypes.c_int64, _I64, _I64,
                                        ctypes.c_int64, ctypes.c_int64,
                                        _I64]
            lib.slu_ndorder.restype = ctypes.c_int64
            lib.slu_supernodes.argtypes = [ctypes.c_int64, _I64, _I64,
                                           ctypes.c_int64,
                                           ctypes.c_int64, _I64, _I64,
                                           _I64]
            lib.slu_supernodes.restype = ctypes.c_int64
            lib.slu_cpuid_words.argtypes = [_I64, ctypes.c_int64]
            lib.slu_cpuid_words.restype = ctypes.c_int64
            for name, fp in (("slu_batch_residual_f64", _F64),
                             ("slu_batch_residual_f32", _F32)):
                getattr(lib, name).argtypes = (
                    [ctypes.c_int64] * 4 + [_I64, _I64, _I64]
                    + [fp] * 5 + [ctypes.c_int64])
            lib.slu_version.restype = ctypes.c_int64
            assert lib.slu_version() == 7
            _lib = lib
        except (OSError, AssertionError, AttributeError) as e:
            _failed = True
            warnings.warn(
                f"superlu_dist_tpu: loading {path} failed ({e!r}); "
                "the pure-Python twins take over", RuntimeWarning,
                stacklevel=2)
    return _lib


def available() -> bool:
    return _load() is not None


def native_or_none():
    """Shared dispatch probe: this module when the library loads, else
    None.  Plan-layer call sites use this instead of re-rolling the
    try-import/availability boilerplate."""
    import sys
    mod = sys.modules[__name__]
    return mod if available() else None


def _c64(a: np.ndarray):
    a = np.ascontiguousarray(a, dtype=np.int64)
    return a, a.ctypes.data_as(_I64)


def _cf64(a: np.ndarray):
    a = np.ascontiguousarray(a, dtype=np.float64)
    return a, a.ctypes.data_as(_F64)


def etree(indptr: np.ndarray, indices: np.ndarray, n: int) -> np.ndarray:
    lib = _load()
    a_pp, pp = _c64(indptr)
    a_pi, pi = _c64(indices)
    parent = np.empty(n, dtype=np.int64)
    lib.slu_etree(n, pp, pi, parent.ctypes.data_as(_I64))
    return parent


def postorder(parent: np.ndarray) -> np.ndarray:
    lib = _load()
    n = len(parent)
    a_pp, pp = _c64(parent)
    post = np.empty(n, dtype=np.int64)
    lib.slu_postorder(n, pp, post.ctypes.data_as(_I64))
    return post


def col_counts(indptr: np.ndarray, indices: np.ndarray,
               parent: np.ndarray) -> np.ndarray:
    lib = _load()
    n = len(parent)
    a_pp, pp = _c64(indptr)
    a_pi, pi = _c64(indices)
    a_pa, pa = _c64(parent)
    cc = np.empty(n, dtype=np.int64)
    lib.slu_colcounts(n, pp, pi, pa, cc.ctypes.data_as(_I64))
    return cc


def amd_order(indptr: np.ndarray, indices: np.ndarray,
              n: int) -> np.ndarray:
    """Minimum-degree ordering; returns order[k] = k-th pivot."""
    lib = _load()
    a_pp, pp = _c64(indptr)
    a_pi, pi = _c64(indices)
    order = np.empty(n, dtype=np.int64)
    got = lib.slu_mdorder(n, pp, pi, order.ctypes.data_as(_I64))
    if got != n:
        raise RuntimeError(f"native mdorder returned {got} of {n} pivots")
    return order


def mc64(n: int, colptr: np.ndarray, rowind: np.ndarray,
         absval: np.ndarray):
    """MC64 job=5 on CSC input.  Returns (rowperm, u, v) where
    rowperm[i] = destination position of row i and (u, v) are the dual
    potentials (R_i = exp(u_i), C_j = exp(v_j)/cmax_j scalings)."""
    return mc64_counted(n, colptr, rowind, absval)[:3]


def mc64_counted(n: int, colptr: np.ndarray, rowind: np.ndarray,
                 absval: np.ndarray):
    """`mc64` with the work it took: (rowperm, u, v, work), work =
    {searches: columns the cheap pass left free, rows_finalized and
    edges_scanned: over all their shortest-path searches}.  Counts,
    not seconds: what the tests bound."""
    lib = _load()
    a_pc, pc = _c64(colptr)
    a_pr, pr = _c64(rowind)
    a_pv, pv = _cf64(absval)
    perm = np.empty(n, dtype=np.int64)
    u = np.empty(n, dtype=np.float64)
    v = np.empty(n, dtype=np.float64)
    work = np.zeros(3, dtype=np.int64)
    rc = lib.slu_mc64_counted(n, pc, pr, pv, perm.ctypes.data_as(_I64),
                              u.ctypes.data_as(_F64),
                              v.ctypes.data_as(_F64),
                              work.ctypes.data_as(_I64))
    if rc != 0:
        raise ValueError("structurally singular matrix (native mc64)")
    return perm, u, v, dict(zip(
        ("searches", "rows_finalized", "edges_scanned"), work.tolist()))


def cpuid_words() -> np.ndarray:
    """Raw CPUID leaf dump (x86; empty elsewhere) — the
    virtualization-proof half of the compile-cache host fingerprint
    (utils/cache.py)."""
    return _read_cpuid(_load())


def _cpuid_so_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "_slu_cpuid.so")


def cpuid_words_fast() -> np.ndarray:
    """CPUID without the full host library: reuse the big .so when it
    is already current, else build the single-TU helper
    (csrc/slu_cpuid.cc, well under a second) so the compile-cache
    fingerprint includes CPUID from the session's FIRST process.
    Without this, pre-/post-first-native-build processes computed
    different fingerprints on the same host and orphaned each other's
    persistent-cache entries (observed: the 2026-08-01 TPU window's
    executables landed in a dir no later run looked at).  Returns an
    empty array when no helper can be produced (caller falls back to
    the /proc fingerprint)."""
    if flags.env_opt("SLU_TPU_NO_NATIVE"):
        # the documented no-native-code opt-out covers the tiny helper
        # too: no g++ spawns from conftest/bench startup; caller falls
        # back to the /proc fingerprint
        return np.zeros(0, dtype=np.int64)
    if so_is_current() and available():
        return cpuid_words()
    csrc = os.path.join(_repo_root(), "csrc")
    src = os.path.join(csrc, "slu_cpuid.cc")
    hdr = os.path.join(csrc, "slu_cpuid.h")
    out = _cpuid_so_path()
    if not _newer_than_sources(out, [src, hdr]):
        if not os.path.exists(src) or not _compile_so(src, out,
                                                      timeout=60):
            return np.zeros(0, dtype=np.int64)
    try:
        return _read_cpuid(ctypes.CDLL(out))
    except (OSError, AttributeError):
        return np.zeros(0, dtype=np.int64)


def batch_residual(indptr: np.ndarray, indices: np.ndarray, src,
                   vals: np.ndarray, x: np.ndarray, b: np.ndarray,
                   threads: int = 0, out: np.ndarray | None = None):
    """r_m = b_m - A_m x_m and the componentwise backward error of B
    systems on one CSR pattern, one pass over the values, members
    over threads (0: hardware concurrency, at most 8).  `vals` is
    (B, nnz) with the pattern's entry k at `src[k]` (None: at k); x
    and b are (B, n, nrhs); all three share one dtype, float64 or
    float32.  Returns (r, berr (B,)); r is bitwise the block-diagonal
    scipy product's (models/refine.batch_residual_twin, the oracle),
    written into `out` where one is given (x's shape and dtype,
    contiguous: a caller's kept buffer costs no fresh pages)."""
    lib = _load()
    dt = np.dtype(vals.dtype)
    fn, fp = {"float64": (lib.slu_batch_residual_f64, _F64),
              "float32": (lib.slu_batch_residual_f32, _F32)}[dt.name]
    vals, x, b = (np.ascontiguousarray(a, dtype=dt)
                  for a in (vals, x, b))
    B, n, nrhs = x.shape
    a_pp, pp = _c64(indptr)
    a_pi, pi = _c64(indices)
    a_ps, ps = _c64(src) if src is not None else (None, None)
    r = np.empty_like(x) if out is None else out
    assert r.shape == x.shape and r.dtype == dt and r.flags.c_contiguous
    berr = np.empty(B, dtype=dt)
    fn(B, n, nrhs, vals.shape[1], pp, pi, ps,
       *(a.ctypes.data_as(fp) for a in (vals, x, b, r, berr)), threads)
    return r, berr


def hwpm(n: int, colptr: np.ndarray, rowind: np.ndarray,
         absval: np.ndarray, threads: int = 0):
    """Approximate heavy-weight perfect matching on CSC input (the
    LargeDiag_HWPM slot, SRC/dHWPM_CombBLAS.hpp:60 analog): parallel
    locally-dominant greedy + augmenting-path completion.  Returns
    rowperm only — no dual scalings, matching the reference HWPM
    contract.  threads=0 → hardware concurrency."""
    lib = _load()
    a_pc, pc = _c64(colptr)
    a_pr, pr = _c64(rowind)
    a_pv, pv = _cf64(absval)
    perm = np.empty(n, dtype=np.int64)
    rc = lib.slu_hwpm(n, pc, pr, pv, threads,
                      perm.ctypes.data_as(_I64))
    if rc == -2:
        raise OverflowError("n exceeds the 2^32 row-id packing limit "
                            "of the hwpm proposal key")
    if rc != 0:
        raise ValueError("structurally singular matrix (native hwpm)")
    return perm


def nd_order(indptr: np.ndarray, indices: np.ndarray, n: int,
             leaf_size: int = 48, threads: int = 1) -> np.ndarray:
    """Nested-dissection ordering; returns order[k] = k-th pivot.
    Identical output to plan/nested.nd_order (the oracle); threads > 1
    fans the recursion halves over std::thread."""
    lib = _load()
    a_pp, pp = _c64(indptr)
    a_pi, pi = _c64(indices)
    out = np.empty(n, dtype=np.int64)
    got = lib.slu_ndorder(n, pp, pi, leaf_size, threads,
                          out.ctypes.data_as(_I64))
    if got != n:
        raise RuntimeError(f"native ndorder returned {got} of {n}")
    return out


def supernodes(parent: np.ndarray, colcount: np.ndarray, relax: int,
               max_super: int):
    """Supernode partition; returns (nsuper, xsup, supno, sparent) —
    bit-identical to plan/supernodes.find_supernodes (the oracle)."""
    lib = _load()
    n = len(parent)
    a_pp, pp = _c64(parent)
    a_pc, pc = _c64(colcount)
    supno = np.empty(n, dtype=np.int64)
    xsup = np.empty(n + 1, dtype=np.int64)
    sparent = np.empty(n if n else 1, dtype=np.int64)
    ns = int(lib.slu_supernodes(n, pp, pc, relax, max_super,
                                supno.ctypes.data_as(_I64),
                                xsup.ctypes.data_as(_I64),
                                sparent.ctypes.data_as(_I64)))
    return ns, xsup[:ns + 1].copy(), supno, sparent[:ns].copy()


def symbfact(n: int, b_indptr: np.ndarray, b_indices: np.ndarray,
             nsuper: int, xsup: np.ndarray, sparent: np.ndarray,
             threads: int = 1):
    """Supernodal symbolic factorization.  Returns a list of
    per-supernode sorted off-block row index arrays.  threads > 1
    runs the level-parallel variant (identical output)."""
    lib = _load()
    a_pp, pp = _c64(b_indptr)
    a_pi, pi = _c64(b_indices)
    a_px, px = _c64(xsup)
    a_ps, ps = _c64(sparent)
    if threads > 1:
        h = lib.slu_symbfact_create_par(n, pp, pi, nsuper, px, ps,
                                        threads)
    else:
        h = lib.slu_symbfact_create(n, pp, pi, nsuper, px, ps)
    if not h:
        raise MemoryError("slu_symbfact_create failed")
    try:
        sizes = np.empty(nsuper, dtype=np.int64)
        lib.slu_symbfact_sizes(h, sizes.ctypes.data_as(_I64))
        flat = np.empty(int(lib.slu_symbfact_total(h)), dtype=np.int64)
        lib.slu_symbfact_fill(h, flat.ctypes.data_as(_I64))
    finally:
        lib.slu_symbfact_free(h)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    return [flat[offs[s]:offs[s + 1]] for s in range(nsuper)]
