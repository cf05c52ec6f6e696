"""AOT executable persistence — compiled whole-phase programs as
durable artifacts (ISSUE 12, ROADMAP item 1a).

The durable factor store (resilience/store.py) lets a fresh replica
skip the FACTORIZATION; until this module nothing let it skip the
COMPILATION: a genuinely fresh process re-paid 14–33 s of jit
trace/lower warmup plus a 2m4s whole-phase XLA:CPU compile
before serving its first solve.  With static pivoting both costs are
cacheable artifacts — the task graph is fixed at plan time, so the
whole-phase programs are pure functions of (schedule layout, dtype,
merge flags) — and this module persists them on two legs:

  * **export leg** (this module): whole-phase jits serialize via
    `jax.export` — the StableHLO module plus calling convention —
    keyed by `schedule_fingerprint` (per-group layout + dtype + the
    factor/trisolve merge-flag surface + jax version + backend).  A
    fresh process DESERIALIZES instead of re-tracing: the 14–33 s
    Python trace/lower wall collapses to a read.  Integration sites:
    `ops/batched._phase_fns` (whole-phase factor) and
    `ops/trisolve._solve_packed_fn` (the packed solve — the serve hot
    path), via `wrap_jit`'s per-signature read-through/write-through
    proxy.  Producer and consumer both dispatch through the SAME
    exported module (`jax.jit(exported.call)`), so the two can never
    execute divergent programs.
  * **compilation-cache leg**: the deserialized module still needs a
    backend compile, which jax's persistent compilation cache makes a
    disk hit across processes.  The staged per-segment factor
    programs ride this leg alone: they are bounded per-segment
    compiles with donated operands, already warmed/persisted by
    `utils/warmup.py` (a staged handle's sweep is the packed solve
    program, on both legs like any other handle's).

**The rule** (ISSUE 39; no variable, no option): the store is on
exactly when jax's persistent compilation cache is in force —
`jax.config.jax_compilation_cache_dir` non-empty (jax reads
`JAX_COMPILATION_CACHE_DIR` into it) and the cache enabled — and lives
in the sub-directory `slu_aot/` of that directory, which jax's own
eviction (`JAX_COMPILATION_CACHE_MAX_SIZE`; it globs `*-cache` in the
directory itself) neither counts nor clears.  A process that keeps
compiled programs on disk keeps exported ones beside them; one that
keeps none pays one string check a program build.  To clear the
store, remove `<cache dir>/slu_aot`.

**The key sees the code**: the fingerprint leads with a sha256 over
every `.py` file of this package (`source_fingerprint`), so any edit
re-keys every entry — a parent and a change measured against one kept
cache directory each run their own program.  `save` keeps, beside the
entry it writes, the most recently used entry of the same program and
signature under another fingerprint and removes the rest: a kept
directory holds two generations (a parent and a change alternate
without evicting each other), not one a PR.

Storage discipline follows the factor store: atomic-rename writes
(`utils/io.atomic_write_bytes`), a sha256 frame over the payload, and
a header echoing the fingerprint.  The loader REFUSES any mismatch —
frame, fingerprint, jax version, undeserializable payload — with the
typed `AotMismatch` and quarantines the entry (*.quarantined, the
store convention): a stale or corrupt executable is never dispatched.

Off (no cache directory) this module costs one string check per
program build — nothing on the dispatch path.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import threading

import numpy as np

from .. import flags
from ..utils.io import atomic_write_bytes

_MAGIC = b"SLUAOT1\n"
SUFFIX = ".aot"
SUBDIR = "slu_aot"


class AotMismatch(RuntimeError):
    """A persisted AOT entry failed verification (sha256 frame,
    header, fingerprint echo, jax version, deserialization): the
    loader refuses to dispatch it — typed so callers can tell a
    refused artifact from a plain miss — and the entry is quarantined
    so the next boot re-exports a fresh one."""


# --------------------------------------------------------------------
# configuration
# --------------------------------------------------------------------

def aot_dir() -> str | None:
    """`<jax's persistent compilation cache directory>/slu_aot`, or
    None when no such cache is in force (the rule, module docstring)."""
    import jax
    d = jax.config.jax_compilation_cache_dir
    if not d or not jax.config.jax_enable_compilation_cache:
        return None
    return os.path.join(d, SUBDIR)


def enabled() -> bool:
    return aot_dir() is not None


# --------------------------------------------------------------------
# counters (the cold-boot drill's gate reads these)
# --------------------------------------------------------------------

_stats_lock = threading.Lock()
_STATS = {"hits": 0, "misses": 0, "saves": 0, "rejected": 0,
          "unexportable": 0}


def _inc(k: str) -> None:
    with _stats_lock:
        _STATS[k] += 1


def stats() -> dict:
    """{'hits', 'misses', 'saves', 'rejected', 'unexportable'} — hits
    = programs served from a deserialized export, misses = absent
    entries (trace+export paid), rejected = entries refused by
    verification (quarantined, then re-exported), unexportable =
    signatures that fell back to the plain jit.  The start-up ledger
    (obs/compile_watch.py) carries them in `snapshot()["startup"]`."""
    with _stats_lock:
        return dict(_STATS)


def reset_stats() -> None:
    with _stats_lock:
        for k in _STATS:
            _STATS[k] = 0


# --------------------------------------------------------------------
# fingerprint
# --------------------------------------------------------------------

def _pattern_sig(sched) -> str:
    """sha256 over the schedule's INDEX CONTENT — the assembly maps,
    extend-add records and solve gather layouts the whole-phase
    programs bake in as constants.  Extents alone are not identity:
    two different sparsity patterns can share every per-group extent
    while their baked index arrays differ, and a fingerprint collision
    would silently dispatch the wrong program — exactly the failure
    the loader's refusal discipline exists to prevent.  Cached on the
    schedule (one pass over the index bytes, the factor store's
    checksum cost class)."""
    sig = getattr(sched, "_aot_pattern_sig", None)
    if sig is None:
        h = hashlib.sha256()
        for g in sched.groups:
            for arr in (g.a_src, g.a_dst, g.one_dst, g.col_idx,
                        g.struct_idx):
                a = np.ascontiguousarray(np.asarray(arr))
                h.update(repr((a.shape, a.dtype.str)).encode())
                h.update(a.tobytes())
            for host in (g.ea_hosts, g.eb_hosts):
                for rec in host:
                    for a in rec:
                        a = np.ascontiguousarray(np.asarray(a))
                        h.update(a.tobytes())
            h.update(repr(int(g.upd_off_global)).encode())
        sig = sched._aot_pattern_sig = h.hexdigest()
    return sig


def tree_fingerprint(root: str) -> str:
    """sha256 over the relative path and the bytes of every `.py`
    file under `root`, sorted by path."""
    h = hashlib.sha256()
    paths = []
    for d, _dirs, files in os.walk(root):
        paths.extend(os.path.join(d, f) for f in files
                     if f.endswith(".py"))
    for path in sorted(paths):
        h.update(os.path.relpath(path, root).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read() + b"\0")
    return h.hexdigest()


@functools.cache
def source_fingerprint() -> str:
    """The package's own code as a key leg, computed once a process
    (some 27,000 lines, milliseconds): nothing else of a fingerprint
    sees what the kernels compute, and a kept store must not serve a
    program traced from other sources."""
    return tree_fingerprint(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def mesh_fingerprint_legs(mesh, axis=None) -> tuple:
    """Fingerprint legs for a shard_map'd whole-phase program over a
    device mesh (ISSUE 17): mesh shape as (axis-name, extent) pairs in
    axis order, the flattened partition axis, and the participating
    device kinds.  Appended through `schedule_fingerprint`'s `extra`
    by the parallel/factor_dist.py program builders, so an export
    recorded on an 8-CPU test mesh refuses (typed AotMismatch, same
    discipline as every other leg) on a 2x2x2 TPU slice — and any
    mesh reshape, axis rename, or device-kind change re-keys the
    entry instead of dispatching a program compiled for a different
    collective topology."""
    shape = tuple((str(a), int(mesh.shape[a])) for a in mesh.axis_names)
    kinds = tuple(sorted({
        str(getattr(d, "device_kind", None)
            or getattr(d, "platform", "?"))
        for d in np.asarray(mesh.devices).ravel()}))
    ax = axis if axis is None or isinstance(axis, str) else tuple(axis)
    return ("mesh", shape, repr(ax), kinds)


def schedule_fingerprint(sched, dtype, extra=()) -> str:
    """sha256 over everything that shapes a whole-phase program for
    `sched`: the per-group layout (extents AND index content — the
    programs bake the index arrays in as constants, see
    _pattern_sig), dtype, the merge-flag surface (factor + trisolve
    arms — a flag flip changes the program, so it must change the
    key), jax version and backend.  `extra` appends caller legs
    (e.g. the packed-solve pair flag).  The leading tag stands for the
    kernels' own code, which nothing else here sees: an edit that
    changes what a program computes for the same schedule (v3: the
    panel-first dense_lu.partial_lu) bumps it, or a kept store serves
    the old arithmetic."""
    import jax

    from ..ops import batched as B
    from ..ops import trisolve as T
    parts = (
        source_fingerprint(), jax.__version__, jax.default_backend(),
        _pattern_sig(sched),
        np.dtype(dtype).str,
        int(sched.n), int(sched.ndev), int(sched.upd_total),
        int(getattr(sched, "upd_pad", 0)),
        int(sched.L_total), int(sched.U_total),
        int(sched.Li_total), int(sched.Ui_total),
        tuple((int(g.mb), int(g.wb), int(g.n_loc), int(g.level))
              for g in sched.groups),
        B.factor_merge_cells(), B.factor_seg_cells(),
        T.trisolve_mode(), T.merge_cells_limit(), T.seg_cells_limit(),
        flags.env_str("SLU_TPU_PALLAS", "0"),
        tuple(extra),
    )
    return hashlib.sha256(repr(parts).encode()).hexdigest()


# --------------------------------------------------------------------
# save / load
# --------------------------------------------------------------------

def _entry_path(name: str, fp: str) -> str | None:
    d = aot_dir()
    if d is None:
        return None
    os.makedirs(d, exist_ok=True)
    safe = "".join(ch if ch.isalnum() or ch in "._-" else "_"
                   for ch in name)
    return os.path.join(d, f"{safe}.{fp[:16]}{SUFFIX}")


def _drop_superseded(path: str) -> None:
    """Of the entries of `path`'s program and signature under other
    fingerprints keep the most recently used one (a hit touches its
    entry) and remove the rest."""
    d, keep = os.path.split(path)
    stem = keep[:-len(SUFFIX) - 16]     # `<program>.sig<arghash>.`
    old = []
    for f in os.listdir(d):
        if f.startswith(stem) and len(f) == len(keep) and f != keep:
            try:
                old.append((os.path.getmtime(os.path.join(d, f)), f))
            except OSError:
                pass                # a racer removed it
    for _mtime, f in sorted(old)[:-1]:
        try:
            os.remove(os.path.join(d, f))
        except OSError:
            pass


def quarantine(path: str, reason: str = "") -> None:
    """Move a refused entry aside (the store convention): it is never
    dispatched again, and the evidence survives for inspection."""
    try:
        os.replace(path, path + ".quarantined")
    except OSError:
        pass                        # a racer already moved/removed it


def save(name: str, fp: str, exported) -> str | None:
    """Write-through one serialized export atomically and drop what it
    supersedes (`_drop_superseded`); returns the path, or None when
    the feature is off."""
    path = _entry_path(name, fp)
    if path is None:
        return None
    import jax
    payload = exported.serialize()
    header = json.dumps(
        {"format": 1, "name": name, "fingerprint": fp,
         "jax": jax.__version__,
         "platforms": list(exported.platforms)},
        sort_keys=True).encode()
    blob = header + b"\n" + payload
    atomic_write_bytes(path, _MAGIC + hashlib.sha256(blob).digest()
                       + blob)
    _drop_superseded(path)
    _inc("saves")
    return path


def load(name: str, fp: str):
    """Read-through lookup: the deserialized `jax.export.Exported`,
    or None on plain absence.  ANY verification failure — bad frame,
    fingerprint mismatch, jax-version drift, undeserializable payload
    — raises the typed AotMismatch after quarantining the entry: a
    questionable executable is refused, never dispatched."""
    path = _entry_path(name, fp)
    if path is None:
        return None
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError:
        _inc("misses")
        return None
    import jax
    try:
        if not data.startswith(_MAGIC):
            raise AotMismatch(f"{path}: bad magic")
        digest = data[len(_MAGIC):len(_MAGIC) + 32]
        blob = data[len(_MAGIC) + 32:]
        if hashlib.sha256(blob).digest() != digest:
            raise AotMismatch(f"{path}: sha256 frame mismatch")
        head, sep, payload = blob.partition(b"\n")
        if not sep:
            raise AotMismatch(f"{path}: truncated header")
        try:
            meta = json.loads(head)
        except ValueError as e:
            raise AotMismatch(f"{path}: corrupt header: {e}")
        if meta.get("fingerprint") != fp:
            raise AotMismatch(
                f"{path}: fingerprint mismatch — entry was exported "
                "for a different (layout, dtype, merge-flag) world "
                f"({str(meta.get('fingerprint'))[:16]}… != "
                f"{fp[:16]}…)")
        if meta.get("jax") != jax.__version__:
            raise AotMismatch(
                f"{path}: exported under jax {meta.get('jax')}, "
                f"running {jax.__version__}")
        try:
            exported = jax.export.deserialize(payload)
        except Exception as e:      # noqa: BLE001 — any deserializer
            raise AotMismatch(      # failure is a refusal, not a crash
                f"{path}: deserialize failed: {type(e).__name__}: {e}")
    except AotMismatch:
        _inc("rejected")
        quarantine(path)
        raise
    try:
        os.utime(path)              # most recently used: save() keeps it
    except OSError:
        pass
    _inc("hits")
    return exported


# --------------------------------------------------------------------
# the per-signature jit proxy
# --------------------------------------------------------------------

def _named_jit(name: str, call):
    """`jax.jit` of `call` under `name`: a served export is the same
    program to every reader — the profiler's `jit_<name>`, the
    start-up ledger's `name`, the persistent-cache entry — as the jit
    it was exported from, not `jit_call`."""
    import jax

    def fn(*args):
        return call(*args)

    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


class AotJit:
    """Per-signature AOT-backed dispatch proxy over a jit: on each
    NEW call signature it read-throughs the cache (deserialized
    export → a jit of `exported.call` under the wrapped jit's name)
    and on a miss exports the underlying jit ONCE at those avals,
    write-throughs, and dispatches through the same exported module —
    producer and consumer execute identical programs by construction.
    What each signature came to (`hit`, `miss`, `refused`,
    `unexportable`) is stamped on the start-up ledger's open row
    (obs/compile_watch.py).  `lower` and
    other attributes delegate to the wrapped jit (the compile-watch
    and HLO-pin contract); `_cache_size` sums the per-signature jits
    so the serve zero-recompile probes keep working."""

    def __init__(self, name: str, fn, fingerprint: str):
        self._name = name
        self._fn = fn
        self._fp = fingerprint
        self._table: dict = {}
        self._tlock = threading.Lock()

    @staticmethod
    def _sig_key(args):
        # compile_watch._leaf_sig: (shape, dtype) for array-likes,
        # recursion for list/tuple containers, repr for statics —
        # and it memoizes container signatures ON attribute-capable
        # containers (trisolve.PackSet).  Reusing it here means the
        # ~200-leaf packed-solve signature is built once per PackSet
        # (shared with the compile-watch proxy's own memo) instead of
        # tree_flatten'd on every dispatch — the same 0.65 ms/call
        # class the PR 7 signature memo removed from the hot path.
        from ..obs.compile_watch import _leaf_sig
        return tuple(_leaf_sig(a) for a in args)

    def __call__(self, *args):
        key = self._sig_key(args)
        fn = self._table.get(key)   # GIL-atomic hot-path read
        if fn is None:
            fn = self._resolve(key, args)
        try:
            return fn(*args)
        except ValueError as e:
            if (fn is not self._fn
                    and "was exported for platforms" in str(e)):
                # an execution context placed the call on a platform
                # the export does not cover (e.g. an explicit
                # default_device override): fall back to the plain
                # jit for this signature — correct beats cached
                with self._tlock:
                    self._table[key] = self._fn
                return self._fn(*args)
            raise

    def _resolve(self, key, args):
        from ..obs.compile_watch import COMPILE_WATCH
        with self._tlock:
            fn = self._table.get(key)
            if fn is not None:
                return fn
            import jax
            from jax import export as jax_export
            ename = (f"{self._name}.sig"
                     + hashlib.sha256(repr(key).encode())
                     .hexdigest()[:12])
            try:
                exp = load(ename, self._fp)
                status = "miss" if exp is None else "hit"
            except AotMismatch:     # refused + quarantined; re-export
                exp, status = None, "refused"
            if exp is None:
                avals = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(tuple(x.shape),
                                                   x.dtype)
                    if hasattr(x, "shape") and hasattr(x, "dtype")
                    else x, args)
                try:
                    exp = jax_export.export(self._fn)(*avals)
                    save(ename, self._fp, exp)
                except Exception:   # noqa: BLE001 — an unexportable
                    # program (exotic pytree/op) must never break the
                    # dispatch: fall back to the plain jit for this
                    # signature; the entry simply never persists
                    _inc("unexportable")
                    COMPILE_WATCH.stamp_aot("unexportable")
                    self._table[key] = self._fn
                    return self._fn
            COMPILE_WATCH.stamp_aot(status)
            fn = _named_jit(getattr(self._fn, "__name__", self._name),
                            exp.call)
            self._table[key] = fn
            return fn

    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    def warm(self, *avals):
        """Compile, without running it, the program a call at `avals`
        (`jax.ShapeDtypeStruct` leaves) dispatches: the signature is
        resolved as a call resolves it (read-through, or export and
        write-through), so what is compiled is the exported module's
        program, not the plain jit's (utils/warmup.py)."""
        key = self._sig_key(avals)
        fn = self._table.get(key) or self._resolve(key, avals)
        fn.lower(*avals).compile()

    def _cache_size(self) -> int:
        # dedupe by identity: every export-failure fallback signature
        # stores the SAME underlying jit, and summing it once per
        # entry would inflate the serve zero-recompile probes
        seen = {id(f): f for f in self._table.values()}
        return sum(int(f._cache_size()) for f in seen.values())

    def __getattr__(self, name):
        return getattr(self._fn, name)


def wrap_jit(name: str, fn, fingerprint: str):
    """AOT-wrap `fn` when the store is on (the rule, module
    docstring), else return it unchanged — the one-line integration
    hook `_phase_fns` / `_solve_packed_fn` and the mesh builders
    call."""
    if not enabled():
        return fn
    return AotJit(name, fn, fingerprint)
