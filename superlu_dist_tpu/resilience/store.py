"""Durable factor store: crash-safe persistence of factorizations.

A factorization costs minutes at production scale while a solve costs
milliseconds — so a replica restart that drops
process memory is a multi-minute outage PER HOT KEY unless the factors
survive on disk.  This module is the persistence tier under
`serve/factor_cache.py` (`SLU_FT_STORE=dir`): write-through on every
fresh factorization, read-through on every full-key miss, so a
`kill -9`'d replica boots warm.

Durability discipline:

  * atomic rename — entries are written tmp+fsync+`os.replace`
    (utils/io.atomic_write_bytes), so a crash mid-write leaves the old
    entry (or nothing), never a torn file;
  * ABFT-lite checksum — sha256 over the factor arrays' bytes, stored
    in the payload and recomputed on load; a flipped bit anywhere in
    the numeric payload (disk rot, truncation, chaos `store_flip`)
    quarantines the entry instead of serving corrupted factors;
  * format version — an entry written by an incompatible layout is
    quarantined, not misinterpreted;
  * schedule-layout fingerprint — device flats are only valid against
    the slab layout the CURRENT env knobs produce (SLU_COOP_MB etc.
    move offsets); a mismatch quarantines rather than serving
    factors misaligned against a rebuilt schedule.

Quarantine renames the file to `<entry>.quarantined` — the evidence
survives for forensics, the load path never sees it again, and the
next factorization's write-through replaces it.

Multi-writer sharing (fleet/).  One store directory may be mounted by
N replica PROCESSES as a shared warm tier.  The discipline that makes
that safe is already the single-process one, held cross-process:
writes stage into per-process tmp files (utils/io.atomic_write_bytes
carries the writer's pid in the tmp name on top of mkstemp's O_EXCL
uniqueness) and land by atomic rename, so two replicas racing a key
never interleave bytes — the loser's complete entry simply replaces
the winner's complete, byte-identical entry.  Reads treat EVERY
concurrent-rename surprise as a miss, never an error: an entry
quarantined or replaced by another replica between the existence
check and the open is indistinguishable from absence, and the caller
re-factors (or, under fleet single-flight, adopts the next published
copy).  Cross-process single-flight itself — a cold key factoring
once across the pool — is layered above by fleet/lease.py, keyed on
the same entry names.

What is stored: the plan (FactorPlan strips its jit caches via
__getstate__), effective options, the original matrix (refinement
residuals need A), and the factor arrays converted to host numpy.
Device handles are rebuilt on load from the plan's schedule.

Mesh-resident handles (ISSUE 17).  The `dist` backend's factors live
sharded over a device mesh, but their GLOBAL flats are ordinary
ndev-concatenated device-major arrays — gathering them to host numpy
(kind="dist", with the mesh shape + axis names alongside) makes the
entry every bit as durable as a single-device one.  The asymmetry is
on LOAD: rebuilding needs a live mesh of the IDENTICAL shape to
re-shard onto, so a store opened without one (`store.mesh` unset — a
single-device replica reading a shared warm tier) REFUSES the entry
typed (DistMeshUnavailable → `factor_store.refused_dist`) without
quarantining it: the entry is valid, THIS process just can't host it,
and the mesh replica that can must still find it intact.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import threading

import numpy as np

from .. import flags
from ..models.gssvx import (LUFactorization, factor_arrays,
                            factors_finite)
from ..sparse import CSRMatrix
from ..utils.io import atomic_write_bytes
from ..utils.stats import Stats
from . import chaos

FORMAT_VERSION = 1
SUFFIX = ".slufactor"
# file framing: magic+version, then sha256 over the pickle blob, then
# the blob.  The outer digest catches a flipped bit ANYWHERE in the
# entry (plan, matrix, metadata — not just factor arrays); the inner
# per-array checksum (payload["checksum"]) is the ABFT-lite layer that
# additionally survives the rebuild (it is recomputed from the
# reconstructed handle, so a deserialization bug that mangles arrays
# is caught even when the bytes on disk were pristine).
_MAGIC = b"SLUF\x01"


class StoreCorrupt(RuntimeError):
    """A persisted entry failed verification (version, key echo,
    checksum, layout); the load path quarantines and re-factors."""


class DistMeshUnavailable(RuntimeError):
    """A kind="dist" entry is valid but THIS process cannot host it
    (no `store.mesh`, or a different mesh shape/axes than the factors
    were sharded over).  A typed refusal, NOT corruption: the load
    path counts `factor_store.refused_dist` and returns a miss
    without quarantining — the entry stays intact for a replica whose
    mesh matches."""


def checksum_arrays(arrays) -> str:
    """sha256 over the factor arrays' raw bytes, in order — the
    ABFT-lite content signature."""
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def entry_name(key) -> str:
    """Filesystem name for a cache key: hash of all three key legs
    (pattern, values, options) — collision-safe and path-safe."""
    h = hashlib.sha256()
    h.update(key.pattern.encode())
    h.update(b"\x00")
    h.update(key.values.encode())
    h.update(b"\x00")
    h.update(repr(key.options).encode())
    return h.hexdigest()[:40] + SUFFIX


def _entry_arrays(lu: LUFactorization):
    """The numeric payload of a handle as host numpy: factor_arrays
    for host/jax backends, the gathered global flats for dist (the
    mesh-sharded arrays are fully addressable, so np.asarray assembles
    the device-major concatenation — exactly what device_put with the
    same NamedSharding re-shards on load)."""
    if lu.backend == "dist":
        d = lu.device_lu
        return [np.asarray(d.L_flat), np.asarray(d.U_flat),
                np.asarray(d.Li_flat), np.asarray(d.Ui_flat)]
    return factor_arrays(lu)


def _mesh_legs(mesh) -> tuple:
    """Shape signature a dist entry is valid against: ordered
    (axis-name, size) pairs."""
    return tuple((str(a), int(mesh.shape[a])) for a in mesh.axis_names)


def _device_layout(lu: LUFactorization):
    """Slab-layout fingerprint of a device handle's schedule; None for
    host factors (panel layout is env-independent)."""
    d = lu.device_lu
    if d is None:
        return None
    s = d.schedule
    return (int(s.L_total), int(s.U_total), int(s.Li_total),
            int(s.Ui_total), int(getattr(s, "upd_pad", 0)),
            len(s.groups))


class FactorStore:
    """Directory-backed store of LUFactorization payloads.

    Thread-safe; counters go to the injected metrics object
    (duck-typed `.inc`) under `factor_store.*`."""

    def __init__(self, root: str, metrics=None, mesh=None) -> None:
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)
        self._metrics = metrics
        self._lock = threading.Lock()
        # live device mesh kind="dist" entries rebuild onto (set by
        # FactorCache when serving mesh-resident); None ⇒ dist
        # entries refuse typed on load
        self.mesh = mesh

    def _inc(self, name: str) -> None:
        if self._metrics is not None:
            self._metrics.inc(name)

    def path_for(self, key) -> str:
        return os.path.join(self.root, entry_name(key))

    def contains(self, key) -> bool:
        return os.path.exists(self.path_for(key))

    def entries(self) -> list[str]:
        return sorted(p for p in os.listdir(self.root)
                      if p.endswith(SUFFIX))

    def quarantined(self) -> list[str]:
        return sorted(p for p in os.listdir(self.root)
                      if p.endswith(".quarantined"))

    # -- write path ----------------------------------------------------

    def save(self, key, lu: LUFactorization) -> str | None:
        """Persist `lu` under `key` atomically; returns the path."""
        arrays = _entry_arrays(lu)
        if lu.backend == "dist":
            kind = "dist"
        elif lu.backend == "host":
            kind = "host"
        elif hasattr(lu.device_lu, "panels"):
            kind = "staged"
        else:
            kind = "device"
        a = lu.a
        payload = {
            "format": FORMAT_VERSION,
            "key": key,
            "backend": lu.backend,
            "kind": kind,
            "options": lu.effective_options,
            "plan": lu.plan,
            "a": (None if a is None else
                  (a.m, a.n, np.asarray(a.indptr),
                   np.asarray(a.indices), np.asarray(a.data))),
            "arrays": [np.ascontiguousarray(x) for x in arrays],
            "dtype": (str(np.dtype(lu.device_lu.dtype))
                      if lu.device_lu is not None else None),
            "tiny_pivots": int(getattr(
                lu.host_lu if lu.backend == "host" else lu.device_lu,
                "tiny_pivots", 0)),
            "layout": _device_layout(lu),
            "checksum": checksum_arrays(arrays),
        }
        if kind == "dist":
            d = lu.device_lu
            # the mesh signature the flats were sharded over: load
            # refuses (typed) unless the reader's mesh matches
            payload["mesh_shape"] = _mesh_legs(d.mesh)
            payload["dist_axis"] = (d.axis if isinstance(d.axis, str)
                                    or d.axis is None
                                    else tuple(d.axis))
        blob = pickle.dumps(payload, protocol=4)
        framed = _MAGIC + hashlib.sha256(blob).digest() + blob
        # chaos site: a slow shared warm tier (store_latency) — the
        # fleet drill's stand-in for object-store write latency
        chaos.maybe_sleep("store_latency")
        atomic_write_bytes(self.path_for(key), framed)
        self._inc("factor_store.saves")
        return self.path_for(key)

    # -- read path -----------------------------------------------------

    def load(self, key) -> LUFactorization | None:
        """Read-through lookup: a verified handle, or None (absent OR
        quarantined — the caller re-factors either way)."""
        path = self.path_for(key)
        if not os.path.exists(path):
            self._inc("factor_store.misses")
            return None
        loaded = self._load_path(path, expect_key=key)
        if loaded is None:
            return None
        self._inc("factor_store.hits")
        return loaded[1]

    def _load_path(self, path: str, expect_key=None):
        """Read + verify one entry: (key, handle), or None (entry
        vanished concurrently, or failed verification → quarantined).
        NOTHING is unpickled before the sha256 frame digest passes —
        pickle never sees unverified bytes."""
        chaos.maybe_sleep("store_latency")
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:
            # quarantined/removed by a concurrent loader — possibly
            # in ANOTHER REPLICA PROCESS — between the caller's
            # existence check and our open: a miss, not an error —
            # the caller re-factors
            self._inc("factor_store.misses")
            return None
        # chaos site: one flipped bit in the persisted entry — the
        # fault the checksum exists to catch
        data = chaos.maybe_flip_bit("store_flip", data)
        try:
            if data[:len(_MAGIC)] != _MAGIC:
                raise StoreCorrupt("bad magic / truncated entry")
            digest = data[len(_MAGIC):len(_MAGIC) + 32]
            blob = data[len(_MAGIC) + 32:]
            if hashlib.sha256(blob).digest() != digest:
                raise StoreCorrupt("entry digest mismatch")
            payload = pickle.loads(blob)
            if payload.get("format") != FORMAT_VERSION:
                raise StoreCorrupt(
                    f"format {payload.get('format')} != "
                    f"{FORMAT_VERSION}")
            if expect_key is not None and payload["key"] != expect_key:
                raise StoreCorrupt("key echo mismatch")
            lu = self._rebuild(payload)
            if checksum_arrays(_entry_arrays(lu)) \
                    != payload["checksum"]:
                raise StoreCorrupt("factor checksum mismatch")
            if not factors_finite(lu):
                raise StoreCorrupt("persisted factors non-finite")
            return payload["key"], lu
        except DistMeshUnavailable as e:
            # typed refusal, NOT corruption: the entry is valid for a
            # mesh this process doesn't have — leave it on disk for
            # the replica that does, count it, report a miss
            from .. import obs
            self._inc("factor_store.refused_dist")
            obs.instant("resilience.store_refused_dist",
                        cat="resilience",
                        args={"entry": os.path.basename(path),
                              "reason": str(e)[:200]})
            return None
        except Exception as e:
            self.quarantine(path, reason=repr(e))
            return None

    def _rebuild(self, payload) -> LUFactorization:
        plan = payload["plan"]
        a = payload["a"]
        mat = (None if a is None else
               CSRMatrix(a[0], a[1], a[2], a[3], a[4]))
        arrays = payload["arrays"]
        kind = payload["kind"]
        st = Stats()
        if kind == "dist":
            # mesh-resident rebuild: re-shard the persisted global
            # flats onto the CURRENT process's mesh.  The warm path is
            # real — device_put of the verified flats, no
            # refactorization — but only onto the identical mesh
            # signature; anything else refuses typed.
            mesh = self.mesh
            if mesh is None:
                raise DistMeshUnavailable(
                    "kind=dist entry needs a live device mesh "
                    "(store.mesh unset: single-device reader)")
            if _mesh_legs(mesh) != tuple(payload["mesh_shape"]):
                raise DistMeshUnavailable(
                    f"mesh {_mesh_legs(mesh)} != saved "
                    f"{tuple(payload['mesh_shape'])}")
            arrays = payload["arrays"]
            if len(arrays) != 4:
                raise StoreCorrupt("dist payload needs 4 flats")
            if not all(np.isfinite(x).all() for x in arrays):
                # factors_finite is trivially True for live dist
                # handles (mesh-bound probe), so the finiteness leg of
                # verification runs here on the host flats instead
                raise StoreCorrupt("persisted dist factors non-finite")
            import jax
            from jax.sharding import NamedSharding, PartitionSpec
            from ..ops import batched
            from ..parallel import factor_dist as fd
            axis, ndev = fd._resolve_axis(mesh, payload["dist_axis"])
            sched = batched.get_schedule(plan, ndev)
            shard = NamedSharding(mesh, PartitionSpec(axis))
            L, U, Li, Ui = (jax.device_put(x, shard) for x in arrays)
            dev = fd.DistLU(plan=plan, mesh=mesh, axis=axis,
                            dtype=np.dtype(payload["dtype"]),
                            schedule=sched, L_flat=L, U_flat=U,
                            Li_flat=Li, Ui_flat=Ui,
                            tiny_pivots=payload["tiny_pivots"])
            lu = LUFactorization(plan=plan, backend="dist",
                                 device_lu=dev, a=mat, stats=st)
            if payload.get("layout") is not None \
                    and _device_layout(lu) != payload["layout"]:
                raise StoreCorrupt(
                    "schedule layout changed since save (env knobs "
                    "moved slab offsets); refusing misaligned factors")
            lu.options = payload["options"]
            st.lu_nnz = plan.lu_nnz()
            return lu
        if kind == "host":
            from ..ops.ref_multifrontal import HostLU
            ns = plan.frontal.nsuper
            if len(arrays) != 4 * ns:
                raise StoreCorrupt(
                    f"host payload has {len(arrays)} panels for "
                    f"{ns} supernodes")
            chunks = [arrays[i * ns:(i + 1) * ns] for i in range(4)]
            host_lu = HostLU(plan=plan, L=chunks[0], U=chunks[1],
                             Linv=chunks[2], Uinv=chunks[3],
                             tiny_pivots=payload["tiny_pivots"])
            lu = LUFactorization(plan=plan, backend="host",
                                 host_lu=host_lu, a=mat, stats=st)
        else:
            import jax.numpy as jnp
            from ..ops import batched
            sched = batched.get_schedule(plan, 1)
            dtype = np.dtype(payload["dtype"])
            if kind == "staged":
                if len(arrays) % 4:
                    raise StoreCorrupt("staged payload not 4-aligned")
                panels = [tuple(jnp.asarray(x)
                                for x in arrays[i:i + 4])
                          for i in range(0, len(arrays), 4)]
                dev = batched.StagedLU(
                    plan=plan, schedule=sched, dtype=dtype,
                    panels=panels,
                    tiny_pivots=payload["tiny_pivots"])
            else:
                if len(arrays) != 4:
                    raise StoreCorrupt("device payload needs 4 flats")
                dev = batched.DeviceLU(
                    plan=plan, schedule=sched, dtype=dtype,
                    L_flat=jnp.asarray(arrays[0]),
                    U_flat=jnp.asarray(arrays[1]),
                    Li_flat=jnp.asarray(arrays[2]),
                    Ui_flat=jnp.asarray(arrays[3]),
                    tiny_pivots=payload["tiny_pivots"])
            lu = LUFactorization(plan=plan, backend="jax",
                                 device_lu=dev, a=mat, stats=st)
            if payload.get("layout") is not None \
                    and _device_layout(lu) != payload["layout"]:
                raise StoreCorrupt(
                    "schedule layout changed since save (env knobs "
                    "moved slab offsets); refusing misaligned factors")
        lu.options = payload["options"]
        st.lu_nnz = plan.lu_nnz()
        return lu

    # -- quarantine / warm boot ---------------------------------------

    def quarantine(self, path: str, reason: str = "") -> None:
        """Move a failed entry aside so it is never loaded again; the
        loudest store event there is (a quarantine means bits rotted
        or a writer lied) — counted and traced."""
        from .. import obs
        with self._lock:
            try:
                os.replace(path, path + ".quarantined")
            except OSError:
                pass
        self._inc("factor_store.quarantined")
        obs.instant("resilience.store_quarantine", cat="resilience",
                    args={"entry": os.path.basename(path),
                          "reason": reason[:200]})

    def warm_boot(self, cache) -> int:
        """Load every verified entry into `cache` (FactorCache) — the
        explicit eager variant of read-through for a fresh replica
        that wants its working set resident before traffic."""
        n = 0
        for name in self.entries():
            # one verified read per entry; the key comes from the
            # verified payload itself (never from unverified bytes)
            loaded = self._load_path(os.path.join(self.root, name))
            if loaded is not None:
                key, lu = loaded
                cache.put(key, lu)
                self._inc("factor_store.hits")
                n += 1
        return n

    def stats(self) -> dict:
        return {"entries": len(self.entries()),
                "quarantined": len(self.quarantined()),
                "root": self.root}


def store_from_env(metrics=None) -> FactorStore | None:
    """The `SLU_FT_STORE=dir` hookup used by FactorCache."""
    d = flags.env_str("SLU_FT_STORE").strip()
    return FactorStore(d, metrics=metrics) if d else None
