"""Mesh-resident serving (ISSUE 17): the serve tier on a 2-CPU-device
mesh.

What tier-1 pins here:

* the serve-path mesh solve is bitwise `array_equal` to the sequential
  one-device `mesh_oracle_solve` of the SAME lsum layout (NOREFINE —
  the oracle models the raw trisolve, not the refinement loop);
* a prefactored key serves a load burst with ZERO recompiles, counted
  both ways (obs.COMPILE_WATCH misses AND dist solve-arm jit-cache
  growth);
* flight records carry the replica's `mesh` leg in the combined queue
  event (`arm="dist"`), and stay `mesh=None` on single-device serving;
* Options.mesh_shape is a factor-key leg BOTH WAYS: mesh and
  single-device requests can never serve each other — across the
  in-memory cache, the durable store's entry names, and the fleet
  ring coordinate;
* kind="dist" store entries round-trip onto an identical mesh and
  refuse TYPED (factor_store.refused_dist, no quarantine) on a
  single-device or reshaped reader;
* a mesh replica is ONE ring member with a device-count capacity
  weight (keyspace share scales; adding capacity moves keys only TO
  the resized replica);
* mesh AOT warm boot: a rebuilt world (fresh plan objects) serves the
  shard_map'd factor + merged solve from deserialized exports
  (hits >= 2, misses == 0) bitwise-identically.
"""

import os

import numpy as np
import pytest

import jax

from superlu_dist_tpu import Options, obs
from superlu_dist_tpu.obs import flight
from superlu_dist_tpu.options import IterRefine
from superlu_dist_tpu.parallel import factor_dist
from superlu_dist_tpu.parallel.grid import make_solver_mesh
from superlu_dist_tpu.plan.plan import plan_factorization
from superlu_dist_tpu.resilience import aot
from superlu_dist_tpu.resilience.store import FactorStore, entry_name
from superlu_dist_tpu.serve import (Metrics, ServeConfig, SolveService,
                                    run_load, solve_jit_cache_size)
from superlu_dist_tpu.serve.factor_cache import matrix_key
from superlu_dist_tpu.utils.testmat import laplacian_3d

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs 2 virtual devices")


@pytest.fixture(autouse=True)
def _flight_off():
    flight.configure(enabled=False)
    yield
    flight.configure(enabled=False)


def _mesh2():
    """The serve-shaped 2-device mesh (solver axis names r/c/z — the
    _mesh_leg/flight spelling is '2x1x1')."""
    return make_solver_mesh(2, 1, 1).mesh


def _mesh_service(mesh=None, **kw):
    kw.setdefault("max_linger_s", 0.002)
    return SolveService(ServeConfig(mesh=mesh or _mesh2(), **kw),
                        metrics=Metrics())


_OPTS = Options(factor_dtype="float64")


# --------------------------------------------------------------------
# bitwise: serve path vs the sequential mesh oracle
# --------------------------------------------------------------------

@pytest.mark.parametrize("arm", [None, "merged"])
def test_serve_path_bitwise_vs_mesh_oracle(monkeypatch, arm):
    """End to end through SolveService on a mesh: the batched,
    shard_map'd solve of a keyed request bit-matches mesh_oracle_solve
    (the sequential one-device execution of the SAME merged layout:
    the ranged reconciliation all-reduces v + 0, exactly v), with no
    variable set as under an explicit SLU_TRISOLVE=merged: a mesh
    replica's narrow sweep is the trisolve arm's.
    NOREFINE: default serving refines (gssvx), which the oracle
    deliberately does not model."""
    monkeypatch.delenv("SLU_TRISOLVE", raising=False)
    if arm:
        monkeypatch.setenv("SLU_TRISOLVE", arm)
    a = laplacian_3d(5)
    svc = _mesh_service()
    try:
        key = svc.prefactor(
            a, _OPTS.replace(iter_refine=IterRefine.NOREFINE))
        lu = svc.cache.peek(key)
        assert lu is not None and lu.backend == "dist"
        dlu, plan = lu.device_lu, lu.plan
        b = np.random.default_rng(7).standard_normal(a.n)
        x_serve = np.asarray(svc.solve(key, b))
        # the oracle takes/returns FACTOR ordering; apply the plan's
        # row/col transforms exactly as models/gssvx.solve does
        bf = np.zeros(a.n, np.float64)
        bf[plan.final_row] = b * plan.row_scale
        xo = factor_dist.mesh_oracle_solve(dlu, bf[:, None])[:, 0]
        x_oracle = xo[plan.final_col] * plan.col_scale
        assert np.array_equal(x_serve, x_oracle), (
            f"maxdiff={np.abs(x_serve - x_oracle).max()}")
        # (rhs_sharded, merged): the narrow bucket's program is the
        # merged one, and no replicated-X sweep was built beside it
        built = {k[4:6] for k in plan._dist_solve_fns}
        assert (False, True) in built and (False, False) not in built
    finally:
        svc.close()


# --------------------------------------------------------------------
# zero recompiles under load (both counters)
# --------------------------------------------------------------------

def test_mesh_load_recompile_free_and_all_ok():
    """A prefactored mesh key serves a concurrent burst with zero
    recompiles — pinned through BOTH counters the bench gate uses:
    the obs compile-watch miss count and the dist solve-arm jit-cache
    size (growth there is a recompile even if a wrapper misattributes
    it)."""
    a = laplacian_3d(5)
    svc = _mesh_service()
    try:
        key = svc.prefactor(a, _OPTS)
        lu = svc.cache.peek(key)
        jit_before = solve_jit_cache_size(lu)
        miss_before = obs.COMPILE_WATCH.misses()
        report = run_load(svc, [key], requests=32, concurrency=4,
                          seed=11)
        assert report["by_status"] == {"ok": 32}
        assert obs.COMPILE_WATCH.misses() - miss_before == 0
        assert solve_jit_cache_size(lu) - jit_before == 0
    finally:
        svc.close()


# --------------------------------------------------------------------
# flight: the combined queue event names the mesh leg
# --------------------------------------------------------------------

def test_flight_queue_event_carries_mesh_leg():
    flight.configure(enabled=True)
    a = laplacian_3d(4)
    svc = _mesh_service()
    try:
        key = svc.prefactor(a, _OPTS)
        info = {}
        svc.solve(key, np.ones(a.n), info=info)
        rec = flight.get_recorder().lookup(info["request_id"])
        assert rec is not None and rec["outcome"] == "ok"
        queue = [e for e in rec["events"] if e["stage"] == "queue"]
        assert queue, [e["stage"] for e in rec["events"]]
        assert queue[-1]["mesh"] == "2x1x1"
        assert queue[-1]["arm"] == "dist"
    finally:
        svc.close()


def test_flight_mesh_leg_none_on_single_device():
    flight.configure(enabled=True)
    a = laplacian_3d(4)
    svc = SolveService(ServeConfig(backend="host", mesh=None),
                       metrics=Metrics())
    try:
        key = svc.prefactor(a, _OPTS)
        info = {}
        svc.solve(key, np.ones(a.n), info=info)
        rec = flight.get_recorder().lookup(info["request_id"])
        queue = [e for e in rec["events"] if e["stage"] == "queue"]
        assert queue and queue[-1]["mesh"] is None
    finally:
        svc.close()


# --------------------------------------------------------------------
# factor-key residency leg: both-ways miss
# --------------------------------------------------------------------

def test_mesh_shape_is_a_key_leg_both_ways(tmp_path):
    """A mesh replica's keys and a single-device replica's keys for
    the SAME matrix+options never collide: the cache key, the store
    entry name, and the fleet ring coordinate all differ — and an
    explicit caller-set mesh_shape survives stamping."""
    a = laplacian_3d(4)
    svc = _mesh_service(store_dir=str(tmp_path))
    try:
        stamped = svc._stamp_mesh(_OPTS)
        assert stamped.mesh_shape == (2, 1, 1)
        # explicit residency pin wins over the replica stamp
        pinned = svc._stamp_mesh(_OPTS.replace(mesh_shape=(4, 1, 1)))
        assert pinned.mesh_shape == (4, 1, 1)

        key_mesh = matrix_key(a, stamped)
        key_plain = matrix_key(a, _OPTS)
        assert key_mesh != key_plain
        assert entry_name(key_mesh) != entry_name(key_plain)
        from superlu_dist_tpu.fleet.pool import _route_key
        assert _route_key(key_mesh) != _route_key(key_plain)

        # a mesh-factored entry is invisible to a single-device
        # read-through of the same matrix (different entry name —
        # miss, not refusal)
        assert svc.prefactor(a, _OPTS) == key_mesh
        store = svc.cache.store
        assert store is not None and store.contains(key_mesh)
        assert not store.contains(key_plain)
    finally:
        svc.close()


# --------------------------------------------------------------------
# durable store: dist round-trip + typed refusal
# --------------------------------------------------------------------

def _dist_entry(tmp_path):
    """One service-written kind='dist' entry; returns (key, lu, root)."""
    a = laplacian_3d(4)
    svc = _mesh_service(store_dir=str(tmp_path))
    try:
        key = svc.prefactor(a, _OPTS)
        lu = svc.cache.peek(key)
        assert svc.cache.store.contains(key)
        return key, lu
    finally:
        svc.close()


def test_store_dist_roundtrip_identical_mesh(tmp_path):
    key, lu = _dist_entry(tmp_path)
    m = Metrics()
    reader = FactorStore(str(tmp_path), metrics=m, mesh=_mesh2())
    got = reader.load(key)
    assert got is not None and got.backend == "dist"
    assert m.counter("factor_store.hits") == 1
    for name in ("L_flat", "U_flat", "Li_flat", "Ui_flat"):
        assert np.array_equal(np.asarray(getattr(got.device_lu, name)),
                              np.asarray(getattr(lu.device_lu, name)))
    # the rebuilt handle solves — and bit-matches the saved one's
    # oracle (same layout, same flats)
    b = np.random.default_rng(3).standard_normal((got.plan.n, 1))
    assert np.array_equal(factor_dist.mesh_oracle_solve(got.device_lu, b),
                          factor_dist.mesh_oracle_solve(lu.device_lu, b))


def test_store_dist_refusal_is_typed_not_quarantine(tmp_path):
    """A kind='dist' entry on a reader without the matching mesh is a
    TYPED refusal: counted (factor_store.refused_dist), reported as a
    miss, and the entry stays on disk for the replica that can host
    it — never quarantined as corruption."""
    key, _lu = _dist_entry(tmp_path)
    # single-device reader: no mesh at all
    m1 = Metrics()
    r1 = FactorStore(str(tmp_path), metrics=m1, mesh=None)
    assert r1.load(key) is None
    assert m1.counter("factor_store.refused_dist") == 1
    # reshaped reader: same device count, different axis signature
    from jax.sharding import Mesh
    m2 = Metrics()
    r2 = FactorStore(str(tmp_path), metrics=m2,
                     mesh=Mesh(np.array(jax.devices()[:2]), ("d",)))
    assert r2.load(key) is None
    assert m2.counter("factor_store.refused_dist") == 1
    assert r1.quarantined() == [] and r2.quarantined() == []
    assert r1.contains(key)


# --------------------------------------------------------------------
# fleet: a mesh replica is one ring member with capacity weight
# --------------------------------------------------------------------

def test_hashring_capacity_scales_keyspace_share():
    from superlu_dist_tpu.fleet.router import HashRing
    ring = HashRing(["mesh8", "solo"], vnodes=64,
                    capacities={"mesh8": 8.0})
    shares = ring.shares(samples=4096)
    # an 8x-capacity replica owns ~8/9 of the keyspace (generous
    # band: vnode placement is hash-noisy at 64 vnodes)
    assert 0.75 <= shares["mesh8"] <= 0.97, shares
    assert abs(sum(shares.values()) - 1.0) < 1e-9


def test_hashring_capacity_change_moves_keys_only_to_resized():
    """Karger minimal movement under a capacity change: growing one
    replica's weight adds only ITS vnodes, so every re-homed key lands
    on the resized replica — siblings never trade keys."""
    from superlu_dist_tpu.fleet.router import HashRing
    names = ["a", "b", "c"]
    r1 = HashRing(names, vnodes=64)
    r2 = HashRing(names, vnodes=64, capacities={"c": 3.0})
    keys = [f"k{i}" for i in range(512)]
    moved = [k for k in keys if r1.home(k) != r2.home(k)]
    assert moved, "capacity change moved nothing; vnode hashing drifted"
    assert all(r2.home(k) == "c" for k in moved)


def test_replica_pool_derives_mesh_capacity():
    import types
    from superlu_dist_tpu.fleet.pool import (ReplicaPool,
                                             _endpoint_capacity)
    mesh_ep = types.SimpleNamespace(
        config=types.SimpleNamespace(mesh=_mesh2()))
    solo_ep = types.SimpleNamespace(config=types.SimpleNamespace(
        mesh=None))
    assert _endpoint_capacity(mesh_ep) == 2.0
    assert _endpoint_capacity(solo_ep) == 1.0
    pool = ReplicaPool({"m": mesh_ep, "s": solo_ep}, vnodes=32)
    assert pool.ring.capacities["m"] == 2.0
    assert pool.ring.capacities["s"] == 1.0
    # an explicit override still wins (drill socket stubs)
    pool2 = ReplicaPool({"m": mesh_ep, "s": solo_ep}, vnodes=32,
                        capacities={"m": 4.0})
    assert pool2.ring.capacities["m"] == 4.0


# --------------------------------------------------------------------
# mesh AOT warm boot (in-process drill)
# --------------------------------------------------------------------

def test_mesh_aot_warm_boot_serves_from_exports(aot_store):
    """The in-process cold→warm drill for the shard_map'd programs: a
    rebuilt world (fresh plan objects — the fresh-process stand-in)
    deserializes the mesh factor + merged solve exports (hits >= 2,
    misses == 0) and serves bitwise-identical results."""
    mesh = _mesh2()
    a = laplacian_3d(4)
    b = np.random.default_rng(0).standard_normal((a.n, 2))

    def run():
        plan = plan_factorization(a, _OPTS)
        factor = factor_dist.make_dist_factor(plan, mesh)
        dlu = factor(plan.scaled_values(a))
        solve = factor_dist.make_dist_solve_merged(plan, mesh)
        return np.asarray(solve(dlu.L_flat, dlu.U_flat, dlu.Li_flat,
                                dlu.Ui_flat, b))

    aot.reset_stats()
    x_cold = run()                       # export write-through
    cold = aot.stats()
    assert cold["saves"] >= 2, cold      # dist_factor + merged solve
    aot.reset_stats()
    x_warm = run()                       # rebuilt world: read-through
    warm = aot.stats()
    assert warm["hits"] >= 2, warm
    assert warm["misses"] == 0 and warm["rejected"] == 0 \
        and warm["unexportable"] == 0, warm
    assert np.array_equal(x_cold, x_warm)
    assert any(p.endswith(aot.SUFFIX) for p in os.listdir(aot_store))
