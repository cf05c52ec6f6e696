"""Fleet drill: ≥3 replica processes, one shared store, one kill -9.

The multi-process proof of the fleet layer (superlu_dist_tpu/fleet/),
gated the way CHAOS.jsonl gates the single-replica story:

  1. COLD BURST — the same cold key is thrown at every replica
     simultaneously.  Cross-process single-flight (fleet/lease.py)
     must elect one leader: the pool-wide factorization count for the
     key is exactly 1, everyone else adopts the published entry.
  2. PREFACTOR — each remaining key is served once at its
     consistent-hash home (fleet/router.py), publishing every key to
     the shared store.  `fleet_factorizations_per_cold_key` — total
     factorizations across the pool over total cold keys — must be
     exactly 1.0.
  3. CHAOS LOAD + KILL — closed-loop load routed by the ring under
     injected store latency; mid-load the HOME of the hot key is
     killed with SIGKILL via the `replica_kill` chaos site (armed
     over the wire: the process dies the way `kill -9` kills it).
     The driver's clients treat the connection reset as the death
     signal, mark the replica down, and fail over along the ring.
     Gates: zero lost requests (every request reaches a final
     ok/degraded/typed outcome), zero hung workers, and WARM TAKEOVER
     — survivors absorb the victim's keys with factorizations == 0
     (they adopt from the store; they never re-factor).

All replicas append flight records to ONE shared SLU_FLIGHT_JSONL —
the fleet trace.  The drill verifies the per-process rids are
disambiguated by replica id ((replica, rid) unique across the merged
log) and that tools/trace_export.py converts it per-replica.

One JSON line is appended to SLU_FLEET_OUT (default FLEET.jsonl);
tools/regress.py gates the committed history.  Wire-up:
`python -m tools.fleet_drill` or `python bench.py --fleet`.  Knobs: SLU_FLEET_REPLICAS / SLU_FLEET_K /
SLU_FLEET_REQUESTS / SLU_FLEET_KILL_AFTER / SLU_FLEET_TTL_S.

MESH-REPLICA ARM (ISSUE 17): `SLU_FLEET_MESH=N` runs every replica
as a MESH replica — an in-process N-device CPU mesh
(utils/compat.set_cpu_devices, the shard_map'd dist backend) behind
the same SolveService front.  The same gates then prove the
mesh-resident story: cross-process single-flight holds when the
cold-key LEADER is a mesh (one dist factorization pool-wide, siblings
adopt the kind="dist" store entry), and the kill's warm takeover
re-shards persisted flats instead of re-factoring (takeover
factorizations == 0 over mesh-resident keys).

`--day` runs the DAY-IN-THE-LIFE drill instead (ISSUE 16): the
elastic fleet controller (superlu_dist_tpu/fleet/controller.py)
driving popularity-based prefactor, SLO-burn-triggered weighted shed
+ autoscale with ring-arc handoff, rolling restarts, and one SIGKILL
— gated on zero lost requests, every shed typed, one factorization
per cold key across the whole day, zero takeover factorizations and
bounded per-phase p99; appended to SLU_FLEET_DAY_OUT (default
FLEET_DAY.jsonl).  Knobs: SLU_FLEET_DAY_REQUESTS /
SLU_FLEET_DAY_P99_MS.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

_AUTHKEY = b"slu-fleet-drill"


def _repo() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _drill_matrices(k: int, n_keys: int):
    """The drill's key family: distinct PATTERNS (different grid
    sizes), so the hash ring spreads them across homes.  Sizes stay
    tiny — the drill proves coordination, not kernels."""
    from superlu_dist_tpu.utils.testmat import laplacian_3d
    return [laplacian_3d(k + i) for i in range(n_keys)]


# --------------------------------------------------------------------
# replica process
# --------------------------------------------------------------------

def run_replica(name: str, socket_path: str, store_dir: str,
                k: int, n_keys: int, factor_delay_s: float,
                ttl_s: float, mesh_ndev: int = 0) -> None:
    """One replica: a SolveService on the shared store with fleet
    single-flight, served over a unix socket.  Protocol: one pickled
    dict per request — solve / stats / chaos / chaos_off / die /
    ping / close.  `mesh_ndev` > 0 makes this a MESH replica: an
    in-process mesh of that many virtual CPU devices, factoring and
    solving through the shard_map'd dist backend."""
    if mesh_ndev:
        # before any jax backend init: the device count is a
        # process-creation property
        from superlu_dist_tpu.utils.compat import set_cpu_devices
        set_cpu_devices(int(mesh_ndev))
    from multiprocessing.connection import Listener

    import numpy as np

    from superlu_dist_tpu import Options
    from superlu_dist_tpu.fleet.lease import FleetCoordinator
    from superlu_dist_tpu.fleet.policy import QosGate
    from superlu_dist_tpu.models.gssvx import factorize
    from superlu_dist_tpu.obs import flight, slo
    from superlu_dist_tpu.resilience import chaos
    from superlu_dist_tpu.resilience.breaker import CircuitBreaker
    from superlu_dist_tpu.resilience.store import FactorStore
    from superlu_dist_tpu.serve import (DegradedResult, FactorCache,
                                        ServeConfig, ServeError,
                                        SolveService, matrix_key)

    flight.configure()          # adopt SLU_FLIGHT_JSONL from the env
    slo.configure()             # adopt SLU_SLO (day drill sets it)
    mats = _drill_matrices(k, n_keys)
    opts = Options(factor_dtype="float64")
    mesh_obj = None
    if mesh_ndev:
        import jax
        from jax.sharding import Mesh
        mesh_obj = Mesh(np.array(jax.devices()[:int(mesh_ndev)]),
                        axis_names=("z",))

    def slow_factorize(a, options, plan):
        # stand-in for the minutes-long production factorization:
        # wide enough a window that the cold burst genuinely races
        if factor_delay_s > 0:
            time.sleep(factor_delay_s)
        from superlu_dist_tpu.plan.plan import plan_factorization
        if plan is None:
            plan = plan_factorization(a, options)
        if mesh_obj is not None:
            return factorize(a, options, plan=plan, backend="dist",
                             grid=mesh_obj)
        return factorize(a, options, plan=plan, backend="host")

    store = FactorStore(store_dir)
    qos = QosGate()             # fractions set over the wire ("shed")
    coord = FleetCoordinator(store_dir, ttl_s=ttl_s, poll_s=0.02)
    svc = SolveService(ServeConfig(
        max_queue_depth=1024, backend="host", degraded=True,
        factor_retries=1, retry_base_s=0.01,
        breaker_threshold=3, breaker_cooldown_s=1.0, fleet=False,
        qos=qos, mesh=mesh_obj),
        cache=FactorCache(
            backend="host", store=store, fleet=coord,
            breaker=CircuitBreaker(threshold=3, cooldown_s=1.0),
            factorize_fn=slow_factorize, mesh=mesh_obj))
    keys = [matrix_key(m, opts) for m in mats]
    key_index = {kk: i for i, kk in enumerate(keys)}

    # drill-side "fleet" registry provider: the cache's demand ledger
    # in fleet-comparable form (drill key INDICES, not CacheKeys) plus
    # the QoS gate — so the replica's export snapshot carries
    # everything obs/aggregate.py needs to merge popularity and the
    # remote gather (signals_from_snapshots) needs no "stats" cmd
    from superlu_dist_tpu.obs import export as obs_export
    from superlu_dist_tpu.obs.registry import REGISTRY

    class _FleetLedgerProvider:
        @staticmethod
        def snapshot() -> dict:
            return {
                "popularity": [{"key_i": key_index[e["key"]],
                                "count": e["count"],
                                "resident": e["resident"]}
                               for e in svc.cache.popularity()
                               if e["key"] in key_index],
                "qos": qos.snapshot(),
            }

    REGISTRY.register("fleet", _FleetLedgerProvider())

    def handle(conn) -> None:
        rng_cache: dict = {}
        while True:
            try:
                msg = conn.recv()
            except (EOFError, OSError):
                break
            cmd = msg.get("cmd")
            try:
                if cmd == "ping":
                    conn.send({"pong": os.getpid(),
                               "replica": flight.replica_id()})
                elif cmd == "solve":
                    i = int(msg["key_i"])
                    # by_key: a KEYED submit — under the day drill's
                    # trickle this fails typed (FactorMissError) on
                    # cold keys while still seeding the cache's
                    # demand ledger, so the controller's prefactor is
                    # the thing that actually warms the fleet
                    a = keys[i] if msg.get("by_key") else mats[i]
                    seed = int(msg.get("seed", 0))
                    rng = rng_cache.setdefault(
                        seed, np.random.default_rng(seed))
                    b = rng.standard_normal(mats[i].n)
                    info: dict = {}
                    try:
                        x = svc.solve(a, b, options=opts,
                                      deadline_s=msg.get("deadline_s"),
                                      info=info,
                                      tenant=msg.get("tenant"))
                        status = ("nonfinite"
                                  if not np.all(np.isfinite(x))
                                  else "degraded"
                                  if isinstance(x, DegradedResult)
                                  else "ok")
                    except ServeError as e:
                        status = type(e).__name__
                    conn.send({"status": status,
                               "rid": info.get("request_id"),
                               "replica": flight.replica_id()})
                elif cmd == "prefactor":
                    # the controller's warm path: runs the fleet
                    # single-flight, so a concurrent prefactor of the
                    # same key elsewhere still factors ONCE pool-wide
                    i = int(msg["key_i"])
                    try:
                        svc.prefactor(mats[i], opts)
                        conn.send({"ok": True})
                    except Exception as e:  # noqa: BLE001 — typed
                        conn.send({"ok": False,         # to driver
                                   "status": type(e).__name__})
                elif cmd == "shed":
                    qos.set_fractions(dict(msg.get("fractions") or {}))
                    conn.send({"ok": True})
                elif cmd == "drain":
                    # retire protocol step (fleet/scaler.py): release
                    # every held lease so successors never wait out
                    # this replica's TTL
                    coord.release_all()
                    conn.send({"ok": True})
                elif cmd == "stats":
                    st = svc.cache.stats()
                    burn = 0.0
                    if slo.enabled():
                        for sk, rec_ in slo.snapshot()["keys"].items():
                            # "unrouted" holds front-door refusals —
                            # including this replica's OWN QoS sheds —
                            # and never sees ok traffic: feeding it
                            # back would latch the shed forever
                            # (fleet/controller.signals_from skips it
                            # for the same reason)
                            if sk == "unrouted":
                                continue
                            burn = max(
                                burn,
                                float(rec_["burn_rate_availability"]),
                                float(rec_["burn_rate_latency"]))
                    pop = [{"key_i": key_index[e["key"]],
                            "count": e["count"],
                            "resident": e["resident"]}
                           for e in svc.cache.popularity()
                           if e["key"] in key_index]
                    conn.send({
                        "replica": flight.replica_id(),
                        "pid": os.getpid(),
                        "cache": st,
                        "burn": burn,
                        "popularity": pop,
                        "qos": qos.snapshot(),
                        "breaker": (svc.cache.breaker.snapshot()
                                    if svc.cache.breaker is not None
                                    else {}),
                        "flight": {
                            k_: v for k_, v in
                            flight.snapshot().items()
                            if k_ in ("replica", "started",
                                      "finished", "by_outcome")},
                    })
                elif cmd == "obs_export":
                    # the export plane over the replica wire protocol
                    # (ISSUE 19): the same versioned record the
                    # SLU_OBS_EXPORT endpoint serves — what feeds
                    # FleetController.gather() remotely
                    svc.drain_observability()
                    conn.send(obs_export.export_snapshot())
                elif cmd == "chaos":
                    chaos.install(msg["spec"],
                                  seed=int(msg.get("seed", 0)))
                    conn.send({"ok": True})
                elif cmd == "chaos_off":
                    chaos.uninstall()
                    conn.send({"ok": True})
                elif cmd == "die":
                    # the drill's kill -9: arm the replica_kill chaos
                    # site and fire it — a SIGKILL with no cleanup
                    chaos.install(
                        f"replica_kill=1:{float(msg.get('delay', 0))}")
                    armed = chaos.maybe_replica_kill()
                    conn.send({"armed": armed})
                elif cmd == "close":
                    conn.send({"ok": True})
                    os._exit(0)
                else:
                    conn.send({"error": f"unknown cmd {cmd!r}"})
            except (EOFError, OSError):
                break

    # backlog: the drill's workers open one connection per request
    # concurrently; the Listener default of 1 refuses the burst and
    # a refused connect is indistinguishable from a dead replica
    with Listener(socket_path, family="AF_UNIX", backlog=128,
                  authkey=_AUTHKEY) as listener:
        # readiness marker: the driver polls for this file, then pings
        with open(socket_path + ".ready", "w") as f:
            f.write(str(os.getpid()))
        while True:
            conn = listener.accept()
            threading.Thread(target=handle, args=(conn,),
                             daemon=True).start()


# --------------------------------------------------------------------
# driver
# --------------------------------------------------------------------

class _ReplicaClient:
    """Driver-side request issuing with ring failover: one connection
    per request (the drill's volumes are tiny), a connection error IS
    the replica-death signal."""

    def __init__(self, sockets: dict, ring, down: set,
                 lock: threading.Lock) -> None:
        self.sockets = sockets
        self.ring = ring
        self.down = down
        self.lock = lock
        self.failovers = 0

    def _is_down(self, name: str) -> bool:
        with self.lock:
            return name in self.down

    def _mark_down(self, name: str) -> None:
        with self.lock:
            self.down.add(name)

    def request(self, order: list, msg: dict,
                timeout_s: float = 60.0,
                ignore_down: bool = False) -> dict | None:
        """Send `msg` to the first live replica in `order`, failing
        over on connection death.  A transient connect refusal is
        retried before the replica is declared dead (a full accept
        queue must not read as a kill); an EOF mid-conversation IS
        the death signal.  None = every replica refused (the 'lost'
        outcome the gate forbids).  `ignore_down` bypasses the
        down-set for post-mortem stats collection."""
        from multiprocessing.connection import Client
        for name in order:
            if not ignore_down and self._is_down(name):
                with self.lock:
                    self.failovers += 1
                continue
            for attempt in range(3):
                try:
                    with Client(self.sockets[name], family="AF_UNIX",
                                authkey=_AUTHKEY) as c:
                        c.send(msg)
                        if not c.poll(timeout_s):
                            raise EOFError("reply timeout")
                        out = c.recv()
                        out["served_by"] = name
                        return out
                except (EOFError, ConnectionResetError,
                        BrokenPipeError):
                    break          # died mid-conversation: no retry
                except (OSError, ConnectionError):
                    time.sleep(0.05)     # transient refusal: retry
            # retries exhausted or mid-flight death: mark down and
            # walk the chain — the request is NOT lost
            self._mark_down(name)
            with self.lock:
                self.failovers += 1
        return None


def run_drill(argv=()) -> dict:
    import shutil
    import tempfile

    repo = _repo()
    sys.path.insert(0, repo)
    n_replicas = max(3, int(os.environ.get("SLU_FLEET_REPLICAS", "3")))
    k = int(os.environ.get("SLU_FLEET_K", "4"))
    requests = int(os.environ.get("SLU_FLEET_REQUESTS", "48"))
    kill_after = float(os.environ.get("SLU_FLEET_KILL_AFTER", "0.33"))
    # unset or "0" -> the drill's own 20 s TTL (NOT default_ttl_s(),
    # which scales off the measured minutes-class factorization and
    # would dwarf the drill's 60 s per-request / 300 s join budgets)
    ttl_s = float(os.environ.get("SLU_FLEET_TTL_S") or 0.0) or 20.0
    # mesh-replica arm (ISSUE 17): every replica fronts an in-process
    # N-device CPU mesh and factors through the dist backend
    mesh_ndev = int(os.environ.get("SLU_FLEET_MESH", "0"))
    out_path = os.environ.get("SLU_FLEET_OUT",
                              os.path.join(repo, "FLEET.jsonl"))
    n_keys = 4
    factor_delay_s = 0.5
    workdir = tempfile.mkdtemp(prefix="slu_fleet_")
    store_dir = os.path.join(workdir, "store")
    flight_log = os.path.join(workdir, "fleet_flight.jsonl")
    os.makedirs(store_dir, exist_ok=True)

    names = [f"r{i}" for i in range(n_replicas)]
    sockets = {n: os.path.join(workdir, n + ".sock") for n in names}
    # three or more replica processes cannot share one chip: the
    # drill is a CPU correctness drill, pinned so and stamped so
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["SLU_FLIGHT_JSONL"] = flight_log     # ONE shared fleet trace
    env["SLU_FLEET_TTL_S"] = str(ttl_s)

    procs: dict = {}
    report: dict = {"mode": "fleet", "replicas": n_replicas, "k": k,
                    "requests": requests, "keys": n_keys,
                    "mesh_ndev": mesh_ndev,
                    "ts": time.strftime("%Y-%m-%dT%H:%M:%S")}
    try:
        for n in names:
            procs[n] = subprocess.Popen(
                [sys.executable, "-m", "tools.fleet_drill",
                 "--replica", "--name", n, "--socket", sockets[n],
                 "--store", store_dir, "--k", str(k),
                 "--keys", str(n_keys),
                 "--factor-delay", str(factor_delay_s),
                 "--ttl", str(ttl_s),
                 "--mesh", str(mesh_ndev)],
                cwd=repo, env=env)
        down: set = set()
        lock = threading.Lock()

        from superlu_dist_tpu import Options
        from superlu_dist_tpu.fleet.pool import _route_key
        from superlu_dist_tpu.fleet.router import HashRing
        from superlu_dist_tpu.serve import matrix_key
        ring = HashRing(names)
        client = _ReplicaClient(sockets, ring, down, lock)

        # readiness: each replica drops a .ready marker, then answers
        # pings — budget generous for cold jax imports
        deadline = time.monotonic() + 180.0
        for n in names:
            while not os.path.exists(sockets[n] + ".ready"):
                if time.monotonic() > deadline:
                    raise RuntimeError(f"replica {n} never came up")
                time.sleep(0.1)
            while client.request([n], {"cmd": "ping"}, 10.0) is None:
                if time.monotonic() > deadline:
                    raise RuntimeError(f"replica {n} never answered")
                time.sleep(0.2)
        print(f"# fleet: {n_replicas} replicas up", file=sys.stderr)

        mats = _drill_matrices(k, n_keys)
        opts = Options(factor_dtype="float64")
        keys = [matrix_key(m, opts) for m in mats]
        routes = [ring.route(_route_key(kk)) for kk in keys]

        # --- phase 1: COLD BURST — same cold key at every replica at
        # once; cross-process single-flight must factor it ONCE
        burst: list = [None] * n_replicas

        def burst_one(i: int, n: str) -> None:
            burst[i] = client.request(
                [n], {"cmd": "solve", "key_i": 0, "seed": 100 + i},
                timeout_s=120.0)

        ts = [threading.Thread(target=burst_one, args=(i, n))
              for i, n in enumerate(names)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        stats1 = {n: client.request([n], {"cmd": "stats"}, 30.0)
                  for n in names}
        burst_factorizations = sum(
            s["cache"]["factorizations"] for s in stats1.values())
        report["cold_burst"] = {
            "outcomes": [r and r["status"] for r in burst],
            "factorizations": burst_factorizations,
            "adopted": sum(s["cache"]["fleet_adopted"]
                           for s in stats1.values()),
            "store_hits": sum(s["cache"]["store_hits"]
                              for s in stats1.values()),
        }
        print(f"# fleet: cold burst factored "
              f"{burst_factorizations}x pool-wide", file=sys.stderr)

        # --- phase 2: PREFACTOR the rest at their ring homes
        for i in range(1, n_keys):
            r = client.request(routes[i],
                               {"cmd": "solve", "key_i": i,
                                "seed": 200 + i}, timeout_s=120.0)
            assert r is not None and r["status"] == "ok", r
        stats2 = {n: client.request([n], {"cmd": "stats"}, 30.0)
                  for n in names}
        total_factorizations = sum(
            s["cache"]["factorizations"] for s in stats2.values())
        report["fleet_factorizations_per_cold_key"] = \
            total_factorizations / n_keys
        prekill = {n: s["cache"]["factorizations"]
                   for n, s in stats2.items()}

        # --- phase 3: CHAOS LOAD + KILL the hot key's home
        victim = routes[0][0]
        for n in names:
            client.request([n], {"cmd": "chaos",
                                 "spec": "store_latency=0.3:0.01,"
                                         "latency=0.1:0.002",
                                 "seed": 0}, 30.0)
        statuses: list = []
        st_lock = threading.Lock()
        kill_at = max(1, int(requests * kill_after))
        served = [0]
        killed = [False]

        def kill_victim() -> None:
            print(f"# fleet: kill -9 {victim} "
                  f"(pid {procs[victim].pid})", file=sys.stderr)
            client.request([victim], {"cmd": "die", "delay": 0.0},
                           10.0, ignore_down=True)
            time.sleep(0.3)
            if procs[victim].poll() is None:
                # the socket died before the arm landed: double-tap
                import signal as _sig
                os.kill(procs[victim].pid, _sig.SIGKILL)

        n_workers = min(6, requests)
        counts = [requests // n_workers] * n_workers
        for i in range(requests % n_workers):
            counts[i] += 1

        def worker(wid: int, n_req: int) -> None:
            import numpy as _np
            rng = _np.random.default_rng(1000 + wid)
            for j in range(n_req):
                # think time spreads the load so the kill lands
                # MID-load, with requests genuinely in flight at the
                # victim when it dies
                time.sleep(float(rng.exponential(0.03)))
                ki = int(rng.integers(n_keys)) \
                    if rng.random() > 0.5 else 0     # hot key 0
                r = client.request(routes[ki],
                                   {"cmd": "solve", "key_i": ki,
                                    "seed": wid * 10000 + j},
                                   timeout_s=60.0)
                with st_lock:
                    statuses.append(r["status"] if r else "lost")
                    served[0] += 1
                    if served[0] >= kill_at and not killed[0]:
                        killed[0] = True
                        threading.Thread(target=kill_victim,
                                         daemon=True).start()

        workers = [threading.Thread(target=worker, args=(i, c),
                                    daemon=True)
                   for i, c in enumerate(counts)]
        t0 = time.monotonic()
        for w in workers:
            w.start()
        join_deadline = t0 + 300.0
        for w in workers:
            w.join(max(0.0, join_deadline - time.monotonic()))
        hung = sum(1 for w in workers if w.is_alive())
        wall_s = time.monotonic() - t0

        survivors = [n for n in names if n != victim]
        stats3 = {}
        for n in survivors:
            s = client.request([n], {"cmd": "stats"}, 30.0,
                               ignore_down=True)
            if s is not None:
                stats3[n] = s
        by_status: dict = {}
        for s in statuses:
            by_status[s] = by_status.get(s, 0) + 1
        takeover = sum(
            stats3[n]["cache"]["factorizations"] - prekill[n]
            for n in stats3)
        report.update({
            "victim": victim,
            "by_status": by_status,
            "lost": by_status.get("lost", 0),
            # requests that produced NO status at all (a worker died
            # to an uncaught exception mid-loop): without this, a
            # dead worker's unissued requests would vanish from both
            # the lost and hung accounting and the gate would pass
            # with work unaccounted for
            "unaccounted": requests - len(statuses),
            "hung": hung,
            "wall_s": round(wall_s, 3),
            "route_failovers": client.failovers,
            "takeover_factorizations": takeover,
            "survivor_stats": {
                n: {"factorizations": s["cache"]["factorizations"],
                    "store_hits": s["cache"]["store_hits"],
                    "fleet_adopted": s["cache"]["fleet_adopted"],
                    "fleet_steals": s["cache"]["fleet_steals"]}
                for n, s in stats3.items()},
        })

        # --- fleet trace: (replica, rid) must be unique across the
        # merged log, and trace_export must convert it per-replica
        report["flight_trace"] = _check_fleet_trace(flight_log)

        for n in survivors:
            client.request([n], {"cmd": "close"}, 10.0,
                           ignore_down=True)
    finally:
        for n, p in procs.items():
            if p.poll() is None:
                p.kill()
        shutil.rmtree(workdir, ignore_errors=True)

    untyped = sum(v for s, v in report["by_status"].items()
                  if s not in ("ok", "degraded") and s != "lost"
                  and not s[:1].isupper())
    report["platform"] = env.get("JAX_PLATFORMS", "cpu").split(",")[0]
    report["gate"] = {
        "zero_lost": report["lost"] == 0,
        "zero_hung": report["hung"] == 0,
        "all_accounted": report["unaccounted"] == 0,
        "single_flight": report["cold_burst"]["factorizations"] == 1,
        "one_factorization_per_cold_key":
            report["fleet_factorizations_per_cold_key"] == 1.0,
        "warm_takeover": report["takeover_factorizations"] == 0,
        "failover_exercised": report["route_failovers"] > 0,
        "all_typed": untyped == 0,
        "rids_fleet_unique":
            report["flight_trace"].get("rids_unique", False),
    }
    report["gate"]["passed"] = all(report["gate"].values())

    line = json.dumps(report)
    print(line)
    with open(out_path, "a") as f:
        f.write(line + "\n")
    if not report["gate"]["passed"]:
        print(f"# FLEET GATE FAILED: {report['gate']}",
              file=sys.stderr)
        raise SystemExit(1)
    return report


def _check_fleet_trace(flight_log: str) -> dict:
    """Parse the replicas' shared flight JSONL: per-process rids must
    be disambiguated by replica id, and trace_export must group the
    merged log per-replica."""
    recs = []
    try:
        with open(flight_log) as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        recs.append(json.loads(line))
                    except ValueError:
                        pass
    except OSError:
        return {"records": 0, "rids_unique": False}
    pairs = [(r.get("replica"), r.get("rid")) for r in recs]
    replicas = {p[0] for p in pairs if p[0]}
    plain_rids = [p[1] for p in pairs]
    out = {
        "records": len(recs),
        "replicas": len(replicas),
        "plain_rid_collisions":
            len(plain_rids) - len(set(plain_rids)),
        "rids_unique": (len(pairs) == len(set(pairs))
                        and len(replicas) >= 2 and len(recs) > 0),
    }
    try:
        from tools.trace_export import flight_to_chrome
        events = flight_to_chrome(recs)
        pids = {e["pid"] for e in events}
        out["trace_events"] = len(events)
        out["trace_pids_unique_per_request"] = \
            len(pids) == len(set(pairs))
    except Exception as e:
        out["trace_error"] = repr(e)
    return out


# --------------------------------------------------------------------
# day-in-the-life drill (ISSUE 16): the elastic fleet controller
# --------------------------------------------------------------------

class _FactLedger:
    """Cumulative factorization accounting across replica GENERATIONS:
    `last_seen` tracks each live process's counter at its most recent
    stats poll; a process that exits (close, retire, kill) has its
    last-seen count BANKED so restarts — whose counters reset to 0 —
    never make fleet-wide work disappear.  total() is therefore the
    number of factorizations ever run by any process in the drill,
    and total()/n_keys is the one-factorization-per-cold-key gate."""

    def __init__(self) -> None:
        self.last_seen: dict[str, int] = {}
        self.banked = 0

    def update(self, name: str, count: int) -> None:
        self.last_seen[name] = int(count)

    def bank(self, name: str) -> None:
        self.banked += self.last_seen.pop(name, 0)

    def total(self) -> int:
        return self.banked + sum(self.last_seen.values())


def run_day_drill(argv=()) -> dict:
    """A day in the life of the elastic fleet, end to end:

      trickle   — keyed solves fail typed on cold keys (failfast
                  semantics of the keyed path) while seeding the
                  demand ledger
      prefactor — controller tick: popularity-driven Prefactor at
                  each key's ring home; the ONLY factorizations of
                  the whole day (one per key, fleet-wide)
      morning   — ramped tenant-mixed load, ring-routed, all warm
      flash     — flash crowd on the hot key + latency chaos at its
                  home; the SLO burn trips the controller: weighted
                  shed (batch drops, premium never) + scale-up with
                  ring-arc handoff (the new replica adopts from the
                  store)
      rolling   — each original replica drained out of the ring,
                  restarted, re-announced — under live load
      evening   — load falls, the burn reads low again: shed lifts,
                  the elastic replica is retired (drain → demote →
                  release-leases → stop)
      kill      — one original SIGKILL'd mid-load; survivors take
                  over WARM (zero takeover factorizations)

    Gates: zero lost / zero hung / all accounted, every non-ok
    status typed, one factorization per cold key ACROSS THE WHOLE
    DAY, zero takeover factorizations, shed exercised with premium
    untouched, >=1 scale-up and >=1 retire, and bounded p99 through
    every phase.  One line appended to SLU_FLEET_DAY_OUT
    (FLEET_DAY.jsonl), gated by tools/regress.py.
    """
    import shutil
    import tempfile

    repo = _repo()
    sys.path.insert(0, repo)
    k = int(os.environ.get("SLU_FLEET_K", "4"))
    per_phase = int(os.environ.get("SLU_FLEET_DAY_REQUESTS", "32"))
    p99_cap_ms = float(os.environ.get("SLU_FLEET_DAY_P99_MS",
                                      "10000"))
    ttl_s = float(os.environ.get("SLU_FLEET_TTL_S") or 0.0) or 20.0
    out_path = os.environ.get("SLU_FLEET_DAY_OUT",
                              os.path.join(repo, "FLEET_DAY.jsonl"))
    n_keys = 4
    n_orig = 3
    factor_delay_s = 0.5
    workdir = tempfile.mkdtemp(prefix="slu_fleet_day_")
    store_dir = os.path.join(workdir, "store")
    members_dir = os.path.join(workdir, "members")
    flight_log = os.path.join(workdir, "fleet_flight.jsonl")
    os.makedirs(store_dir, exist_ok=True)

    # three or more replica processes cannot share one chip: the
    # drill is a CPU correctness drill, pinned so and stamped so
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["SLU_FLIGHT_JSONL"] = flight_log
    env["SLU_FLEET_TTL_S"] = str(ttl_s)
    # tight p99 target + short window: the flash crowd's injected
    # latency must show up as burn within one controller cadence
    env["SLU_SLO"] = "p99_ms=20,avail=0.999,window_s=10"

    from superlu_dist_tpu import Options
    from superlu_dist_tpu.fleet import (FleetController, FleetPolicy,
                                        FleetSignals,
                                        MembershipDirectory,
                                        PolicyConfig, ReplicaScaler,
                                        arc_moves)
    from superlu_dist_tpu.fleet.pool import _route_key
    from superlu_dist_tpu.fleet.router import HashRing
    from superlu_dist_tpu.serve import matrix_key

    mats = _drill_matrices(k, n_keys)
    opts = Options(factor_dtype="float64")
    keys = [matrix_key(m, opts) for m in mats]
    route_keys = [_route_key(kk) for kk in keys]

    names = [f"r{i}" for i in range(n_orig)]
    all_names = names + [f"r{i}" for i in range(n_orig, n_orig + 4)]
    sockets = {n: os.path.join(workdir, n + ".sock")
               for n in all_names}
    procs: dict = {}
    down: set = set()
    lock = threading.Lock()
    client = _ReplicaClient(sockets, None, down, lock)
    ledger = _FactLedger()
    membership = MembershipDirectory(members_dir)
    state = {"ring": None, "routes": [], "live": set(),
             "arc_moves": 0, "ring_changes": 0}

    def spawn_proc(name: str) -> None:
        for p in (sockets[name], sockets[name] + ".ready"):
            try:
                os.unlink(p)
            except OSError:
                pass
        procs[name] = subprocess.Popen(
            [sys.executable, "-m", "tools.fleet_drill",
             "--replica", "--name", name, "--socket", sockets[name],
             "--store", store_dir, "--k", str(k),
             "--keys", str(n_keys),
             "--factor-delay", str(factor_delay_s),
             "--ttl", str(ttl_s)],
            cwd=repo, env=env)
        deadline = time.monotonic() + 180.0
        while not os.path.exists(sockets[name] + ".ready"):
            if time.monotonic() > deadline:
                raise RuntimeError(f"replica {name} never came up")
            time.sleep(0.1)
        while client.request([name], {"cmd": "ping"}, 10.0,
                             ignore_down=True) is None:
            if time.monotonic() > deadline:
                raise RuntimeError(f"replica {name} never answered")
            time.sleep(0.2)
        with lock:
            down.discard(name)

    def set_ring(members) -> None:
        old = state["ring"]
        state["ring"] = (old.with_replicas(members) if old is not None
                         else HashRing(members))
        state["routes"] = [state["ring"].route(rk)
                           for rk in route_keys]
        if old is not None:
            moved = arc_moves(old, state["ring"], route_keys)
            state["arc_moves"] += len(moved)
            state["ring_changes"] += 1

    def stop_proc(name: str) -> None:
        """Graceful stop: bank the replica's factorization count,
        close it over the wire, reap the process."""
        s = client.request([name], {"cmd": "stats"}, 30.0,
                           ignore_down=True)
        if s is not None:
            ledger.update(name, s["cache"]["factorizations"])
        client.request([name], {"cmd": "close"}, 10.0,
                       ignore_down=True)
        p = procs.get(name)
        if p is not None:
            try:
                p.wait(timeout=30.0)
            except subprocess.TimeoutExpired:
                p.kill()
        ledger.bank(name)

    # -- controller wiring: gather / actuator over the wire ----------

    shed_table = {"fractions": {}}

    # the remote gather (ISSUE 19): FleetSignals built SOLELY from
    # exported snapshots — each replica answers "obs_export" with the
    # same versioned record its SLU_OBS_EXPORT endpoint would serve,
    # and signals_from_snapshots merges them through obs/aggregate.
    # A replica that dies mid-gather yields None: counted in
    # controller.gather_failures on `ctl_metrics`, stamped inf in
    # snapshot_stale_s, never a crash.
    from superlu_dist_tpu.fleet.controller import \
        signals_from_snapshots
    from superlu_dist_tpu.serve.metrics import Metrics
    ctl_metrics = Metrics()

    def gather() -> FleetSignals:
        snaps: dict = {}
        for n in sorted(state["live"]):
            s = client.request([n], {"cmd": "obs_export"}, 30.0)
            snaps[n] = s
            if s is not None:
                c = (s.get("obs") or {}).get("cache") or {}
                if "factorizations" in c:
                    ledger.update(n, int(c["factorizations"]))
        return signals_from_snapshots(
            snaps,
            key_home=lambda ki: state["ring"].home(route_keys[ki]),
            replicas=tuple(sorted(state["live"])),
            metrics=ctl_metrics)

    scaler = ReplicaScaler(
        membership,
        spawn_fn=spawn_proc,
        drain_fn=lambda n: client.request(
            [n], {"cmd": "drain"}, 30.0, ignore_down=True),
        stop_fn=stop_proc)

    class _DayActuator:
        def __init__(self) -> None:
            self.prefactor_results: list = []

        def prefactor(self, act) -> None:
            r = client.request([act.home],
                               {"cmd": "prefactor",
                                "key_i": int(act.key)},
                               timeout_s=120.0)
            self.prefactor_results.append(
                {"key_i": int(act.key), "home": act.home,
                 "ok": bool(r and r.get("ok"))})

        def scale_up(self, act) -> None:
            free = [n for n in all_names if n not in state["live"]
                    and n not in down]
            if not free:
                raise RuntimeError("no replica slots left")
            name = free[0]
            print(f"# day: scale up {name} ({act.reason})",
                  file=sys.stderr)
            scaler.scale_up(name)
            if shed_table["fractions"]:
                # a replica joining mid-shed must enforce the same
                # policy as its peers from its first request
                client.request([name], {"cmd": "shed",
                                        "fractions":
                                        shed_table["fractions"]},
                               30.0)
            state["live"].add(name)
            set_ring(sorted(state["live"]))

        def retire(self, act) -> None:
            print(f"# day: retire {act.replica} ({act.reason})",
                  file=sys.stderr)
            state["live"].discard(act.replica)
            set_ring(sorted(state["live"]))
            scaler.retire(act.replica)

        def shed(self, act) -> None:
            shed_table["fractions"] = dict(act.fractions)
            for n in sorted(state["live"]):
                client.request([n], {"cmd": "shed",
                                     "fractions": act.fractions},
                               30.0)

    actuator = _DayActuator()
    policy = FleetPolicy(PolicyConfig(
        burn_high=2.0, burn_low=0.25, min_replicas=n_orig,
        max_replicas=n_orig + 1, scale_cooldown_s=0.0,
        prefactor_min=2,
        tenant_weights={"premium": 1.0, "batch": 0.0}))
    controller = FleetController(policy, gather, actuator)

    # -- phase runner -------------------------------------------------

    phases: list = []
    all_statuses: list = []
    shed_by_tenant: dict[str, int] = {}
    hung_total = [0]

    def load_phase(name: str, total: int, pick_key, pick_tenant,
                   think_s: float, by_key: bool = False,
                   n_workers: int = 4, on_served=None) -> dict:
        statuses: list = []
        lats: list = []
        st_lock = threading.Lock()
        served = [0]

        def worker(wid: int, n_req: int) -> None:
            import numpy as _np
            rng = _np.random.default_rng(7000 + wid)
            for j in range(n_req):
                time.sleep(float(rng.exponential(think_s)))
                ki = int(pick_key(rng))
                tenant = pick_tenant(rng)
                t0 = time.monotonic()
                r = client.request(
                    state["routes"][ki],
                    {"cmd": "solve", "key_i": ki, "by_key": by_key,
                     "seed": wid * 10000 + j, "tenant": tenant},
                    timeout_s=60.0)
                lat = time.monotonic() - t0
                with st_lock:
                    st = r["status"] if r else "lost"
                    statuses.append(st)
                    lats.append(lat)
                    if st == "TenantThrottled":
                        shed_by_tenant[tenant] = \
                            shed_by_tenant.get(tenant, 0) + 1
                    served[0] += 1
                    n_served = served[0]
                if on_served is not None:
                    on_served(n_served)

        n_workers = min(n_workers, total)
        counts = [total // n_workers] * n_workers
        for i in range(total % n_workers):
            counts[i] += 1
        ws = [threading.Thread(target=worker, args=(i, c),
                               daemon=True)
              for i, c in enumerate(counts)]
        t0 = time.monotonic()
        for w in ws:
            w.start()
        join_deadline = t0 + 300.0
        for w in ws:
            w.join(max(0.0, join_deadline - time.monotonic()))
        hung = sum(1 for w in ws if w.is_alive())
        hung_total[0] += hung
        by_status: dict = {}
        for s in statuses:
            by_status[s] = by_status.get(s, 0) + 1
        lats_ok = sorted(lats)
        p99_ms = (lats_ok[min(len(lats_ok) - 1,
                              int(round(0.99 * (len(lats_ok) - 1))))]
                  * 1e3 if lats_ok else 0.0)
        rec = {"phase": name, "requests": total,
               "by_status": by_status,
               "lost": by_status.get("lost", 0),
               "unaccounted": total - len(statuses), "hung": hung,
               "p99_ms": round(p99_ms, 1),
               "wall_s": round(time.monotonic() - t0, 3)}
        phases.append(rec)
        all_statuses.extend(statuses)
        print(f"# day: phase {name}: {by_status} "
              f"p99={rec['p99_ms']}ms", file=sys.stderr)
        return rec

    report: dict = {"mode": "fleet_day", "replicas": n_orig,
                    "max_replicas": n_orig + 1, "k": k,
                    "keys": n_keys,
                    "requests_per_phase": per_phase,
                    "ts": time.strftime("%Y-%m-%dT%H:%M:%S")}
    try:
        for n in names:
            spawn_proc(n)
            membership.announce(n, state="up")
            state["live"].add(n)
        set_ring(sorted(state["live"]))
        print(f"# day: {n_orig} replicas up", file=sys.stderr)

        # --- TRICKLE: keyed solves — typed misses seed the demand
        # ledger at each key's home; nothing factors yet
        def trickle_key(rng):
            trickle_key.i = (getattr(trickle_key, "i", -1) + 1)
            return trickle_key.i % n_keys

        load_phase("trickle", 3 * n_keys, trickle_key,
                   lambda rng: "premium", think_s=0.01, by_key=True,
                   n_workers=1)
        pre_tick_factorizations = \
            (gather(), ledger.total())[1]   # gather refreshes ledger

        # --- PREFACTOR: controller tick #1 — popularity-driven
        # warming at ring homes, the only factorizations of the day
        controller.tick()
        gather()
        report["prefactor"] = {
            "pre_tick_factorizations": pre_tick_factorizations,
            "actions": list(actuator.prefactor_results),
            "post_tick_factorizations": ledger.total(),
        }
        print(f"# day: prefactor warmed {ledger.total()} keys "
              f"(policy-driven)", file=sys.stderr)

        # --- MORNING: ramped tenant-mixed warm load
        load_phase("morning", per_phase,
                   lambda rng: int(rng.integers(n_keys)),
                   lambda rng: ("premium" if rng.random() < 0.5
                                else "batch"),
                   think_s=0.02)

        # --- FLASH CROWD: hot key 0 + latency chaos at its home;
        # the burn trips the controller into shed + scale-up
        hot_home = state["ring"].home(route_keys[0])
        client.request([hot_home],
                       {"cmd": "chaos", "spec": "latency=1.0:0.05",
                        "seed": 0}, 30.0)
        load_phase("flash", per_phase,
                   lambda rng: (0 if rng.random() < 0.8
                                else int(rng.integers(n_keys))),
                   lambda rng: ("premium" if rng.random() < 0.5
                                else "batch"),
                   think_s=0.01)
        controller.tick()       # sees the burn: Shed + ScaleUp
        report["flash_burn"] = controller.snapshot()["burn"]
        load_phase("flash_shed", per_phase,
                   lambda rng: (0 if rng.random() < 0.8
                                else int(rng.integers(n_keys))),
                   lambda rng: ("premium" if rng.random() < 0.5
                                else "batch"),
                   think_s=0.01)
        client.request([hot_home], {"cmd": "chaos_off"}, 30.0,
                       ignore_down=True)

        # --- ROLLING RESTART: each original replica drained out of
        # the ring, restarted, re-announced — under live load
        for victim in names:
            def bg_key(rng):
                return int(rng.integers(n_keys))

            bg_done = threading.Event()

            def bg_load() -> None:
                load_phase(f"rolling_{victim}", per_phase // 2,
                           bg_key, lambda rng: "premium",
                           think_s=0.05, n_workers=2)
                bg_done.set()

            membership.announce(victim, state="draining")
            state["live"].discard(victim)
            set_ring(sorted(state["live"]))
            bg = threading.Thread(target=bg_load, daemon=True)
            bg.start()
            stop_proc(victim)
            spawn_proc(victim)
            membership.announce(victim, state="up")
            state["live"].add(victim)
            set_ring(sorted(state["live"]))
            bg_done.wait(timeout=300.0)

        # --- EVENING: load falls; the rolling restarts cleared the
        # originals' flash-era SLO windows, so the burn reads low
        # again — the controller lifts the shed and retires the
        # elastic replica
        load_phase("evening", per_phase // 2,
                   lambda rng: int(rng.integers(n_keys)),
                   lambda rng: "premium", think_s=0.2, n_workers=2)

        def refresh_slo_windows() -> None:
            # the burn signal is per-replica and an SLO window trims
            # relative to its LAST observation — a replica whose ring
            # arc holds none of the drill's keys (r3, never restarted)
            # quiesces with its flash-era burn intact forever.  A real
            # deployment's health-check/trickle traffic keeps every
            # window current; model it: one direct full-matrix solve
            # per live replica (store adoption, never a factorization)
            for i, n in enumerate(sorted(state["live"])):
                client.request(
                    [n], {"cmd": "solve", "key_i": i % n_keys,
                          "by_key": False, "seed": 31337 + i,
                          "tenant": "premium"},
                    timeout_s=60.0, ignore_down=True)

        deadline = time.monotonic() + 60.0
        while (gather().burn > policy.config.burn_low
               and time.monotonic() < deadline):
            refresh_slo_windows()
            load_phase("evening_cooldown", 4,
                       lambda rng: int(rng.integers(n_keys)),
                       lambda rng: "premium", think_s=0.3,
                       n_workers=1)
        controller.tick()       # burn low: Shed({}) + Retire
        report["controller"] = controller.snapshot()
        report["members_after_retire"] = \
            sorted(membership.ring_members())

        # --- NIGHT KILL: SIGKILL one original mid-load; survivors
        # take over WARM off the shared store — zero factorizations
        kill_victim = next(n for n in state["routes"][1]
                           if n in names)
        gather()                # last-seen counts BEFORE the kill
        total_before_kill = ledger.total()
        killed = [False]

        def maybe_kill(n_served: int) -> None:
            if n_served >= per_phase // 3 and not killed[0]:
                killed[0] = True
                print(f"# day: kill -9 {kill_victim} "
                      f"(pid {procs[kill_victim].pid})",
                      file=sys.stderr)
                client.request([kill_victim],
                               {"cmd": "die", "delay": 0.0}, 10.0,
                               ignore_down=True)
                time.sleep(0.3)
                if procs[kill_victim].poll() is None:
                    import signal as _sig
                    os.kill(procs[kill_victim].pid, _sig.SIGKILL)

        load_phase("kill", per_phase,
                   lambda rng: int(rng.integers(n_keys)),
                   lambda rng: "premium", think_s=0.02,
                   on_served=maybe_kill)
        state["live"].discard(kill_victim)
        membership.remove(kill_victim)      # reap the dead member
        set_ring(sorted(state["live"]))
        gather()
        report["takeover_factorizations"] = \
            ledger.total() - total_before_kill
        report["kill_victim"] = kill_victim

        for n in sorted(state["live"]):
            stop_proc(n)
            membership.remove(n)
        state["live"].clear()
    finally:
        for n, p in procs.items():
            if p.poll() is None:
                p.kill()
        shutil.rmtree(workdir, ignore_errors=True)

    by_status: dict = {}
    for s in all_statuses:
        by_status[s] = by_status.get(s, 0) + 1
    untyped = sum(v for s, v in by_status.items()
                  if s not in ("ok", "degraded") and s != "lost"
                  and not s[:1].isupper())
    total_requests = sum(p["requests"] for p in phases)
    ratio = ledger.total() / n_keys
    ctl = report.get("controller", {})
    acts = ctl.get("actions", {})
    pre = report.get("prefactor", {})
    report.update({
        "phases": phases,
        "by_status": by_status,
        "shed_by_tenant": dict(shed_by_tenant),
        "requests_total": total_requests,
        "lost": by_status.get("lost", 0),
        "unaccounted": sum(p["unaccounted"] for p in phases),
        "hung": hung_total[0],
        "route_failovers": client.failovers,
        "arc_moves": state["arc_moves"],
        "ring_changes": state["ring_changes"],
        "fleet_factorizations_per_cold_key": ratio,
        "platform": env.get("JAX_PLATFORMS", "cpu").split(",")[0],
        # the day's signals came exclusively from exported remote
        # snapshots (ISSUE 19); fetch failures were contained, not
        # crashed — the kill phase normally produces a few
        "remote_gather": True,
        "gather_failures":
            ctl_metrics.counter("controller.gather_failures"),
    })
    worst_p99 = max((p["p99_ms"] for p in phases), default=0.0)
    report["worst_phase_p99_ms"] = worst_p99
    report["gate"] = {
        "zero_lost": report["lost"] == 0,
        "zero_hung": report["hung"] == 0,
        "all_accounted": report["unaccounted"] == 0,
        "all_typed": untyped == 0,
        "policy_prefactor":
            pre.get("pre_tick_factorizations") == 0
            and len(pre.get("actions", ())) == n_keys
            and all(a["ok"] for a in pre.get("actions", ())),
        "one_factorization_per_cold_key": ratio == 1.0,
        "warm_takeover":
            report.get("takeover_factorizations") == 0,
        "shed_exercised":
            shed_by_tenant.get("batch", 0) > 0
            and shed_by_tenant.get("premium", 0) == 0,
        "scaled": acts.get("scale_up", 0) >= 1
        and acts.get("retire", 0) >= 1,
        "p99_bounded": worst_p99 <= p99_cap_ms,
    }
    report["gate"]["passed"] = all(report["gate"].values())

    line = json.dumps(report)
    print(line)
    with open(out_path, "a") as f:
        f.write(line + "\n")
    if not report["gate"]["passed"]:
        print(f"# FLEET DAY GATE FAILED: {report['gate']}",
              file=sys.stderr)
        raise SystemExit(1)
    return report


def main() -> None:
    argv = sys.argv[1:]
    if "--replica" in argv:
        def opt(flag, default=None):
            return (argv[argv.index(flag) + 1] if flag in argv
                    else default)
        run_replica(name=opt("--name", "r?"),
                    socket_path=opt("--socket"),
                    store_dir=opt("--store"),
                    k=int(opt("--k", "4")),
                    n_keys=int(opt("--keys", "4")),
                    factor_delay_s=float(opt("--factor-delay", "0.5")),
                    ttl_s=float(opt("--ttl", "20")),
                    mesh_ndev=int(opt("--mesh", "0")))
        return
    repo = _repo()
    if "--day" in argv:
        run_day_drill(argv)
    else:
        run_drill(argv)
    if os.environ.get("SLU_REGRESS", "1") != "0":
        sys.path.insert(0, repo)
        from tools import regress
        findings, passed = regress.check_repo(repo)
        print(regress.format_findings(findings), file=sys.stderr)
        if not passed:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
