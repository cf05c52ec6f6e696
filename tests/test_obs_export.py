"""ISSUE 19 contract tests: the exported-telemetry plane.

Pins the fleet control room end to end: the versioned SLU_OBS_EXPORT
endpoint (schema/version stamp, /metrics text form), the off-path
zero-growth guarantee, the JSONL write-through's self-disabling sink
discipline, aggregate.merge's torn/stale/duplicate/missing tolerance,
the controller's remote-gather equivalence
(signals_from_snapshots == signals_from on the same world), the
gather-failure containment counter when a replica dies mid-gather,
per-factorization device-memory watermarks with the documented
prediction slack, the ROADMAP 5a PLAN_LATENCY emission, and the
tooling legs (trace_export snapshot tracks, fleet_top CLI hygiene).
"""

import json
import math
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest
import scipy.sparse as sp

from superlu_dist_tpu import Options, obs
from superlu_dist_tpu.fleet.controller import (signals_from,
                                               signals_from_snapshots)
from superlu_dist_tpu.models.gssvx import factorize
from superlu_dist_tpu.obs import aggregate, export
from superlu_dist_tpu.obs import memory as obs_memory
from superlu_dist_tpu.serve.metrics import Metrics
from superlu_dist_tpu.sparse import csr_from_scipy

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))
import fleet_top  # noqa: E402
import trace_export  # noqa: E402


@pytest.fixture(autouse=True)
def _export_off_after():
    """The exporter is process-global; never leak a listener or a
    JSONL ticker across tests."""
    yield
    export.configure(enabled=False)


def _testmat(m=10):
    t = sp.diags([-1.0, 2.4, -1.1], [-1, 0, 1], shape=(m, m))
    return csr_from_scipy(sp.kronsum(t, t, format="csr").tocsr())


def _mk_snap(replica, seq=1, ts=None, *, hits=0, misses=0,
             factorizations=0, burn=None, popularity=(),
             version=export.EXPORT_VERSION):
    """A synthetic, minimal-but-valid export snapshot."""
    obs_payload = {
        "cache": {"hits": hits, "misses": misses,
                  "factorizations": factorizations,
                  "hit_rate": (hits / (hits + misses)
                               if hits + misses else 0.0),
                  "breaker_by_state": {"closed": 1}},
        "health": {"factorizations": factorizations, "solves": 0},
    }
    if burn is not None:
        obs_payload["slo"] = {"keys": {
            k: {"burn_rate_availability": v,
                "burn_rate_latency": 0.0} for k, v in burn.items()}}
    if popularity:
        obs_payload["fleet"] = {"popularity": list(popularity)}
    return {"schema": export.EXPORT_SCHEMA, "version": version,
            "replica": replica, "pid": 1234, "seq": seq,
            "ts": time.time() if ts is None else ts,
            "obs": obs_payload}


# --------------------------------------------------------------------
# the endpoint: schema pin + both wire forms
# --------------------------------------------------------------------

def test_export_endpoint_schema_and_version(tmp_path):
    """/snapshot serves the versioned, schema-stamped JSON record and
    /metrics the Prometheus-style text under the same stamp — the
    cross-version contract every consumer (aggregate, fleet_top,
    trace_export) parses."""
    sock = str(tmp_path / "obs.sock")
    exp = export.configure(enabled=True, listen=f"unix:{sock}")
    assert exp is not None and export.export_enabled()

    snap = export.fetch(exp.address, "/snapshot")
    assert snap["schema"] == export.EXPORT_SCHEMA
    assert snap["version"] == export.EXPORT_VERSION
    assert isinstance(snap["replica"], str) and snap["replica"]
    assert snap["pid"] == os.getpid()
    assert isinstance(snap["seq"], int) and snap["seq"] >= 1
    assert isinstance(snap["obs"], dict)
    # the registry's standing providers ride every snapshot
    for provider in ("compile", "health", "memory", "export"):
        assert provider in snap["obs"], provider
    assert aggregate.is_export_snapshot(snap)

    text = export.fetch(exp.address, "/metrics")
    assert text.startswith(f"# slu.obs schema={export.EXPORT_SCHEMA} "
                           f"version={export.EXPORT_VERSION} ")
    assert any(ln.startswith("slu_") for ln in text.splitlines())

    # an unknown path is a clean 404 (typed at the client)
    with pytest.raises(ValueError):
        export.fetch(exp.address, "/nope")
    # sequence numbers are monotonic across fetches: consumers order
    # duplicate/torn lines by (replica, seq) without trusting clocks
    snap2 = export.fetch(exp.address, "/snapshot")
    assert snap2["seq"] > snap["seq"]
    # the exporter reports on itself
    assert snap2["obs"]["export"]["requests"] >= 1


def test_export_off_is_one_pointer_check():
    """The off-path zero-growth pin: flag unset means no exporter
    object, no 'export' provider in the registry, and no listener or
    ticker threads anywhere."""
    export.configure(enabled=False)
    assert not export.export_enabled()
    assert export.get_exporter() is None
    assert "export" not in obs.snapshot()
    # export_snapshot() itself stays available regardless of the
    # HTTP flag
    assert aggregate.is_export_snapshot(export.export_snapshot())


def test_jsonl_sink_self_disables_on_io_error(tmp_path):
    """Tracer sink discipline: the first I/O error turns the JSONL
    write-through off for the exporter's lifetime and records why —
    export never throws into serving."""
    bad = str(tmp_path / "no" / "such" / "dir" / "obs.jsonl")
    exp = export.configure(enabled=True, jsonl_path=bad,
                           period_s=60.0)
    exp.flush_jsonl()               # must not raise
    s = exp.snapshot()
    assert s["jsonl_error"] is not None
    assert s["jsonl_path"] is None and s["writes"] == 0
    exp.flush_jsonl()               # disabled: still silent

    # the good path appends one parseable snapshot line per flush
    good = str(tmp_path / "obs.jsonl")
    exp = export.configure(enabled=True, jsonl_path=good,
                           period_s=60.0)
    exp.flush_jsonl()
    exp.flush_jsonl()
    lines = [json.loads(ln) for ln in
             open(good).read().splitlines()]
    assert len(lines) == 2
    assert all(aggregate.is_export_snapshot(ln) for ln in lines)
    assert exp.snapshot()["writes"] == 2


# --------------------------------------------------------------------
# aggregation: one fleet view out of torn/stale/duplicate inputs
# --------------------------------------------------------------------

def test_aggregate_merge_torn_stale_duplicate_missing():
    now = time.time()
    snaps = [
        None,                                     # failed fetch
        {"schema": "bogus", "obs": {}},           # torn
        _mk_snap("rA", seq=1, hits=1, misses=1),  # duplicate, older
        _mk_snap("rA", seq=3, hits=10, misses=10, factorizations=2,
                 burn={"k0": 2.5, "unrouted": 99.0},
                 popularity=[{"key_i": 0, "count": 4,
                              "resident": True}]),
        _mk_snap("rB", seq=1, ts=now - 120.0, hits=30, misses=10,
                 factorizations=1,
                 popularity=[{"key_i": 0, "count": 2,
                              "resident": False},
                             {"key_i": 1, "count": 1,
                              "resident": False}]),
    ]
    fleet = aggregate.merge(snaps, now=now, stale_s=30.0)
    assert fleet["schema"] == aggregate.FLEET_SCHEMA
    assert fleet["version"] == aggregate.FLEET_VERSION
    assert fleet["n_replicas"] == 2
    assert fleet["dropped"] == 2
    assert fleet["dropped_reasons"] == {"missing": 1, "torn": 1,
                                        "duplicate": 1}
    # newest (seq, ts) won the duplicate
    assert fleet["replicas"]["rA"]["seq"] == 3
    assert fleet["replicas"]["rA"]["factorizations"] == 2
    # staleness is stamped, never a drop: rB's data still merged
    assert fleet["stale_replicas"] == ["rB"]
    assert fleet["replicas"]["rB"]["stale"] is True
    assert fleet["max_stale_s"] >= 120.0
    # counters sum fleet-wide; hit_rate is recomputed from the sums
    assert fleet["cache"]["hits"] == 40 and fleet["cache"]["misses"] == 20
    assert fleet["cache"]["hit_rate"] == pytest.approx(40 / 60)
    assert fleet["breaker_by_state"] == {"closed": 2}
    assert fleet["health"]["factorizations"] == 3
    # burn: per-key max across replicas; unrouted never drives burn_max
    assert fleet["burn"]["k0"] == 2.5
    assert fleet["burn_max"] == 2.5
    # demand merges per key_i: counts sum, residency ORs, sorted desc
    assert fleet["popularity"][0] == {"key_i": 0, "count": 6,
                                     "resident": True}
    assert fleet["popularity"][1]["count"] == 1


def test_aggregate_rejects_future_version():
    """A snapshot from a NEWER schema version is torn, not
    misparsed — the version stamp is the compatibility gate."""
    snap = _mk_snap("rZ", version=export.EXPORT_VERSION + 1)
    fleet = aggregate.merge([snap], now=time.time())
    assert fleet["n_replicas"] == 0
    assert fleet["dropped_reasons"] == {"torn": 1}


# --------------------------------------------------------------------
# the controller's remote gather
# --------------------------------------------------------------------

def test_signals_from_snapshots_equivalence():
    """FleetSignals built SOLELY from exported snapshots must agree
    with the in-process gatherer's shape: burn (unrouted excluded),
    breaker states, demand entries carrying key/home."""
    snaps = {
        "r0": _mk_snap("r0", burn={"k0": 1.5, "unrouted": 50.0},
                       popularity=[{"key_i": 2, "count": 7,
                                    "resident": False}]),
        "r1": _mk_snap("r1", burn={"k0": 0.5, "k1": 3.0}),
    }
    sig = signals_from_snapshots(
        snaps, key_home=lambda ki: f"home{ki}",
        replicas=("r0", "r1"))
    assert sig.burn == 3.0                    # max over keys, not 50
    assert sig.replicas == ("r0", "r1")
    assert sig.breaker_by_state == {"closed": 2}
    ent = sig.popularity[0]
    # FleetPolicy.decide reads ent["key"]/"home" — same shape as
    # signals_from builds from an in-process cache ledger
    assert ent["key"] == 2 and ent["home"] == "home2"
    assert sig.snapshot_stale_s["r0"] < 5.0


def test_signals_from_snapshots_matches_in_process_service():
    """The equivalence drill in miniature: one real SolveService,
    gathered once in-process (signals_from) and once through its own
    export snapshot (signals_from_snapshots) — identical breaker
    view, burn, and demand ledger.  The snapshot's demand leg rides a
    "fleet" provider mapping CacheKeys to key indices, exactly the
    drill replica's ledger shape."""
    from superlu_dist_tpu.obs.registry import REGISTRY
    from superlu_dist_tpu.serve import (FactorCache, ServeConfig,
                                        SolveService)
    a = _testmat(8)
    svc = SolveService(ServeConfig(backend="host"),
                       cache=FactorCache(backend="host"))
    key_index = [e["key"] for e in svc.cache.popularity()]

    class _Ledger:
        @staticmethod
        def snapshot():
            ents = svc.cache.popularity()
            for e in ents:
                if e["key"] not in key_index:
                    key_index.append(e["key"])
            return {"popularity": [
                {"key_i": key_index.index(e["key"]),
                 "count": e["count"], "resident": e["resident"]}
                for e in ents]}

    REGISTRY.register("fleet", _Ledger)
    try:
        svc.solve(a, np.ones(a.n))
        svc.solve(a, np.ones(a.n) * 2.0)
        local = signals_from(svc, replicas=("me",))
        remote = signals_from_snapshots(
            {"me": export.export_snapshot()}, replicas=("me",))
        assert remote.breaker_by_state == local.breaker_by_state
        assert remote.burn == local.burn
        assert ([key_index[e["key"]] for e in remote.popularity]
                == [e["key"] for e in local.popularity])
        assert ([(e["count"], e["resident"])
                 for e in remote.popularity]
                == [(e["count"], e["resident"])
                    for e in local.popularity])
    finally:
        REGISTRY.unregister("fleet", _Ledger)
        svc.close()


def test_gather_failure_lands_in_containment_counters(tmp_path):
    """Kill a replica mid-gather: round 1 fetches its live export
    endpoint; SIGKILL; round 2's fetch failure must land in the
    gather-containment counter and stamp snapshot_stale_s=inf —
    never a crash."""
    sock = str(tmp_path / "r0.sock")
    code = (
        "import sys, time\n"
        f"sys.path.insert(0, {_REPO!r})\n"
        "from superlu_dist_tpu.obs import export\n"
        f"export.configure(enabled=True, listen='unix:{sock}')\n"
        "print('READY', flush=True)\n"
        "time.sleep(120)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                            stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "READY"
        deadline = time.monotonic() + 30.0
        while not os.path.exists(sock):
            assert time.monotonic() < deadline
            time.sleep(0.05)

        metrics = Metrics()

        def gather_round():
            try:
                snap = export.fetch(f"unix:{sock}", timeout_s=5.0)
            except (OSError, ValueError):
                snap = None
            return signals_from_snapshots({"r0": snap},
                                          replicas=("r0",),
                                          metrics=metrics)

        sig = gather_round()
        assert sig.snapshot_stale_s["r0"] < 10.0
        assert metrics.counter("controller.gather_failures") == 0

        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)

        sig = gather_round()                  # contained, no raise
        assert sig.snapshot_stale_s["r0"] == math.inf
        assert metrics.counter("controller.gather_failures") == 1
        assert sig.burn == 0.0 and sig.popularity == ()
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=30)


# --------------------------------------------------------------------
# device-memory accounting
# --------------------------------------------------------------------

def test_memory_watermarks_on_every_factorization():
    """Every committed factorization record carries the watermark
    pair — on Stats, on the health monitor's per-factorization ring,
    and on the MEMWATCH provider."""
    a = _testmat(9)
    before = obs.MEMWATCH.snapshot()["factorizations"]
    lu = factorize(a, Options(), backend="host")
    mem = lu.stats.mem_watermarks
    for k in ("plan_bytes_predicted", "peak_bytes_measured",
              "source"):
        assert k in mem, k
    assert mem["plan_bytes_predicted"] > 0
    assert lu.stats.snapshot()["mem_watermarks"] == mem

    ring = obs.HEALTH.snapshot()["last_factor"]
    assert ring["mem"]["plan_bytes_predicted"] \
        == mem["plan_bytes_predicted"]

    mw = obs.MEMWATCH.snapshot()
    assert mw["factorizations"] == before + 1
    assert mw["last"]["plan_bytes_predicted"] \
        == mem["plan_bytes_predicted"]
    assert "FACT" in mw["by_phase"]


def test_memory_prediction_within_documented_slack():
    """plan_bytes_predicted vs peak_bytes_measured: on CPU the probe
    usually reports nothing, so the record must SAY it's the analytic
    model; when a measurement does exist the pair stays within the
    documented PREDICTION_SLACK."""
    a = _testmat(9)
    obs_memory.configure(probe=True)
    try:
        lu = factorize(a, Options(), backend="jax")
        mem = lu.stats.mem_watermarks
        assert mem["source"] in ("analytic", "measured")
        pred = mem["plan_bytes_predicted"]
        meas = mem["peak_bytes_measured"]
        assert pred > 0 and meas > 0
        if mem["source"] == "analytic":
            # no device measurement: the measured figure IS the model
            assert meas == pred and mem["live_bytes_measured"] is None
        else:
            # the model may under-count XLA temporaries but must not
            # over-predict the measured peak past the documented slack
            assert pred <= meas * obs_memory.PREDICTION_SLACK
    finally:
        obs_memory.configure(probe=None)


def test_schedule_bytes_predicted_matches_handle_model():
    """The prediction from the bare schedule and the handle-side
    model must agree."""
    from superlu_dist_tpu.ops.batched import build_schedule
    from superlu_dist_tpu.plan import plan_factorization
    a = _testmat(8)
    opts = Options(factor_dtype="float64")
    plan = plan_factorization(a, opts)
    sched = build_schedule(plan, ndev=1)
    pred = obs_memory.schedule_bytes_predicted(sched, "float64")
    lu = factorize(a, opts, backend="jax")
    assert lu.stats.mem_watermarks["plan_bytes_predicted"] == pred


# --------------------------------------------------------------------
# PLAN_LATENCY emission (ROADMAP 5a)
# --------------------------------------------------------------------

def test_plan_latency_record_emitted(tmp_path, monkeypatch):
    from superlu_dist_tpu.plan import plan as plan_mod
    from superlu_dist_tpu.plan.plan import (pattern_sha1,
                                            plan_factorization)
    out = str(tmp_path / "pl.jsonl")
    monkeypatch.setenv("SLU_PLAN_LATENCY_OUT", out)
    a = _testmat(8)
    plan_factorization(a, Options())
    recs = [json.loads(ln) for ln in open(out).read().splitlines()]
    assert len(recs) == 1
    rec = recs[0]
    assert rec["mode"] == "plan_latency" and rec["source"] == "plan"
    assert rec["n"] == a.n and rec["nnz"] == a.nnz
    assert rec["pattern_sha1"] == pattern_sha1(a)
    assert rec["t_plan_s"] > 0

    # sink discipline: an unwritable path disables emission for the
    # process (planning never throws for observability's sake)
    monkeypatch.setenv("SLU_PLAN_LATENCY_OUT",
                       str(tmp_path / "no" / "dir" / "pl.jsonl"))
    plan_factorization(a, Options())          # must not raise
    assert plan_mod._pl_error is not None
    plan_mod._pl_error = None                 # un-latch for the suite


# --------------------------------------------------------------------
# tooling: trace_export snapshot tracks, fleet_top CLI hygiene
# --------------------------------------------------------------------

def test_trace_export_converts_snapshot_jsonl(tmp_path):
    """An export JSONL (snapshot lines) converts to per-replica
    Perfetto counter tracks via the same CLI that converts flight
    logs."""
    jl = str(tmp_path / "export.jsonl")
    with open(jl, "w") as f:
        for snap in (_mk_snap("rA", seq=1, hits=3, misses=1),
                     _mk_snap("rA", seq=2, hits=5, misses=1),
                     _mk_snap("rB", seq=1, hits=0, misses=2)):
            f.write(json.dumps(snap) + "\n")
    out = str(tmp_path / "out.trace.json")
    assert trace_export.main([jl, "-o", out]) == 0
    evs = trace_export.load(out)
    counters = [e for e in evs if e.get("ph") == "C"]
    assert counters, "no counter events emitted"
    assert {e["name"] for e in counters} >= {"cache.hits",
                                            "cache.misses"}
    # one pid block per replica, named for it
    meta = [e for e in evs if e.get("ph") == "M"]
    assert len({e["pid"] for e in meta}) == 2


def test_trace_export_malformed_snapshot_line_is_clean_error(
        tmp_path, capsys):
    jl = str(tmp_path / "bad.jsonl")
    with open(jl, "w") as f:
        f.write(json.dumps(_mk_snap("rA")) + "\n")
        f.write("{not json\n")
    assert trace_export.main([jl, "-o",
                              str(tmp_path / "o.json")]) == 1
    err = capsys.readouterr().err
    assert "bad.jsonl" in err and "2" in err


def test_fleet_top_renders_and_rejects_corrupt_input(tmp_path,
                                                     capsys):
    jl = str(tmp_path / "fleet.jsonl")
    with open(jl, "w") as f:
        f.write(json.dumps(_mk_snap("rA", hits=4, misses=1,
                                    factorizations=2)) + "\n")
        f.write(json.dumps(_mk_snap("rB", hits=1, misses=1)) + "\n")
    assert fleet_top.main([jl]) == 0
    out = capsys.readouterr().out
    assert "rA" in out and "rB" in out

    assert fleet_top.main([jl, "--json"]) == 0
    fleet = json.loads(capsys.readouterr().out)
    assert fleet["schema"] == aggregate.FLEET_SCHEMA
    assert fleet["n_replicas"] == 2

    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write("torn{\n")
    assert fleet_top.main([bad]) == 1
    assert "malformed" in capsys.readouterr().err
    assert fleet_top.main([]) == 2            # usage
