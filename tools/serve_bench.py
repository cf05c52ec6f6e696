"""Serve-mode load benchmark: micro-batched vs sequential solves —
plus the chaos gate (`--chaos [SPEC]`), which runs the standard load
under fault injection (resilience/chaos.py) and gates on zero hangs
and zero silent wrong answers, appending a record to CHAOS.jsonl
(SLU_CHAOS_OUT), and the flight-recorder overhead A/B
(`--flight-ab`), which measures SLU_FLIGHT=1 against flight-off on
the same box at the same moment (interleaved trials, median ratio)
and appends a `flight_ab` record gating the <=5% overhead contract.
`--export-ab` is the same interleaved discipline for the telemetry
export plane (ISSUE 19): full SLU_OBS_EXPORT deployment (unix-socket
listener + a 20 Hz scraper + the JSONL write-through) vs export-off,
appending an `export_ab` record under the same <=5% budget
(SLU_EXPORT_MAX_OVERHEAD).

The standard run drives the load with the flight recorder ON (unless
SLU_FLIGHT=0) and the SLO engine declared (SLU_SLO or a default
declaration), so the committed record carries EXEMPLARS — the request
IDs of the p99/worst requests and of every non-ok status — plus the
per-(n-bucket, dtype-tier) SLO verdicts.  After appending its record
it runs the perf-regression sentinel (tools/regress.py) against the
committed BASELINES.json and fails the process on regression
(SLU_REGRESS=0 skips).

Factors one hot matrix (3D Laplacian, k=SLU_SERVE_K), then measures:

  1. the sequential baseline — the same request stream served
     one-at-a-time through the FACTORED rung (nrhs=1 per dispatch,
     no batching), i.e. what a naive per-request server would do;
  2. the serve path — SLU_SERVE_CONCURRENCY closed-loop workers
     against SolveService, whose micro-batcher coalesces concurrent
     RHS into bucket-padded blocks.

Emits one JSON line (appended to SLU_SERVE_OUT, default
SERVE_LATENCY.jsonl) with p50/p95/p99 latency, solves/s for both
arms, the speedup, batch-occupancy distribution, cache hit rate and
the jit-recompile pin (solve-program cache size before vs after the
load; equal = zero recompiles after warmup).  Also reachable as
`python bench.py --serve`.  CPU rehearsal: JAX_PLATFORMS=cpu.
"""

import json
import os
import sys
import time

import numpy as np


def _jax_env():
    """Shared platform/cache setup; returns (repo_root, jax device)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    from superlu_dist_tpu.utils.cache import (ensure_portable_cpu_isa,
                                              place_compile_cache)
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        os.environ["XLA_FLAGS"] = ensure_portable_cpu_isa(
            os.environ.get("XLA_FLAGS", ""))
    import jax
    dev = jax.devices()[0]
    place_compile_cache()
    return repo, dev


def _observability_on():
    """Flight recorder + SLO declaration for bench loads: on by
    default so committed records carry exemplars and SLO verdicts;
    SLU_FLIGHT=0 / SLU_SLO=0 opt out explicitly."""
    from superlu_dist_tpu.obs import flight, slo
    if os.environ.get("SLU_FLIGHT") != "0":
        flight.configure(enabled=True)
    if os.environ.get("SLU_SLO", "") != "0":
        slo.configure(os.environ.get("SLU_SLO")
                      or "p99_ms=100,avail=0.99,window_s=300")
    return flight, slo


def run(argv=()):
    repo, dev = _jax_env()

    from superlu_dist_tpu import Options, obs, solve
    from superlu_dist_tpu.serve import (ServeConfig, SolveService,
                                        run_load, solve_jit_cache_size)
    from superlu_dist_tpu.utils.testmat import laplacian_3d

    flight, slo = _observability_on()
    k = int(os.environ.get("SLU_SERVE_K", "8"))
    concurrency = int(os.environ.get("SLU_SERVE_CONCURRENCY", "16"))
    requests = int(os.environ.get("SLU_SERVE_REQUESTS", "192"))
    linger_s = float(os.environ.get("SLU_SERVE_LINGER_MS", "2")) / 1e3
    out_path = os.environ.get(
        "SLU_SERVE_OUT", os.path.join(repo, "SERVE_LATENCY.jsonl"))

    a = laplacian_3d(k)
    opts = Options(factor_dtype="float64")
    svc = SolveService(ServeConfig(max_queue_depth=max(64, 4 * requests),
                                   max_linger_s=linger_s))
    print(f"# factoring n={a.n} (k={k}) ...", file=sys.stderr)
    t0 = time.perf_counter()
    key = svc.prefactor(a, opts)     # factor + warm every bucket
    t_warm = time.perf_counter() - t0
    lu = svc.cache.peek(key)

    # sequential baseline: same per-request work, one rhs per dispatch
    rng = np.random.default_rng(0)
    seq_n = min(requests, 64)
    t0 = time.perf_counter()
    for _ in range(seq_n):
        x = solve(lu, rng.standard_normal(a.n))
    seq_wall = time.perf_counter() - t0
    seq_rate = seq_n / seq_wall
    assert np.all(np.isfinite(x))

    # recompile pin: the unified obs compile counter (every watched
    # jit's cache misses, shape-attributed) — replaces the old
    # ad-hoc solve-program cache-size probe; the probe stays in the
    # record as a cross-check of the same contract
    misses_before = obs.COMPILE_WATCH.misses()
    jit_before = solve_jit_cache_size(lu)
    report = run_load(svc, [key], requests=requests,
                      concurrency=concurrency, hot_fraction=1.0,
                      seed=0)
    jit_after = solve_jit_cache_size(lu)
    misses_after = obs.COMPILE_WATCH.misses()

    # --- mixed-dtype-traffic scenario (SLU_SERVE_MIXED=1): the SAME
    # matrix resident at TWO precision rungs — fp32 factors solving
    # through the doubleword-residual policy and fp64 factors solving
    # natively — with traffic alternating between them.  The pin: the
    # PR 3 obs compile counter must stay FLAT across the mixed run
    # (each rung's batcher variants were warmed by prefactor; rung
    # switching must route, never recompile).  This is the serve-layer
    # contract behind dtype tiers: precision is a CACHE KEY, not a
    # compile trigger. ---
    mixed = None
    if os.environ.get("SLU_SERVE_MIXED") == "1":
        from superlu_dist_tpu import PrecisionPolicy, ResidualMode
        print("# mixed-dtype scenario: prefactor fp32+df64 rung ...",
              file=sys.stderr)
        opts32 = PrecisionPolicy(
            factor_dtype="float32",
            residual=ResidualMode.DOUBLEWORD).apply()
        key32 = svc.prefactor(a, opts32)
        mixed_n = max(32, requests // 2)
        misses_b = obs.COMPILE_WATCH.misses()
        mixed_report = run_load(svc, [key, key32],
                                requests=mixed_n,
                                concurrency=concurrency,
                                hot_fraction=0.5, seed=1)
        mixed = {
            "requests": mixed_n,
            "by_status": mixed_report["by_status"],
            "solves_per_s": mixed_report["solves_per_s"],
            "recompiles_across_rungs":
                obs.COMPILE_WATCH.misses() - misses_b,
            "rungs": ["float64", "float32+df64"],
        }

    # --- batch-coalescer scenario (SLU_BATCH_COALESCE=1): the solve
    # mix gains a batch_fraction lane of COLD same-pattern factor
    # requests (perturbed values -> fresh keys), which the factor
    # coalescer (serve/coalescer.py) merges into batched dispatches
    # up the B-ladder.  A slice of those requests carries all-zero
    # values under a replace_tiny_pivot=NO option set, pinning the
    # masked-member contract under concurrent load: those requests
    # read batch_member_refused (typed, per-index) while their
    # siblings read batch_ok. ---
    batch = None
    if os.environ.get("SLU_BATCH_COALESCE") == "1":
        from superlu_dist_tpu.options import YesNo
        print("# batch-coalescer scenario: cold-key bursts ...",
              file=sys.stderr)
        bopts = Options(factor_dtype="float64",
                        replace_tiny_pivot=YesNo.NO)
        bn = max(32, requests // 2)
        mm = svc.metrics
        ctr0 = {c: mm.counter(c) for c in
                ("serve.batch_coalesce_submits", "serve.batch_flushes",
                 "serve.batch_fanned_back", "serve.batch_member_refused")}
        breport = run_load(svc, [a], requests=bn,
                           concurrency=concurrency, hot_fraction=1.0,
                           seed=2, batch_fraction=0.5,
                           batch_singular_fraction=0.1,
                           batch_options=bopts)
        batch = {
            "requests": bn,
            "by_status": breport["by_status"],
            "coalesce_submits":
                mm.counter("serve.batch_coalesce_submits")
                - ctr0["serve.batch_coalesce_submits"],
            "flushes": mm.counter("serve.batch_flushes")
            - ctr0["serve.batch_flushes"],
            "fanned_back": mm.counter("serve.batch_fanned_back")
            - ctr0["serve.batch_fanned_back"],
            "member_refused":
                mm.counter("serve.batch_member_refused")
                - ctr0["serve.batch_member_refused"],
        }

    obs_dump = svc.dump_metrics_text()
    svc.close()

    m = report["metrics"]
    rec = {
        "mode": "serve",
        "n": a.n,
        "k": k,
        "factor_dtype": opts.factor_dtype,
        "concurrency": concurrency,
        "requests": requests,
        "linger_ms": linger_s * 1e3,
        "by_status": report["by_status"],
        "p50_ms": report.get("p50_ms"),
        "p95_ms": report.get("p95_ms"),
        "p99_ms": report.get("p99_ms"),
        "solves_per_s": report["solves_per_s"],
        "seq_solves_per_s": seq_rate,
        "speedup_vs_sequential": report["solves_per_s"] / seq_rate,
        "batch_occupancy": m["histograms"].get("serve.batch_occupancy",
                                               {}),
        "queue_wait": m["histograms"].get("serve.queue_wait_s", {}),
        "device_solve": m["histograms"].get("serve.device_solve_s", {}),
        "cache": svc.cache.stats(),
        "jit_cache_before": jit_before,
        "jit_cache_after": jit_after,
        "mixed_dtype": mixed,
        "batch_coalesce": batch,
        "recompiles_under_load": misses_after - misses_before,
        "jit_cache_growth": (jit_after - jit_before
                             if jit_before >= 0 else None),
        "compile_misses_total": misses_after,
        "warmup_s": t_warm,
        # exemplars: the p99/worst rids + every non-ok status's rids —
        # one lookup from their flight records (SLU_FLIGHT_JSONL /
        # obs.snapshot()['flight'])
        "exemplars": report.get("exemplars"),
        "flight": {k2: v for k2, v in flight.snapshot().items()
                   if k2 != "records"},
        "slo": slo.snapshot(),
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    line = json.dumps(rec)
    print(line)
    # the unified registry's text exposition (serve metrics + compile
    # + health), for eyeballs; the JSON line is the machine record
    print("# --- obs registry dump ---", file=sys.stderr)
    print(obs_dump, file=sys.stderr, end="")
    with open(out_path, "a") as f:
        f.write(line + "\n")
    return rec


def run_flight_ab(argv=()):
    """Flight-recorder overhead A/B: the same load with the recorder
    OFF vs ON, interleaved on the same service at the same moment so
    box noise hits both arms alike; the MEDIAN per-arm throughput
    ratio is the measurement.  Appends a `flight_ab` record to
    SLU_SERVE_OUT and fails (exit 1) when the on-arm loses more than
    SLU_FLIGHT_MAX_OVERHEAD (default 0.05 — the ISSUE-8 acceptance:
    within 5%, and strictly one flag check on the path when off)."""
    repo, dev = _jax_env()

    from superlu_dist_tpu import Options
    from superlu_dist_tpu.obs import flight
    from superlu_dist_tpu.serve import (ServeConfig, SolveService,
                                        run_load)
    from superlu_dist_tpu.utils.testmat import laplacian_3d

    k = int(os.environ.get("SLU_SERVE_K", "8"))
    concurrency = int(os.environ.get("SLU_SERVE_CONCURRENCY", "16"))
    requests = int(os.environ.get("SLU_SERVE_REQUESTS", "192"))
    trials = int(os.environ.get("SLU_FLIGHT_AB_TRIALS", "5"))
    budget = float(os.environ.get("SLU_FLIGHT_MAX_OVERHEAD", "0.05"))
    out_path = os.environ.get(
        "SLU_SERVE_OUT", os.path.join(repo, "SERVE_LATENCY.jsonl"))

    a = laplacian_3d(k)
    svc = SolveService(ServeConfig(
        max_queue_depth=max(64, 4 * requests)))
    print(f"# flight A/B: factoring n={a.n} (k={k}) ...",
          file=sys.stderr)
    key = svc.prefactor(a, Options(factor_dtype="float64"))

    # interleaved pairs with ALTERNATING arm order (the box warms
    # monotonically through the run; a fixed order would bias one
    # arm); the measurement is the median of per-pair on/off ratios,
    # so slow drift cancels within each pair
    rates: dict = {"off": [], "on": []}
    ratios = []
    for t in range(trials):
        order = ("off", "on") if t % 2 == 0 else ("on", "off")
        pair = {}
        for arm in order:
            flight.configure(enabled=(arm == "on"))
            rep = run_load(svc, [key], requests=requests,
                           concurrency=concurrency,
                           hot_fraction=1.0, seed=t)
            pair[arm] = rep["solves_per_s"]
            rates[arm].append(rep["solves_per_s"])
            print(f"# trial {t} {arm}: "
                  f"{rep['solves_per_s']:.1f} solves/s",
                  file=sys.stderr)
        if pair["off"] > 0 and pair["on"] > 0:
            ratios.append(pair["on"] / pair["off"])
        else:
            # an arm that completed zero solves (total deadline
            # blowout on an overloaded box) is a failed trial, not a
            # division — it is excluded from the median and reported
            print(f"# trial {t}: zero-throughput arm, pair discarded",
                  file=sys.stderr)
    flight.configure(enabled=False)
    svc.close()

    med_off = sorted(rates["off"])[trials // 2]
    med_on = sorted(rates["on"])[trials // 2]
    if ratios:
        med_ratio = sorted(ratios)[len(ratios) // 2]
        overhead = max(0.0, 1.0 - med_ratio)
    else:
        overhead = 1.0          # no valid pair: fail loudly below
    rec = {
        "mode": "flight_ab",
        "n": a.n, "k": k,
        "concurrency": concurrency,
        "requests": requests,
        "trials": trials,
        "solves_per_s_off": rates["off"],
        "solves_per_s_on": rates["on"],
        "median_off": med_off,
        "median_on": med_on,
        "pair_ratios": [round(r, 4) for r in ratios],
        "overhead_frac": round(overhead, 4),
        "budget_frac": budget,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    line = json.dumps(rec)
    print(line)
    with open(out_path, "a") as f:
        f.write(line + "\n")
    if overhead > budget:
        print(f"# FLIGHT OVERHEAD REGRESSION: {overhead:.1%} > "
              f"{budget:.1%} (off {med_off:.1f}, on {med_on:.1f})",
              file=sys.stderr)
        raise SystemExit(1)
    return rec


def run_export_ab(argv=()):
    """Telemetry-export overhead A/B (ISSUE 19): the same load with
    the export plane OFF vs ON — listener serving a live scraper +
    the periodic JSONL write-through, i.e. the full SLU_OBS_EXPORT
    deployment — interleaved exactly like --flight-ab.  Appends an
    `export_ab` record to SLU_SERVE_OUT and fails (exit 1) when the
    on-arm loses more than SLU_EXPORT_MAX_OVERHEAD (default 0.05)."""
    import tempfile
    import threading

    repo, dev = _jax_env()

    from superlu_dist_tpu import Options
    from superlu_dist_tpu.obs import export
    from superlu_dist_tpu.serve import (ServeConfig, SolveService,
                                        run_load)
    from superlu_dist_tpu.utils.testmat import laplacian_3d

    k = int(os.environ.get("SLU_SERVE_K", "8"))
    concurrency = int(os.environ.get("SLU_SERVE_CONCURRENCY", "16"))
    requests = int(os.environ.get("SLU_SERVE_REQUESTS", "192"))
    trials = int(os.environ.get("SLU_EXPORT_AB_TRIALS", "5"))
    budget = float(os.environ.get("SLU_EXPORT_MAX_OVERHEAD", "0.05"))
    out_path = os.environ.get(
        "SLU_SERVE_OUT", os.path.join(repo, "SERVE_LATENCY.jsonl"))

    a = laplacian_3d(k)
    svc = SolveService(ServeConfig(
        max_queue_depth=max(64, 4 * requests)))
    print(f"# export A/B: factoring n={a.n} (k={k}) ...",
          file=sys.stderr)
    key = svc.prefactor(a, Options(factor_dtype="float64"))

    workdir = tempfile.mkdtemp(prefix="slu_export_ab_")
    sock_path = os.path.join(workdir, "obs.sock")
    jsonl_path = os.path.join(workdir, "obs.jsonl")

    rates: dict = {"off": [], "on": []}
    ratios = []
    scrapes = [0]
    for t in range(trials):
        order = ("off", "on") if t % 2 == 0 else ("on", "off")
        pair = {}
        for arm in order:
            stop_poll = threading.Event()
            poller = None
            if arm == "on":
                # the ON arm is the full deployment: listener +
                # periodic JSONL, with a live scraper hitting
                # /snapshot through the load — the worst realistic
                # cost, not an idle listener
                export.configure(enabled=True, listen=f"unix:{sock_path}",
                                 jsonl_path=jsonl_path, period_s=0.2)

                def poll() -> None:
                    while not stop_poll.wait(0.05):
                        try:
                            export.fetch(f"unix:{sock_path}")
                            scrapes[0] += 1
                        except (OSError, ValueError):
                            pass
                poller = threading.Thread(target=poll, daemon=True)
                poller.start()
            else:
                export.configure(enabled=False)
            rep = run_load(svc, [key], requests=requests,
                           concurrency=concurrency,
                           hot_fraction=1.0, seed=t)
            stop_poll.set()
            if poller is not None:
                poller.join(timeout=2.0)
            pair[arm] = rep["solves_per_s"]
            rates[arm].append(rep["solves_per_s"])
            print(f"# trial {t} {arm}: "
                  f"{rep['solves_per_s']:.1f} solves/s",
                  file=sys.stderr)
        if pair["off"] > 0 and pair["on"] > 0:
            ratios.append(pair["on"] / pair["off"])
        else:
            print(f"# trial {t}: zero-throughput arm, pair discarded",
                  file=sys.stderr)
    export.configure(enabled=False)
    svc.close()
    import shutil
    shutil.rmtree(workdir, ignore_errors=True)

    med_off = sorted(rates["off"])[trials // 2]
    med_on = sorted(rates["on"])[trials // 2]
    if ratios:
        med_ratio = sorted(ratios)[len(ratios) // 2]
        overhead = max(0.0, 1.0 - med_ratio)
    else:
        overhead = 1.0          # no valid pair: fail loudly below
    rec = {
        "mode": "export_ab",
        "n": a.n, "k": k,
        "concurrency": concurrency,
        "requests": requests,
        "trials": trials,
        "scrapes": scrapes[0],
        "solves_per_s_off": rates["off"],
        "solves_per_s_on": rates["on"],
        "median_off": med_off,
        "median_on": med_on,
        "pair_ratios": [round(r, 4) for r in ratios],
        "overhead_frac": round(overhead, 4),
        "budget_frac": budget,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    line = json.dumps(rec)
    print(line)
    with open(out_path, "a") as f:
        f.write(line + "\n")
    if overhead > budget:
        print(f"# EXPORT OVERHEAD REGRESSION: {overhead:.1%} > "
              f"{budget:.1%} (off {med_off:.1f}, on {med_on:.1f})",
              file=sys.stderr)
        raise SystemExit(1)
    return rec


# default chaos spec: every failure class the resilience layer claims
# to contain, all at once — lead-factorization raises, NaN factors,
# persisted-entry bit flips, flusher death, dispatch latency
DEFAULT_CHAOS_SPEC = ("factor_raise=0.3,factor_nan=0.3,store_flip=1,"
                      "flusher_raise=0.08,latency=0.2:0.003")


def _traceability(flight, report) -> dict:
    """Cross-check the load report's non-ok rids against the flight
    ring: each must resolve to a record with a failing stage."""
    rec = flight.get_recorder()
    if rec is None:
        return {"enabled": False}
    by_status = report.get("exemplars", {}).get("by_status", {})
    missing = []
    checked = 0
    for status, rids in by_status.items():
        for rid in rids:
            checked += 1
            fr = rec.lookup(rid) if rid is not None else None
            if fr is None or not fr.get("failed_stage"):
                missing.append({"status": status, "rid": rid})
    return {"enabled": True, "non_ok_checked": checked,
            "missing": missing, "complete": not missing}


def run_chaos(spec=None, argv=()):
    """The chaos gate: restart drill + standard load under fault
    injection.  Passes iff (a) the restart drill serves the key warm
    off the store with ZERO new factorizations, (b) every request
    under chaos resolves (no hangs), and (c) no caller ever receives
    a non-finite result.  Appends one JSON line to SLU_CHAOS_OUT
    (default CHAOS.jsonl)."""
    repo, dev = _jax_env()
    import shutil
    import tempfile

    from superlu_dist_tpu import Options
    from superlu_dist_tpu.resilience import chaos
    from superlu_dist_tpu.resilience.store import FactorStore
    from superlu_dist_tpu.serve import (FactorCache, ServeConfig,
                                        SolveService, run_load)
    from superlu_dist_tpu.utils.testmat import laplacian_3d

    flight, slo = _observability_on()
    spec = (spec or os.environ.get("SLU_CHAOS", "").strip()
            or DEFAULT_CHAOS_SPEC)
    seed = int(os.environ.get("SLU_CHAOS_SEED", "0") or "0")
    k = int(os.environ.get("SLU_SERVE_K", "6"))
    concurrency = int(os.environ.get("SLU_SERVE_CONCURRENCY", "8"))
    requests = int(os.environ.get("SLU_SERVE_REQUESTS", "96"))
    out_path = os.environ.get(
        "SLU_CHAOS_OUT", os.path.join(repo, "CHAOS.jsonl"))
    store_dir = tempfile.mkdtemp(prefix="slu_chaos_store_")
    try:
        a = laplacian_3d(k)
        opts = Options(factor_dtype="float64")
        # same pattern, drifted values (a transient-sim step family):
        # every variant is a cold full key whose factorization chaos
        # can kill — and the degraded-mode cover target for the
        # prefactored baseline's factors
        import dataclasses as _dc
        variants = [_dc.replace(a, data=a.data * (1.0 + i * 1e-8))
                    for i in range(1, 5)]

        svc = SolveService(ServeConfig(
            max_queue_depth=max(64, 4 * requests),
            store_dir=store_dir, factor_retries=2,
            retry_base_s=0.01, breaker_threshold=3,
            breaker_cooldown_s=0.5, degraded=True))
        print(f"# chaos: factoring n={a.n} (k={k}) ...",
              file=sys.stderr)
        key = svc.prefactor(a, opts)

        # --- restart gate: kill the replica (drop the cache), keep
        # the store dir; a fresh cache must serve the key warm with
        # zero new factorizations and a checksum-verified load
        cache2 = FactorCache(backend=svc.config.backend,
                             store=FactorStore(store_dir))
        lu2 = cache2.get_or_factorize(a, opts, key=key)
        st2 = cache2.stats()
        restart = {
            "factorizations": st2["factorizations"],
            "store_hits": st2["store_hits"],
            "warm": (st2["factorizations"] == 0
                     and st2["store_hits"] == 1
                     and lu2 is not None),
        }
        del cache2, lu2

        # --- chaos load: fresh values under injected failures
        print(f"# chaos: load under spec {spec!r} seed={seed}",
              file=sys.stderr)
        policy = chaos.install(spec, seed=seed)
        try:
            report = run_load(svc, [a] + variants, requests=requests,
                              concurrency=concurrency,
                              hot_fraction=0.4, seed=seed,
                              join_timeout_s=300.0)
        finally:
            chaos.uninstall()
        # --- corrupt-restart drill: a fresh replica boots against a
        # store whose every read is bit-flipped (chaos store_flip) —
        # every entry must QUARANTINE (never serve corrupt factors)
        # and the request must still succeed via a fresh
        # factorization
        chaos.install("store_flip=1", seed=seed)
        try:
            cache3 = FactorCache(backend=svc.config.backend,
                                 store=FactorStore(store_dir))
            lu3 = cache3.get_or_factorize(a, opts, key=key)
            st3 = cache3.stats()
            corrupt_restart = {
                "quarantined": st3["store_quarantined"],
                "refactored": st3["factorizations"],
                "served": lu3 is not None,
                "contained": (st3["store_quarantined"] >= 1
                              and st3["store_hits"] == 0
                              and lu3 is not None),
            }
            del cache3, lu3
        finally:
            chaos.uninstall()

        m = svc.metrics
        rec = {
            "mode": "chaos",
            "spec": spec,
            "seed": seed,
            "n": a.n,
            "k": k,
            "requests": requests,
            "concurrency": concurrency,
            "by_status": report["by_status"],
            "unresolved": report["unresolved"],
            "chaos_fired": policy.fired(),
            "restart": restart,
            "corrupt_restart": corrupt_restart,
            "cache": svc.cache.stats(),
            "store": svc.cache.store.stats(),
            "degraded_served": m.counter("serve.degraded_served"),
            "degraded_escalations":
                m.counter("serve.degraded_escalations"),
            "flusher_deaths": m.counter("batcher.flusher_died"),
            "batchers_replaced": m.counter("serve.batcher_replaced"),
            "breaker": (svc.cache.breaker.snapshot()
                        if svc.cache.breaker else None),
            # traceability: every non-ok outcome must have a flight
            # record naming its failing stage (the ISSUE-8 gate;
            # pinned independently by tests/test_flight.py)
            "exemplars": report.get("exemplars"),
            "flight_traceability": _traceability(flight, report),
            "slo": slo.snapshot(),
            "platform": dev.platform,
            "device_kind": getattr(dev, "device_kind", ""),
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
        svc.close()
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)

    nonfinite = rec["by_status"].get("nonfinite", 0)
    resolved_ok = rec["unresolved"] == 0
    # the documented contract is success / TYPED ServeError /
    # stamped-degraded: an untyped "error" outcome (a genuine bug
    # caught by the loadgen's last-resort handler) fails the gate too
    untyped = rec["by_status"].get("error", 0)
    # every non-ok outcome is one lookup from a flight record naming
    # its failing stage ("complete"); True when the recorder was
    # explicitly disabled (SLU_FLIGHT=0) — the gate then only covers
    # what it can see
    traceable = rec["flight_traceability"].get("complete", True)
    rec["gate"] = {
        "zero_hangs": resolved_ok,
        "zero_nonfinite": nonfinite == 0,
        "all_typed": untyped == 0,
        "restart_warm": rec["restart"]["warm"],
        "corruption_contained": rec["corrupt_restart"]["contained"],
        "traceable": traceable,
        "passed": (resolved_ok and nonfinite == 0 and untyped == 0
                   and rec["restart"]["warm"]
                   and rec["corrupt_restart"]["contained"]
                   and traceable),
    }
    line = json.dumps(rec)
    print(line)
    with open(out_path, "a") as f:
        f.write(line + "\n")
    if not rec["gate"]["passed"]:
        print(f"# CHAOS GATE FAILED: unresolved={rec['unresolved']} "
              f"nonfinite={nonfinite} restart={rec['restart']}",
              file=sys.stderr)
        raise SystemExit(1)
    return rec


def run_cold_boot_child(k: int, requests: int) -> dict:
    """One fresh-interpreter serve pass against the drill's shared
    store + AOT cache (SLU_FT_STORE / SLU_AOT_CACHE from the parent's
    env): prefactor-or-adopt the key, serve `requests` solves, and
    report the counters the gate reads.  Printed as a RESULT line —
    the test_warmup subprocess protocol."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    from superlu_dist_tpu.utils.cache import (ensure_portable_cpu_isa,
                                              place_compile_cache)
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        os.environ["XLA_FLAGS"] = ensure_portable_cpu_isa(
            os.environ.get("XLA_FLAGS", ""))
    import jax
    dev = jax.devices()[0]
    # the compile cache goes where every other run's goes
    # (JAX_COMPILATION_CACHE_DIR, else the checkout's fixed dir), so
    # a later drill finds it again; only the store and the AOT export
    # dir — what the drill is about — are the drill's own
    place_compile_cache()

    # persistent compile-cache hit/miss counters (the warmup drill's
    # monitoring-event probe): informational — the GATE rides the
    # deterministic AOT counters
    cc_hits, cc_misses = [0], [0]

    def _listen(event, *a, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            cc_hits[0] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cc_misses[0] += 1
    jax.monitoring.register_event_listener(_listen)

    from superlu_dist_tpu import Options
    from superlu_dist_tpu.resilience import aot
    from superlu_dist_tpu.serve import ServeConfig, SolveService
    from superlu_dist_tpu.utils.testmat import laplacian_3d

    t_boot = time.perf_counter()
    a = laplacian_3d(k)
    opts = Options(factor_dtype="float64")
    svc = SolveService(ServeConfig(max_queue_depth=256))
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    svc.prefactor(a, opts)          # factor-or-adopt + bucket warmup
    t_warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    x = svc.solve(a, rng.standard_normal(a.n), opts)
    t_first = time.perf_counter() - t0
    finite = bool(np.all(np.isfinite(np.asarray(x))))
    for _ in range(max(0, requests - 1)):
        svc.solve(a, rng.standard_normal(a.n), opts)
    st = svc.cache.stats()
    rec = {
        "factorizations": st["factorizations"],
        "store_hits": st.get("store_hits", 0),
        "aot": aot.stats(),
        "t_warm_s": round(t_warm, 3),
        "t_first_solve_s": round(t_first, 4),
        "t_ready_s": round(time.perf_counter() - t_boot, 3),
        "compile_cache_hits": cc_hits[0],
        "compile_cache_misses": cc_misses[0],
        "finite": finite,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
    }
    svc.close()
    print("RESULT " + json.dumps(rec))
    return rec


def run_cold_boot(argv=(), k=None, requests=None, out_path=None):
    """Fresh-PROCESS cold-boot drill (ISSUE 12; the PR 5 restart
    drill's compile-side peer).  Two child interpreters run the same
    serve pass against ONE shared durable store + AOT cache:

      * child 1 (genuinely cold) factors, exports the whole-phase
        programs write-through, and populates the store + the
        compilation cache;
      * child 2 (fresh process, warm artifacts) must serve with
        `factorizations == 0` (store adoption — the PR 5 contract)
        AND `aot.misses == 0` with `aot.hits >= 1` (every AOT-wrapped
        whole-phase program deserialized instead of re-traced — the
        new contract), i.e. the 14–33 s jit warmup and the 2m4s
        whole-phase compile are both skipped.

    Appends one `mode=cold_boot` line to SLU_SERVE_OUT (default
    SERVE_LATENCY.jsonl); tools/regress.py gates the counters.  A
    failed gate stamps measurement_invalid, persists nothing, and
    exits 1 (the --solve-sweep convention)."""
    import shutil
    import subprocess
    import tempfile
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    k = k if k is not None else int(os.environ.get("SLU_SERVE_K", "8"))
    requests = (requests if requests is not None
                else min(int(os.environ.get("SLU_SERVE_REQUESTS",
                                            "32")), 64))
    out_path = out_path or os.environ.get(
        "SLU_SERVE_OUT", os.path.join(repo, "SERVE_LATENCY.jsonl"))
    store_dir = tempfile.mkdtemp(prefix="slu_cold_store_")
    aot_dir = tempfile.mkdtemp(prefix="slu_cold_aot_")

    def child(tag):
        env = dict(os.environ)
        env["SLU_FT_STORE"] = store_dir
        env["SLU_AOT_CACHE"] = aot_dir
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH",
                                                        "")
        t0 = time.perf_counter()
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--cold-boot-child", str(k), str(requests)],
            env=env, capture_output=True, text=True, timeout=3600)
        wall = time.perf_counter() - t0
        if p.returncode != 0:
            print(p.stderr[-4000:], file=sys.stderr)
            raise SystemExit(f"cold-boot child ({tag}) failed rc="
                             f"{p.returncode}")
        line = [ln for ln in p.stdout.splitlines()
                if ln.startswith("RESULT ")][-1]
        d = json.loads(line[len("RESULT "):])
        d["proc_wall_s"] = round(wall, 2)
        return d

    try:
        print(f"# cold-boot drill: child 1 (cold) k={k} ...",
              file=sys.stderr)
        first = child("cold")
        print(f"# cold-boot drill: child 2 (warm artifacts) ...",
              file=sys.stderr)
        second = child("warm")
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)
        shutil.rmtree(aot_dir, ignore_errors=True)

    # this parent never imports jax: the two children run one after
    # the other and each needs the device to itself; the platform
    # stamp is the warm child's own
    gate = {
        "warm_store": second["factorizations"] == 0
        and second["store_hits"] >= 1,
        "aot_no_retrace": (second["aot"]["misses"] == 0
                           and second["aot"]["rejected"] == 0
                           and second["aot"]["hits"] >= 1),
        "cold_exported": first["aot"]["saves"] >= 1,
        "finite": first["finite"] and second["finite"],
    }
    gate["passed"] = all(gate.values())
    rec = {
        "mode": "cold_boot",
        "desc": f"fresh-process cold-boot drill 3D Laplacian "
                f"n={k ** 3}",
        "k": k, "n": k ** 3, "requests": requests,
        "cold": first, "warm": second,
        "factorizations": second["factorizations"],
        "aot_hits": second["aot"]["hits"],
        "aot_misses": second["aot"]["misses"],
        "aot_rejected": second["aot"]["rejected"],
        "warm_ready_s": second["t_ready_s"],
        "cold_ready_s": first["t_ready_s"],
        "ready_speedup": round(
            first["t_ready_s"] / max(second["t_ready_s"], 1e-9), 2),
        "gate": gate,
        "platform": second["platform"],
        "device_kind": second["device_kind"],
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    if not gate["passed"]:
        rec["measurement_invalid"] = True
        print(json.dumps(rec))
        print(f"# COLD-BOOT GATE FAILED: {gate}", file=sys.stderr)
        raise SystemExit(1)
    line = json.dumps(rec)
    print(line)
    with open(out_path, "a") as f:
        f.write(line + "\n")
    return rec


# --------------------------------------------------------------------
# streaming refactorization drill (ISSUE 13): --stream
# --------------------------------------------------------------------

# default chaos for the kill-drill child: background-factor failures
# (raise + slow) AND the mid-swap kill -9, all at once
STREAM_CHAOS_SPEC = ("refactor_raise=0.25,refactor_slow=0.4:0.05,"
                     "swap_kill=1")


def _stream_params():
    return {
        "k": int(os.environ.get("SLU_SERVE_K", "8")),
        "concurrency": int(os.environ.get("SLU_SERVE_CONCURRENCY",
                                          "8")),
        # 192 (vs the serve drill's 96): the overlap gate reads p99
        # off each arm's ok-latency set — at 96 paced requests p99 is
        # the single worst sample and one unlucky swap collision
        # decides the gate; 192 makes it a real percentile
        "requests": int(os.environ.get("SLU_SERVE_REQUESTS", "192")),
        "steps": int(os.environ.get("SLU_STREAM_STEPS", "24")),
        "step_hz": float(os.environ.get("SLU_STREAM_STEP_HZ", "4")),
        # calibrated: at 5e-4/step a 24-step walk refines to ~2e-16
        # berr off the PINNED generation-1 factors — two decades
        # inside the 64·eps class; 2e-3 breaches the guard by step ~8
        # (measured, 3D Laplacian) — the drill proves refinement
        # covers the drift, not that the guard fires
        "drift": float(os.environ.get("SLU_STREAM_DRIFT", "5e-4")),
        "trials": int(os.environ.get("SLU_STREAM_TRIALS", "3")),
        "tol": float(os.environ.get("SLU_STREAM_OVERLAP_TOL",
                                    "1.10")),
    }


def _drift_values(a, step: int, drift: float, seed: int):
    """Deterministic per-step drifted values: a multiplicative random
    walk of amplitude `drift` per step (seeded by (seed, step) alone,
    so a restarted child regenerates the identical sequence)."""
    import dataclasses as _dc
    data = a.data
    for t in range(1, step + 1):
        rng = np.random.default_rng(seed * 104729 + t)
        data = data * (1.0 + drift * rng.standard_normal(data.shape))
    return _dc.replace(a, data=data)


def _stream_arm(svc, a, p, *, background: bool, seed: int,
                indices=None, journal_path=None, start_step: int = 0,
                join_timeout_s=None):
    """One transient-sim load pass on a FRESH StreamHandle.  The
    drift sequence is deterministic in `seed`; `start_step` lets the
    restart child resume the walk where the killed child's store
    left off."""
    from superlu_dist_tpu.serve import run_stream_load
    from superlu_dist_tpu.stream import StreamConfig

    base = (_drift_values(a, start_step, p["drift"], seed)
            if start_step else a)
    fact_before = svc.cache.stats()["factorizations"]
    h = svc.stream(base, None,
                   StreamConfig(background=background,
                                # drill scale: swaps are LAG-forced
                                # (the calibrated drift never trips
                                # the berr cadence by design), so the
                                # swap rate here is a drill choice.
                                # max_lag=16 at 4 Hz = a swap per 4 s
                                # (~1.5/window): a refactor's ~50 ms
                                # hot window slows colliding solves
                                # ~2x on the shared XLA:CPU pool
                                # (measured; DESIGN §20), so the
                                # drill holds the background duty
                                # cycle ~1% the way a real cadence's
                                # interval_scale would — max_lag=4's
                                # swap-per-second puts 5%+ of paced
                                # requests inside hot windows and p99
                                # reads the collision, not the
                                # steady state
                                interval_scale=0.0, max_lag=16))
    prime_factorizations = (svc.cache.stats()["factorizations"]
                            - fact_before)
    try:
        # pace the load to SPAN the drift window (requests spread
        # over the steps) — an unpaced drain would finish while every
        # value set is still fresh and measure no streaming at all
        n_req = len(indices) if indices is not None else p["requests"]
        steps_left = max(1, p["steps"] - start_step)
        rate = n_req * p["step_hz"] / steps_left
        rep = run_stream_load(
            [(h, lambda t: _drift_values(a, start_step + t,
                                         p["drift"], seed))],
            steps=p["steps"] - start_step, step_hz=p["step_hz"],
            requests=p["requests"], concurrency=p["concurrency"],
            seed=seed, rate_hz=rate, indices=indices,
            journal_path=journal_path,
            join_timeout_s=join_timeout_s)
        rep["status"] = h.status()
        rep["prime_factorizations"] = prime_factorizations
    finally:
        h.close()
    return rep


def run_stream_child(k: int, steps: int, requests: int, drift: float,
                     seed: int, journal_path: str) -> dict:
    """Kill-drill child: stream load under SLU_CHAOS (background
    refactor failures + the mid-swap `swap_kill`) against the shared
    SLU_FT_STORE, journaling every completed request.  Under
    swap_kill=1 this process DIES BY SIGKILL at its first resident
    swap — the RESULT line only appears if chaos never killed it
    (the parent treats that as a drill failure)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    from superlu_dist_tpu.resilience import chaos
    from superlu_dist_tpu.serve import ServeConfig, SolveService
    from superlu_dist_tpu.utils.testmat import laplacian_3d

    _jax_env()
    chaos.install_from_env()
    p = _stream_params()
    p.update(k=k, steps=steps, requests=requests, drift=drift)
    a = laplacian_3d(k)
    svc = SolveService(ServeConfig(
        max_queue_depth=max(64, 4 * requests), factor_retries=1,
        retry_base_s=0.01, breaker_threshold=4,
        breaker_cooldown_s=0.5))
    rep = _stream_arm(svc, a, p, background=True, seed=seed,
                      journal_path=journal_path,
                      join_timeout_s=600.0)
    svc.close()
    rec = {"by_status": rep["by_status"],
           "unresolved": rep["unresolved"],
           "swaps": rep["stream"]["swaps"]}
    print("RESULT " + json.dumps(rec))
    return rec


def run_stream_restart_child(k: int, steps: int, requests: int,
                             drift: float, seed: int,
                             journal_path: str) -> dict:
    """Restart child: boot against the killed child's store, prime
    from WHICHEVER generation the store last published (scan the
    deterministic drift walk newest-first), assert the prime paid no
    factorization (warm-generation restart), then complete every
    journal index the killed child never resolved."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    from superlu_dist_tpu.serve import ServeConfig, SolveService
    from superlu_dist_tpu.serve.factor_cache import matrix_key
    from superlu_dist_tpu.utils.testmat import laplacian_3d

    _jax_env()
    p = _stream_params()
    p.update(k=k, steps=steps, requests=requests, drift=drift)
    a = laplacian_3d(k)
    svc = SolveService(ServeConfig(
        max_queue_depth=max(64, 4 * requests)))
    store = svc.cache.store
    assert store is not None, "restart child needs SLU_FT_STORE"
    # whichever generation the store last published: the drift walk
    # is deterministic, so scan it newest-first for a durable entry
    prime_step = 0
    for t in range(steps, -1, -1):
        key_t = matrix_key(_drift_values(a, t, drift, seed))
        if store.contains(key_t):
            prime_step = t
            break
    done = set()
    with open(journal_path) as f:
        for line in f:
            try:
                done.add(int(json.loads(line)["i"]))
            except (ValueError, KeyError):
                continue
    missing = [i for i in range(requests) if i not in done]
    rep = _stream_arm(svc, a, p, background=True, seed=seed,
                      indices=missing, journal_path=journal_path,
                      start_step=prime_step, join_timeout_s=600.0)
    st = svc.cache.stats()
    rec = {
        "prime_step": prime_step,
        "factorizations_at_prime": rep["prime_factorizations"],
        "factorizations": st["factorizations"],
        "store_hits": st["store_hits"],
        "replayed": len(missing),
        "by_status": rep["by_status"],
        "unresolved": rep["unresolved"],
        "guard_breaches": rep["stream"]["guard_breaches"],
    }
    svc.close()
    print("RESULT " + json.dumps(rec))
    return rec


def run_stream(argv=()):
    """The ISSUE-13 drift drill: (a) steady-state OVERLAP A/B — the
    same transient-sim load with the background refactor pipeline ON
    vs PINNED (no refactor, refine-only), interleaved pairs with
    alternating order, gating the POOLED-across-trials p99 ratio at
    SLU_STREAM_OVERLAP_TOL (1.10: overlap proven — background
    factorization does not steal the serving path's tail); (b) the
    KILL DRILL — a child process under refactor_raise/refactor_slow
    chaos plus swap_kill=1 dies by kill -9 MID-SWAP, the restart
    child boots warm from whichever generation the shared store last
    published (factorizations == 0 at prime) and completes every
    request the victim left unresolved (zero lost fleet-wide).
    Appends one mode="stream" line to SLU_SERVE_OUT and runs the
    regression sentinel; a failed gate stamps measurement_invalid,
    persists nothing, and exits 1."""
    import shutil
    import signal
    import subprocess
    import tempfile

    repo, dev = _jax_env()
    from superlu_dist_tpu import Options
    from superlu_dist_tpu.serve import ServeConfig, SolveService
    from superlu_dist_tpu.utils.testmat import laplacian_3d

    flight, slo = _observability_on()
    p = _stream_params()
    out_path = os.environ.get(
        "SLU_SERVE_OUT", os.path.join(repo, "SERVE_LATENCY.jsonl"))
    a = laplacian_3d(p["k"])
    print(f"# stream drill: n={a.n} (k={p['k']}) steps={p['steps']} "
          f"drift={p['drift']}", file=sys.stderr)

    # --- phase 1: overlap A/B (in-process, interleaved pairs) ---
    svc = SolveService(ServeConfig(
        max_queue_depth=max(64, 4 * p["requests"])))
    svc.prefactor(a, Options())      # shared warm base + jit warmup
    # one UNMEASURED pair first: the first run of each arm pays
    # one-time costs (stale-variant program warmup, the worker's
    # first probe) that a steady-state comparison must not count
    for warm_arm in (False, True):
        _stream_arm(svc, a, p, background=warm_arm, seed=999)
    arms: dict = {"pinned": [], "stream": []}
    ratios = []
    breaches = rejected = 0
    swaps_total = 0
    for t in range(p["trials"]):
        order = (("pinned", "stream") if t % 2 == 0
                 else ("stream", "pinned"))
        pair = {}
        for arm in order:
            rep = _stream_arm(svc, a, p, background=(arm == "stream"),
                              seed=1000 + t)
            pair[arm] = rep
            arms[arm].append(rep)
            if arm == "stream":
                swaps_total += rep["stream"]["swaps"]
            print(f"# trial {t} {arm}: p99={rep.get('p99_ms', 0):.1f}"
                  f"ms ok={rep['by_status'].get('ok', 0)}"
                  f" swaps={rep['stream']['swaps']}", file=sys.stderr)
        # per-run deltas summed over MEASURED runs only: the
        # cumulative service counter would fail the zero-gate on a
        # breach in the deliberately unmeasured warmup pair
        breaches = sum(r["stream"]["guard_breaches"]
                       for rs in arms.values() for r in rs)
        rejected += sum(r["by_status"].get("stale_rejected", 0)
                        for r in pair.values())
        if pair["pinned"].get("p99_ms") and pair["stream"].get(
                "p99_ms"):
            ratios.append(pair["stream"]["p99_ms"]
                          / pair["pinned"]["p99_ms"])
    svc.close()
    # THE overlap measurement: pooled ok latencies across all trials
    # per arm (trials x requests samples) — a per-pair p99 ratio is
    # decided by each run's worst ~2 samples and flips on scheduler
    # noise (observed pair ratios 0.85-1.50 on one green config);
    # the pooled p99 is a real percentile of the steady state.  The
    # per-pair ratios stay in the record for transparency.
    from superlu_dist_tpu.serve.metrics import nearest_rank
    pooled = {arm: np.array(sorted(
        ms for r in reps for ms in r.get("ok_ms", [])))
        for arm, reps in arms.items()}
    overlap_ratio = None
    if len(pooled["pinned"]) and len(pooled["stream"]):
        overlap_ratio = (nearest_rank(pooled["stream"], 99)
                         / nearest_rank(pooled["pinned"], 99))
    unresolved = sum(r["unresolved"] for rs in arms.values()
                     for r in rs)
    nonfinite = sum(r["by_status"].get("nonfinite", 0)
                    for rs in arms.values() for r in rs)
    untyped = sum(r["by_status"].get("error", 0)
                  for rs in arms.values() for r in rs)

    # --- phase 2: the kill drill (subprocesses on one store) ---
    store_dir = tempfile.mkdtemp(prefix="slu_stream_store_")
    jdir = tempfile.mkdtemp(prefix="slu_stream_journal_")
    journal = os.path.join(jdir, "journal.jsonl")
    drill_seed = int(os.environ.get("SLU_CHAOS_SEED", "0") or "0")

    def child(kind, extra_env):
        # this parent has already run the overlap arms on jax, so it
        # holds whatever accelerator there is; the kill drill is a
        # CPU correctness drill and its children say so
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["SLU_FT_STORE"] = store_dir
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH",
                                                        "")
        env.update(extra_env)
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__), kind,
             str(p["k"]), str(p["steps"]), str(p["requests"]),
             str(p["drift"]), str(drill_seed), journal],
            env=env, capture_output=True, text=True, timeout=3600)

    try:
        print("# stream drill: victim child (chaos + swap_kill) ...",
              file=sys.stderr)
        spec = os.environ.get("SLU_CHAOS", "").strip() \
            or STREAM_CHAOS_SPEC
        victim = child("--stream-child", {"SLU_CHAOS": spec})
        killed_by_sigkill = victim.returncode == -signal.SIGKILL
        if not killed_by_sigkill:
            print(victim.stderr[-3000:], file=sys.stderr)
        with open(journal) as f:
            victim_done = sum(1 for _ in f)
        print(f"# victim rc={victim.returncode} "
              f"(SIGKILL={killed_by_sigkill}), "
              f"{victim_done}/{p['requests']} journaled",
              file=sys.stderr)
        print("# stream drill: restart child (warm takeover) ...",
              file=sys.stderr)
        restart = child("--stream-restart-child", {"SLU_CHAOS": ""})
        if restart.returncode != 0:
            print(restart.stderr[-3000:], file=sys.stderr)
            raise SystemExit("stream restart child failed rc="
                             f"{restart.returncode}")
        line = [ln for ln in restart.stdout.splitlines()
                if ln.startswith("RESULT ")][-1]
        rst = json.loads(line[len("RESULT "):])
        # fleet-wide accounting off the shared journal: every index
        # resolved exactly once across victim + restart
        seen: dict = {}
        nonfinite_drill = 0
        with open(journal) as f:
            for ln in f:
                try:
                    d = json.loads(ln)
                    i, status = int(d["i"]), d["status"]
                except (ValueError, KeyError, TypeError):
                    # the victim's SIGKILL can tear its final line;
                    # the fragment's index was never durably recorded
                    # and the restart child replayed it
                    continue
                seen[i] = status
                if status == "nonfinite":
                    nonfinite_drill += 1
        lost = p["requests"] - len(seen)
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)
        shutil.rmtree(jdir, ignore_errors=True)

    drill = {
        "platform": "cpu",      # the children's, pinned above
        "chaos_spec": spec,
        "killed_rc": victim.returncode,
        "killed_by_sigkill": killed_by_sigkill,
        "victim_journaled": victim_done,
        "restart": rst,
        "lost": lost,
        "hung": rst["unresolved"],
        "nonfinite": nonfinite_drill,
        "by_status": _count_statuses(seen),
    }
    gate = {
        "overlap": (overlap_ratio is not None
                    and overlap_ratio <= p["tol"]),
        "swaps": swaps_total >= 1,
        "zero_unresolved": unresolved == 0,
        "zero_nonfinite": nonfinite == 0 and nonfinite_drill == 0,
        "all_typed": (untyped == 0
                      and sum(1 for s in seen.values()
                              if s == "error") == 0),
        # every drill request resolved OK fleet-wide — zero_lost/
        # zero_hung alone would pass a journaled typed FAILURE
        # (stale_rejected, serve_error) as accounted-for
        "drill_all_ok": (len(seen) > 0
                         and all(s == "ok" for s in seen.values())),
        "berr_guard_never_breached": breaches == 0 and rejected == 0
        and rst["guard_breaches"] == 0,
        "kill_mid_swap": killed_by_sigkill,
        "zero_lost": lost == 0,
        "zero_hung": rst["unresolved"] == 0,
        "warm_generation_restart": (rst["factorizations_at_prime"]
                                    == 0 and rst["store_hits"] >= 1
                                    and rst["prime_step"] >= 1),
    }
    gate["passed"] = all(gate.values())
    rec = {
        "mode": "stream",
        "desc": f"streaming refactorization drift drill 3D Laplacian "
                f"n={a.n}",
        "n": a.n, "k": p["k"], "requests": p["requests"],
        "steps": p["steps"], "step_hz": p["step_hz"],
        "drift": p["drift"], "concurrency": p["concurrency"],
        "trials": p["trials"],
        "arms": {
            arm: {
                "p99_ms": [round(r.get("p99_ms", 0.0), 3)
                           for r in reps],
                "solves_per_s": [round(r["solves_per_s"], 2)
                                 for r in reps],
                "by_status": _merge_statuses(r["by_status"]
                                             for r in reps),
                "swaps": sum(r["stream"]["swaps"] for r in reps),
                # per-run deltas (run_stream_load) summed over the
                # arm's trials: each arm's figure is ITS solves only
                "stale_solves": sum(r["stream"]["stale_solves"]
                                    for r in reps),
                "fresh_solves": sum(r["stream"]["fresh_solves"]
                                    for r in reps),
            } for arm, reps in arms.items()
        },
        "pair_ratios": [round(r, 4) for r in ratios],
        "overlap_ratio": (round(overlap_ratio, 4)
                          if overlap_ratio is not None else None),
        "overlap_tol": p["tol"],
        "swaps": swaps_total,
        "guard_breaches": breaches,
        "stale_rejected": rejected,
        "unresolved": unresolved,
        "lost": lost,
        "hung": rst["unresolved"],
        "drill": drill,
        "gate": gate,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    if not gate["passed"]:
        rec["measurement_invalid"] = True
        print(json.dumps(rec))
        print(f"# STREAM GATE FAILED: "
              f"{ {k: v for k, v in gate.items() if not v} }",
              file=sys.stderr)
        raise SystemExit(1)
    line = json.dumps(rec)
    print(line)
    with open(out_path, "a") as f:
        f.write(line + "\n")
    return rec


def _count_statuses(seen: dict) -> dict:
    out: dict = {}
    for s in seen.values():
        out[s] = out.get(s, 0) + 1
    return out


def _merge_statuses(dicts) -> dict:
    out: dict = {}
    for d in dicts:
        for k, v in d.items():
            out[k] = out.get(k, 0) + v
    return out


def _regress_gate(repo):
    """Post-run perf-regression sentinel: the record just appended is
    now the latest — gate it against the committed baselines."""
    if os.environ.get("SLU_REGRESS", "1") == "0":
        return
    # script-style invocation (`python tools/serve_bench.py`) puts
    # tools/ on sys.path, not the repo root; the cold-boot parent
    # never calls _setup() (it only orchestrates child processes), so
    # ensure the root is importable here
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tools import regress
    findings, passed = regress.check_repo(repo)
    print(regress.format_findings(findings), file=sys.stderr)
    if not passed:
        print("# PERF REGRESSION (tools/regress.py): see findings "
              "above; a legitimate perf change re-baselines via "
              "`python -m tools.regress --update`", file=sys.stderr)
        raise SystemExit(1)


def main():
    argv = sys.argv[1:]
    if "--cold-boot-child" in argv:
        i = argv.index("--cold-boot-child")
        run_cold_boot_child(int(argv[i + 1]), int(argv[i + 2]))
        return
    for kind, fn in (("--stream-child", run_stream_child),
                     ("--stream-restart-child",
                      run_stream_restart_child)):
        if kind in argv:
            i = argv.index(kind)
            fn(int(argv[i + 1]), int(argv[i + 2]), int(argv[i + 3]),
               float(argv[i + 4]), int(argv[i + 5]), argv[i + 6])
            return
    if "--stream" in argv:
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        run_stream(argv)
        _regress_gate(repo)
        return
    if "--cold-boot" in argv:
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        run_cold_boot(argv)
        _regress_gate(repo)
        return
    if "--fleet" in argv:
        # the multi-process fleet drill (tools/fleet_drill.py):
        # replica pool + shared store + kill -9, gated via FLEET.jsonl
        from tools.fleet_drill import main as fleet_main
        sys.argv = [sys.argv[0]]       # the drill reads env, not argv
        fleet_main()
        return
    if "--chaos" in argv:
        i = argv.index("--chaos")
        spec = (argv[i + 1] if i + 1 < len(argv)
                and not argv[i + 1].startswith("--") else None)
        run_chaos(spec, argv)
        return
    if "--flight-ab" in argv:
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        run_flight_ab(argv)
        _regress_gate(repo)
        return
    if "--export-ab" in argv:
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        run_export_ab(argv)
        _regress_gate(repo)
        return
    rec = run(argv)
    # regression gate: batching must never LOSE to sequential and
    # never recompile under load — fail the process so exit-code gates
    # (and bench.py --serve) see it.  The floor defaults to 1.0
    # because the timeshared rehearsal box swings the same-moment A/B
    # between ~1.2× and ~3.2× under scheduler noise (quiet-box
    # record: 3.18×, SERVE_LATENCY.jsonl); raise via
    # SLU_SERVE_MIN_SPEEDUP on dedicated hardware.
    floor = float(os.environ.get("SLU_SERVE_MIN_SPEEDUP", "1.0"))
    # both recompile probes must stay at zero: the obs CompileWatch
    # counter attributes misses by (shape, dtype, statics) signature,
    # but jax's own cache also keys on sharding/committed-ness/weak
    # types — a recompile that keeps the signature is only visible as
    # jit-cache growth, so the growth cross-check stays enforced
    # the mixed-dtype scenario's own pin: rung switching under load
    # must never recompile (each rung's variants were warmed by its
    # prefactor) — precision is a cache key, not a compile trigger
    mixed = rec.get("mixed_dtype")
    mixed_ok = (mixed is None
                or mixed["recompiles_across_rungs"] == 0)
    ok = (rec["speedup_vs_sequential"] >= floor
          and (rec["recompiles_under_load"] in (0, None))
          and (rec["jit_cache_growth"] in (0, None))
          and mixed_ok)
    if not ok:
        print(f"# SERVE REGRESSION: speedup="
              f"{rec['speedup_vs_sequential']:.2f} recompiles="
              f"{rec['recompiles_under_load']} jit_cache_growth="
              f"{rec['jit_cache_growth']} mixed="
              f"{mixed and mixed['recompiles_across_rungs']}",
              file=sys.stderr)
        raise SystemExit(1)
    # historical gate: the fresh record vs the committed baselines
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    _regress_gate(repo)


if __name__ == "__main__":
    main()
