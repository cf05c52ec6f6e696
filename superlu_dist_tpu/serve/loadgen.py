"""Closed-loop load generator for the solve service.

`concurrency` worker threads each run a closed loop: draw a think
time from an exponential distribution (Poisson arrivals per worker
when `rate_hz` is set; zero think time = maximum pressure), pick a
matrix key by skew, issue a blocking solve, record (latency, status).
Key skew models multi-tenant traffic: with probability `hot_fraction`
a request hits key 0, else a uniform draw over the rest — so cache
hits, LRU churn and per-key batching are all exercised by one knob.

Everything is seeded; the same load spec replays the same request
sequence (modulo thread scheduling), which keeps the tier-1 serve
test deterministic enough to assert on.

The report is JSON-ready: per-status counts, latency percentiles in
milliseconds, wall-clock solves/s, and the service metrics snapshot.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from .errors import (DeadlineExceeded, DegradedResult, FactorMissError,
                     FactorPoisoned, FlusherDead, ServeError,
                     ServeRejected, StaleFactorError, TenantThrottled)
from .service import SolveService


def run_load(service: SolveService, matrices, *,
             requests: int = 128, concurrency: int = 8,
             rate_hz: float | None = None,
             hot_fraction: float = 1.0,
             deadline_s: float | None = None,
             options=None,
             seed: int = 0,
             grad_fraction: float = 0.0,
             batch_fraction: float = 0.0,
             batch_singular_fraction: float = 0.0,
             batch_options=None,
             join_timeout_s: float | None = None) -> dict:
    """Drive `requests` total solves through `service` from
    `concurrency` closed-loop workers; returns the report dict.

    `matrices` is a list of (CSRMatrix | CacheKey); index 0 is the hot
    key.  Workers split the request count evenly (remainder to the
    first workers).

    `grad_fraction` of requests go through service.grad_solve()
    instead — the adjoint-under-load lane.  Their statuses land in
    the same report prefixed `grad_` (its finite probe covers the
    solution AND both cotangents), so a gate can pin e.g. zero
    `grad_miss_failfast` alongside the solve mix.

    `batch_fraction` of requests are COLD same-pattern factor
    requests instead: the worker perturbs the picked matrix's values
    (fresh key, same pattern) and prefactors it — under concurrency
    these bursts are exactly the traffic the factor coalescer
    (serve/coalescer.py, SLU_BATCH_COALESCE=1) merges into batched
    dispatches.  Statuses land prefixed `batch_`: `batch_ok` for a
    fanned-back resident, `batch_member_refused` for a member's OWN
    typed refusal (the masked-member contract — a singular member
    fails per-index, siblings still read batch_ok).
    `batch_singular_fraction` of those requests carry all-zero values
    to force that refusal (pair it with
    `batch_options=Options(replace_tiny_pivot=NO)`; under default
    options the zero member is perturbed and stamps its ledger
    instead).  Matrices given as CacheKeys can't seed the lane (no
    pattern to perturb) and fall through to ordinary solves.

    `join_timeout_s` bounds the wait for workers: the report's
    `unresolved` field counts requests that never produced a status —
    the chaos gate's zero-hangs pin (a hung future means a worker
    never returns; without the bound the hang would eat the caller).
    None (the default) keeps unbounded joins for cooperative loads."""
    matrices = list(matrices)
    n_workers = min(concurrency, requests)
    counts = [requests // n_workers] * n_workers
    for i in range(requests % n_workers):
        counts[i] += 1
    results: list[tuple[float, str]] = []
    res_lock = threading.Lock()

    def rhs_dim(m):
        # CacheKey carries no n; workers size the RHS off the resident
        # factors instead
        if hasattr(m, "n"):
            return m.n
        lu = service.cache.peek(m, touch=False)
        if lu is None:
            raise ValueError("CacheKey target must be prefactored")
        return lu.n

    dims = [rhs_dim(m) for m in matrices]

    def worker(wid: int, n_req: int) -> None:
        rng = np.random.default_rng(seed * 1009 + wid)
        for _ in range(n_req):
            if rate_hz:
                time.sleep(rng.exponential(n_workers / rate_hz))
            if len(matrices) == 1 or rng.random() < hot_fraction:
                mi = 0
            else:
                mi = 1 + int(rng.integers(len(matrices) - 1))
            b = rng.standard_normal(dims[mi])
            # out-of-band request metadata: the flight-recorder rid
            # (None with SLU_FLIGHT off) keys the exemplar report
            info: dict = {}
            t0 = time.monotonic()
            # ONE status taxonomy (_status_of_solve) for every load
            # generator — a second inline except-chain here had
            # already drifted from it (StaleFactorError folded into
            # serve_error)
            mat = matrices[mi]
            if (batch_fraction > 0.0 and hasattr(mat, "data")
                    and rng.random() < batch_fraction):
                if (batch_singular_fraction > 0.0
                        and rng.random() < batch_singular_fraction):
                    data = np.zeros_like(mat.data)
                else:
                    data = mat.data * (1.0 + 0.05 * rng.standard_normal(
                        len(mat.data)))
                fresh = type(mat)(mat.m, mat.n, mat.indptr,
                                  mat.indices, data)
                status, _x = _status_of_batch(
                    lambda: service.prefactor(
                        fresh, batch_options or options))
            elif grad_fraction > 0.0 and rng.random() < grad_fraction:
                status, _x = _status_of_grad(
                    lambda: service.grad_solve(matrices[mi], b,
                                               options=options))
            else:
                status, _x = _status_of_solve(
                    lambda: service.solve(matrices[mi], b,
                                          options=options,
                                          deadline_s=deadline_s,
                                          info=info))
            with res_lock:
                results.append((time.monotonic() - t0, status,
                                info.get("request_id")))

    threads = [threading.Thread(target=worker, args=(i, c), daemon=True)
               for i, c in enumerate(counts)]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    if join_timeout_s is None:
        for t in threads:
            t.join()
    else:
        join_deadline = t_start + join_timeout_s
        for t in threads:
            t.join(max(0.0, join_deadline - time.monotonic()))
    wall_s = time.monotonic() - t_start
    # flush deferred flight/SLO finalizations before the report reads
    # exemplar rids (finalization is deferred off the flusher thread)
    service.drain_observability()

    by_status: dict[str, int] = {}
    for _, s, _rid in results:
        by_status[s] = by_status.get(s, 0) + 1
    from .metrics import nearest_rank
    ok = sorted(((lat, rid) for lat, s, rid in results if s == "ok"),
                key=lambda t: t[0])
    ok_lat = np.array([lat for lat, _ in ok])
    report = {
        "requests": requests,
        "concurrency": n_workers,
        "hot_fraction": hot_fraction,
        "wall_s": wall_s,
        "by_status": by_status,
        # requests that never produced ANY status: zero unless a
        # worker hung past join_timeout_s — the chaos gate fails on
        # a single one
        "unresolved": requests - len(results),
        "solves_per_s": (len(ok_lat) / wall_s) if wall_s > 0 else 0.0,
        "metrics": service.metrics.snapshot(),
        "exemplars": _exemplars(ok, results),
    }
    if len(ok_lat):
        def pct(p):
            return nearest_rank(ok_lat, p) * 1e3
        report.update(p50_ms=pct(50), p95_ms=pct(95), p99_ms=pct(99),
                      mean_ms=float(ok_lat.mean()) * 1e3)
    return report


def _status_of_solve(do_solve) -> tuple[str, object]:
    """Run one blocking solve; map the outcome to the status
    taxonomy.  Returns (status, x-or-None)."""
    try:
        x = do_solve()
    except TenantThrottled:
        # BEFORE ServeRejected (its base class): a QoS shed is policy
        # doing its job, not a full queue
        return "shed", None
    except ServeRejected:
        return "rejected", None
    except DeadlineExceeded:
        return "deadline", None
    except FactorMissError:
        return "miss_failfast", None
    except FactorPoisoned:
        return "poisoned", None
    except FlusherDead:
        return "flusher_dead", None
    except StaleFactorError:
        # the stream berr guard withheld a result that left the
        # accuracy class — a TYPED refusal, never a silent bad answer
        return "stale_rejected", None
    except ServeError:
        return "serve_error", None
    except Exception:
        return "error", None
    if not np.all(np.isfinite(x)):
        return "nonfinite", None
    if isinstance(x, DegradedResult):
        return "degraded", x
    return "ok", x


def _status_of_grad(do_grad) -> tuple[str, object]:
    """One grad_solve through the SAME taxonomy, statuses prefixed
    `grad_` so the report separates the adjoint lane from the solve
    mix.  The finite probe covers the primal and BOTH cotangents — a
    NaN that only reaches ct_vals must not read `grad_ok`."""
    box: dict = {}

    def run():
        box["res"] = do_grad()
        # placate the solve probe's ndarray checks — the GradResult's
        # own three-leg finite probe runs below
        return np.zeros(1)

    status, _ = _status_of_solve(run)
    if status != "ok":
        return "grad_" + status, None
    res = box["res"]
    for leg in (res.x, res.ct_b, res.ct_vals):
        if not np.all(np.isfinite(np.asarray(leg))):
            return "grad_nonfinite", None
    return "grad_ok", res


def _status_of_batch(do_factor) -> tuple[str, object]:
    """One cold same-pattern factor request (the coalescer lane)
    through a `batch_`-prefixed status taxonomy.  The key property is
    PER-INDEX typing: `batch_member_refused` is the member's OWN
    refusal — singular values at factor time (ZeroDivisionError from
    the batch fan-out or the solo path), a plan-time values refusal
    (ValueError: empty/zero row), or a numerics-layer refusal — and
    never bleeds onto siblings, which keep reading `batch_ok`."""
    from ..numerics.errors import NumericalError
    try:
        key = do_factor()
    except (ZeroDivisionError, NumericalError, ValueError):
        return "batch_member_refused", None
    except TenantThrottled:
        return "batch_shed", None
    except ServeRejected:
        return "batch_rejected", None
    except DeadlineExceeded:
        return "batch_deadline", None
    except FactorPoisoned:
        return "batch_poisoned", None
    except FlusherDead:
        return "batch_flusher_dead", None
    except ServeError:
        return "batch_serve_error", None
    except Exception:
        return "batch_error", None
    return "batch_ok", key


def run_stream_load(streams, *, steps: int = 16,
                    step_hz: float = 4.0,
                    requests: int = 128, concurrency: int = 8,
                    hot_fraction: float = 1.0,
                    deadline_s: float | None = None,
                    seed: int = 0,
                    rate_hz: float | None = None,
                    indices=None,
                    journal_path: str | None = None,
                    join_timeout_s: float | None = None) -> dict:
    """Transient-simulation load: correlated keys with per-step value
    drift (the ISSUE-13 scenario).  `streams` is a list of
    `(StreamHandle, step_fn)` pairs — `step_fn(t) -> CSRMatrix`
    produces step t's drifted values for that stream (t=0 is the
    primed state; the stepper starts at t=1).  Index 0 is the hot
    stream (`hot_fraction` skew, like run_load).

    A stepper thread advances every stream at `step_hz`; meanwhile
    `concurrency` closed-loop workers issue blocking solves against
    the streams' LIVE values.  Request identity is DETERMINISTIC:
    worker threads drain a shared index list (`indices`, default
    range(requests)) and derive each request's stream pick and RHS
    from (seed, index) alone — so a killed process's surviving
    journal (`journal_path`, one flushed JSON line per completed
    request) tells a successor EXACTLY which indices to replay.
    That replay contract is what lets a drill account every
    request across a mid-run kill -9.

    `rate_hz` paces aggregate issuance (open-ish loop): request
    number p is released at `t_start + p / rate_hz`, so the load
    SPANS the drift window instead of draining before the first step
    lands — without it a fast solve path finishes the whole request
    list while every value set is still fresh and the drill measures
    nothing.  Pacing is by drain position, not index, so a restart
    replaying a sparse index list does not idle through the victim's
    completed slots.

    The report is run_load-shaped (by_status / percentiles /
    unresolved) plus the stream-side story: swaps, fresh/stale solve
    counts, guard breaches, and each stream's status() snapshot."""
    import collections
    import itertools
    import json

    streams = list(streams)
    idx_queue = collections.deque(int(i) for i in
                                  (indices if indices is not None
                                   else range(requests)))
    total = len(idx_queue)
    n_workers = max(1, min(concurrency, total))
    results: list[tuple[int, float, str, object]] = []
    res_lock = threading.Lock()
    stop_stepping = threading.Event()
    journal = None
    if journal_path:
        import os
        journal = open(journal_path, "a")
        # a SIGKILLed predecessor (the kill drill's victim) can leave
        # a TORN final line with no trailing newline; heal it so this
        # process's first record doesn't concatenate onto the
        # fragment (readers skip the fragment as unparseable and the
        # index replays — accounting stays exact)
        if os.path.getsize(journal_path) > 0:
            with open(journal_path, "rb") as jf:
                jf.seek(-1, os.SEEK_END)
                if jf.read(1) != b"\n":
                    journal.write("\n")
                    journal.flush()

    dims = [h.swap.current.a.n for h, _ in streams]
    svc = streams[0][0].service
    m = svc.metrics
    # the stream.* counters are service-lifetime totals shared by
    # every run on this service; the report's figures are THIS run's
    # deltas so interleaved A/B arms don't inherit each other's
    # (and the warmup pair's) solves
    _CTRS = ("stream.refactors", "stream.refactor_failures",
             "stream.fresh_solves", "stream.stale_solves",
             "stream.guard_breaches", "stream.worker_died",
             "stream.worker_restarts")
    ctr0 = {c: m.counter(c) for c in _CTRS}

    def stepper() -> None:
        for t in range(1, steps + 1):
            if stop_stepping.wait(1.0 / step_hz if step_hz > 0
                                  else 0.0):
                return
            for h, step_fn in streams:
                try:
                    h.update(step_fn(t))
                except ServeError:
                    return          # stream closed under us: done
        stop_stepping.set()

    released = itertools.count()

    def worker(wid: int) -> None:
        while True:
            try:
                idx = idx_queue.popleft()
            except IndexError:
                return
            if rate_hz:
                due = t_start + next(released) / rate_hz
                delay = due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
            rng = np.random.default_rng(seed * 7919 + idx)
            if len(streams) == 1 or rng.random() < hot_fraction:
                si = 0
            else:
                si = 1 + int(rng.integers(len(streams) - 1))
            b = rng.standard_normal(dims[si])
            h = streams[si][0]
            info: dict = {}
            t0 = time.monotonic()
            status, _x = _status_of_solve(
                lambda: h.solve(b, deadline_s=deadline_s, info=info))
            lat = time.monotonic() - t0
            with res_lock:
                results.append((idx, lat, status,
                                info.get("request_id")))
                if journal is not None:
                    journal.write(json.dumps(
                        {"i": idx, "status": status,
                         "ms": round(lat * 1e3, 3)}) + "\n")
                    journal.flush()

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(n_workers)]
    step_thread = threading.Thread(target=stepper, daemon=True)
    t_start = time.monotonic()
    step_thread.start()
    for t in threads:
        t.start()
    if join_timeout_s is None:
        for t in threads:
            t.join()
    else:
        join_deadline = t_start + join_timeout_s
        for t in threads:
            t.join(max(0.0, join_deadline - time.monotonic()))
    stop_stepping.set()
    step_thread.join(timeout=10.0)
    wall_s = time.monotonic() - t_start
    # ONE locked snapshot: on the join-timeout path stragglers may
    # still be appending, and computing unresolved / by_status /
    # completed_indices from a mutating list would make the report
    # internally inconsistent (unresolved=1 yet every index listed)
    with res_lock:
        results = list(results)
    if journal is not None:
        # close only if every worker really exited: a join that
        # TIMED OUT leaves workers that may still complete solves,
        # and their journal line (the kill-drill accounting record)
        # must not die on a closed file.  res_lock serializes the
        # check against an in-flight write; a leaked fd on the
        # timeout path closes at process exit.
        with res_lock:
            if not any(t.is_alive() for t in threads):
                journal.close()
    svc.drain_observability()

    by_status: dict[str, int] = {}
    for _i, _lat, s, _rid in results:
        by_status[s] = by_status.get(s, 0) + 1
    from .metrics import nearest_rank
    ok_lat = np.array(sorted(lat for _i, lat, s, _r in results
                             if s == "ok"))
    report = {
        "requests": total,
        "concurrency": n_workers,
        "steps": steps,
        "step_hz": step_hz,
        "hot_fraction": hot_fraction,
        "wall_s": wall_s,
        "by_status": by_status,
        "unresolved": total - len(results),
        "completed_indices": sorted(i for i, *_ in results),
        "solves_per_s": (len(ok_lat) / wall_s) if wall_s > 0 else 0.0,
        "stream": {
            "swaps": sum(h.swap.swaps - 1 for h, _ in streams),
            "refactors": m.counter("stream.refactors")
            - ctr0["stream.refactors"],
            "refactor_failures":
                m.counter("stream.refactor_failures")
                - ctr0["stream.refactor_failures"],
            "fresh_solves": m.counter("stream.fresh_solves")
            - ctr0["stream.fresh_solves"],
            "stale_solves": m.counter("stream.stale_solves")
            - ctr0["stream.stale_solves"],
            "guard_breaches": m.counter("stream.guard_breaches")
            - ctr0["stream.guard_breaches"],
            "worker_deaths": m.counter("stream.worker_died")
            - ctr0["stream.worker_died"],
            "worker_restarts": m.counter("stream.worker_restarts")
            - ctr0["stream.worker_restarts"],
            "handles": [h.status() for h, _ in streams],
        },
        "metrics": m.snapshot(),
    }
    if len(ok_lat):
        def pct(p):
            return nearest_rank(ok_lat, p) * 1e3
        report.update(p50_ms=pct(50), p95_ms=pct(95), p99_ms=pct(99),
                      mean_ms=float(ok_lat.mean()) * 1e3,
                      # raw ok latencies (sorted, ms): the drill
                      # pools these across trials so its overlap
                      # gate reads a real percentile of the steady
                      # state, not each run's worst-sample max
                      ok_ms=[round(x * 1e3, 3) for x in ok_lat])
    return report


def _exemplars(ok_sorted, results, cap: int = 8) -> dict:
    """Request IDs that make a committed record one lookup from its
    flight records (obs/flight.py): the p99 and worst `ok` requests,
    and every non-ok status's rids (bounded).  rids are None when the
    flight recorder is off."""
    out: dict = {"p99": None, "worst": [], "by_status": {}}
    if ok_sorted:
        p99_i = min(len(ok_sorted) - 1,
                    max(0, int(round(0.99 * (len(ok_sorted) - 1)))))
        lat, rid = ok_sorted[p99_i]
        out["p99"] = {"rid": rid, "ms": round(lat * 1e3, 3)}
        out["worst"] = [{"rid": rid, "ms": round(lat * 1e3, 3)}
                        for lat, rid in ok_sorted[-cap:][::-1]]
    # keep the LAST rids per status: the flight ring retains the most
    # recent records, so early failures may already be displaced —
    # exemplars must stay resolvable against the ring
    for lat, s, rid in results:
        if s == "ok":
            continue
        out["by_status"].setdefault(s, []).append(rid)
    for s, rids in out["by_status"].items():
        del rids[:-cap * 2]
    return out
