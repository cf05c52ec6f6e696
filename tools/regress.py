"""Perf-regression sentinel over the committed measurement history.

Five rounds of records are committed (SERVE_LATENCY.jsonl,
SOLVE_LATENCY.jsonl, PREC_AB.jsonl, CHAOS.jsonl, BENCH_r*.json)
but until this tool nothing turned that history
into a GATE: a perf loss — the silent-regression failure mode the
HPL-exascale pipelining work warns about (PAPERS.md, arxiv
2304.10397) — would land invisibly.  This module maintains a
committed `BASELINES.json` (per-platform: CPU rehearsal and TPU
records interleave in the same files) and fails when the latest
record for any (platform, check) regresses past a configurable
tolerance:

  * serve      — solves/s floor, p95/p99 ceilings, recompiles == 0
  * flight_ab  — flight-recorder overhead within the declared frac
  * export_ab  — telemetry-export overhead within the same frac
                 (serve_bench --export-ab, ISSUE 19)
  * plan.*     — per-(platform, n) cold plan-build + schedule-build
                 wall ceilings (bench.py --plan-latency,
                 PLAN_LATENCY.jsonl — ROADMAP 5a)
  * solve      — per-nrhs per-rhs latency ceilings
  * factor     — per-(arm, n) staged factor-wall ceilings + the
                 bitwise merged==legacy pin (bench.py --factor-ab)
  * cold_boot  — fresh-process drill: factorizations == 0,
                 aot_misses == 0, aot_rejected == 0, gate.passed
                 (serve_bench --cold-boot, the compile-skip contract)
  * prec_ab    — per-arm berr must stay in its accuracy CLASS
                 (ratio-bounded: a berr that grows 100x left its
                 class; absolute drift within a class is noise)
  * chaos      — unresolved == 0, nonfinite == 0, untyped == 0,
                 gate.passed
  * fleet      — lost == 0, hung == 0,
                 fleet_factorizations_per_cold_key == 1,
                 takeover_factorizations == 0, gate.passed
                 (the multi-process drill record, FLEET.jsonl)
  * fleet_day  — the day-in-the-life drill (fleet_drill --day):
                 lost == 0, hung == 0, unaccounted == 0,
                 untyped == 0 (every shed typed),
                 fleet_factorizations_per_cold_key == 1 (policy
                 prefactor rides the lease single-flight),
                 takeover_factorizations == 0, gate.passed
                 (FLEET_DAY.jsonl)
  * stream     — drift drill (serve_bench --stream): lost == 0,
                 hung == 0, unresolved == 0, guard_breaches == 0
                 (no result ever served past the berr guard),
                 swaps >= 1, overlap_ratio <= the declared ceiling
                 (stream p99 within 1.10x of the pinned arm — the
                 background refactor provably overlaps), gate.passed
  * multichip  — mesh-resident serving A/B (bench.py
                 --multichip-serve, MULTICHIP_r*.json): solves/s
                 floor, p99 ceiling, recompiles == 0,
                 bitwise_vs_mesh_oracle == True, gate.passed
  * grad       — differentiable-solve gate (bench.py --grad,
                 GRAD.jsonl): factorizations == 0 under jax.grad
                 (the adjoint rides the resident factors), the
                 adjoint/forward wall ratio within its ceiling,
                 gate.passed (FD oracle + zero-recompile)
  * batch      — batched-factorization A/B (bench.py --batch,
                 BATCH.jsonl): batch/sequential throughput ratio at
                 the gated cell >= the declared floor, bitwise ==
                 True (batched == shared-plan per-sample execution),
                 recompiles == 0 across the B-ladder, gate.passed
  * bench      — GFLOP/s floor

Usage:

    python -m tools.regress             # gate; exit 1 on regression
    python -m tools.regress --json      # machine-readable findings
    python -m tools.regress --update    # re-baseline from history

Baseline-update workflow (DESIGN.md §15): a LEGITIMATE perf change
ships with `--update` in the same commit — the new BASELINES.json is
reviewed next to the code that moved the numbers.  A regression is
the same diff WITHOUT a code story: the gate (serve_bench post-run,
tests/test_regress.py in tier-1) rejects it
before it lands.  Missing-platform records are tolerated (TPU lines
are absent on the CPU box): those checks report `skip`, never fail.

Numeric baselines are seeded as the MEDIAN of the trailing window of
committed records per (platform, check, metric) — robust to the
timeshared rehearsal box's scheduler noise; the gate compares the
LATEST record against median±tolerance.
"""

from __future__ import annotations

import glob
import json
import os
import sys

# trailing records per (platform, check) the baseline median is
# computed over
_WINDOW = 5

DEFAULT_TOLERANCES = {
    # latest throughput may drop to (1 - frac) * baseline before the
    # gate fires.  Generous: the CPU rehearsal box swings same-moment
    # A/Bs ~2x under scheduler noise (SERVE_LATENCY.jsonl history).
    "throughput_drop_frac": 0.5,
    # latest latency may rise to (1 + frac) * baseline
    "latency_rise_frac": 1.0,
    # berr may grow by this RATIO before it "left its class"
    "berr_class_ratio": 100.0,
    "gflops_drop_frac": 0.5,
    # flight-recorder on/off throughput gap (the ISSUE-8 overhead
    # acceptance: within 5% on a same-box same-moment A/B)
    "flight_overhead_frac": 0.05,
    # stream drill: steady-state p99 of the background-refactor arm
    # over the pinned arm (the ISSUE-13 overlap acceptance)
    "stream_overlap_ratio": 1.10,
    # grad gate: adjoint leg wall over forward leg wall on the SAME
    # resident handle (the ISSUE-18 adjoint-cost acceptance)
    "grad_adjoint_ratio": 1.5,
    # batch gate: batched-arm over sequential-arm throughput at the
    # gated k=256/n=128 cell (the ISSUE-20 batching acceptance — an
    # ABSOLUTE floor, not baseline-relative: below it the batch
    # engine stopped paying for itself)
    "batch_min_ratio": 1.5,
}


# --------------------------------------------------------------------
# record ingestion
# --------------------------------------------------------------------

def _read_jsonl(path: str) -> list[dict]:
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue            # corrupt line: not this gate's job
                if isinstance(rec, dict):
                    out.append(rec)
    except OSError:
        pass
    return out


def _bench_records(root: str) -> list[dict]:
    """GFLOP/s records from the BENCH_r*.json driver wrappers (whose
    bench line hides in the `tail` text).  A line names its platform;
    the pre-PR-23 lines carried `cpu_fallback` instead."""
    out = []

    def _adopt(rec, src):
        if not isinstance(rec, dict) or rec.get("value") is None:
            return
        if rec.get("unit") != "GFLOP/s":
            return
        if rec.get("measurement_invalid"):
            return
        platform = rec.get("platform")
        if platform is None and "cpu_fallback" in rec:
            platform = "cpu" if rec["cpu_fallback"] else "tpu"
        if platform is None:
            return          # a line that names no platform is no record
        out.append({"gflops": float(rec["value"]),
                    "platform": platform, "src": src})

    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        try:
            doc = json.load(open(path))
        except (OSError, ValueError):
            continue
        if "value" in doc:
            _adopt(doc, os.path.basename(path))
            continue
        for ln in str(doc.get("tail", "")).splitlines():
            ln = ln.strip()
            if ln.startswith("{") and '"metric"' in ln:
                try:
                    _adopt(json.loads(ln), os.path.basename(path))
                except ValueError:
                    pass
    return out


def gather(root: str) -> dict:
    """history[platform][check] -> list of records, oldest first."""
    hist: dict = {}

    def add(platform, check, rec):
        if not platform:
            return
        hist.setdefault(platform, {}).setdefault(check, []).append(rec)

    for rec in _read_jsonl(os.path.join(root, "SERVE_LATENCY.jsonl")):
        mode = rec.get("mode")
        if mode == "serve":
            add(rec.get("platform"), "serve", rec)
        elif mode == "flight_ab":
            add(rec.get("platform"), "flight_ab", rec)
        elif mode == "cold_boot":
            add(rec.get("platform"), "cold_boot", rec)
        elif mode == "stream":
            add(rec.get("platform"), "stream", rec)
        elif mode == "export_ab":
            add(rec.get("platform"), "export_ab", rec)
    for rec in _read_jsonl(os.path.join(root, "SOLVE_LATENCY.jsonl")):
        if rec.get("mode") == "factor_ab":
            # staged factor A/B records (bench.py --factor-ab): gate
            # per (arm, n) t_factor_s — a merged-arm regression fails
            # independently of the legacy arm's ceiling
            add(rec.get("platform"),
                f"factor.{rec.get('arm')}.n{rec.get('n')}", rec)
            continue
        if rec.get("per_rhs_ms") is not None:
            # trisolve A/B records (bench.py --solve-sweep) carry an
            # `arm` field and gate per (arm, nrhs) — a merged-arm
            # regression fails independently of the legacy arm's
            # ceiling; legacy records keep the historical check name
            arm = rec.get("arm")
            chk = (f"solve.{arm}.nrhs{rec.get('nrhs')}" if arm
                   else f"solve.nrhs{rec.get('nrhs')}")
            add(rec.get("platform"), chk, rec)
    for rec in _read_jsonl(os.path.join(root, "PREC_AB.jsonl")):
        if rec.get("mode") == "prec_ab":
            add(rec.get("platform"), "prec_ab", rec)
    for rec in _read_jsonl(os.path.join(root, "CHAOS.jsonl")):
        if rec.get("mode") == "chaos":
            add(rec.get("platform"), "chaos", rec)
    for rec in _read_jsonl(os.path.join(root, "FLEET.jsonl")):
        if rec.get("mode") == "fleet":
            add(rec.get("platform"), "fleet", rec)
    for rec in _read_jsonl(os.path.join(root, "FLEET_DAY.jsonl")):
        if rec.get("mode") == "fleet_day":
            add(rec.get("platform"), "fleet_day", rec)
    for rec in _read_jsonl(os.path.join(root, "GAUNTLET.jsonl")):
        if rec.get("mode") == "gauntlet":
            add(rec.get("platform"), "gauntlet", rec)
    for rec in _read_jsonl(os.path.join(root, "GRAD.jsonl")):
        if rec.get("mode") == "grad":
            add(rec.get("platform"), "grad", rec)
    for rec in _read_jsonl(os.path.join(root, "BATCH.jsonl")):
        if rec.get("mode") == "batch":
            add(rec.get("platform"), "batch", rec)
    for rec in _read_jsonl(os.path.join(root, "PLAN_LATENCY.jsonl")):
        # only the bench-committed ladder records gate (they carry
        # the schedule wall + platform); plan/-emitted source="plan"
        # lines are raw telemetry, not promoted measurements
        if (rec.get("mode") == "plan_latency"
                and rec.get("source") == "bench"
                and not rec.get("measurement_invalid")):
            add(rec.get("platform"), f"plan.n{rec.get('n')}", rec)
    for path in sorted(glob.glob(os.path.join(root,
                                              "MULTICHIP_r*.json"))):
        # mesh-resident serving A/B records (bench.py
        # --multichip-serve); pre-ISSUE-17 rounds are driver wrappers
        # with no mode field and are not this gate's to judge
        try:
            doc = json.load(open(path))
        except (OSError, ValueError):
            continue
        if (isinstance(doc, dict)
                and doc.get("mode") == "multichip_serve"
                and not doc.get("measurement_invalid")
                and not doc.get("skipped")):
            add(doc.get("platform"), "multichip", doc)
    for rec in _bench_records(root):
        add(rec.get("platform"), "bench", rec)
    return hist


# --------------------------------------------------------------------
# checking
# --------------------------------------------------------------------

def _median(vals):
    vals = sorted(vals)
    n = len(vals)
    if not n:
        return None
    mid = n // 2
    return (vals[mid] if n % 2
            else 0.5 * (vals[mid - 1] + vals[mid]))


def _finding(platform, check, metric, value, baseline, limit, status,
             why=""):
    return {"platform": platform, "check": check, "metric": metric,
            "value": value, "baseline": baseline, "limit": limit,
            "status": status, "why": why}


def _num(rec, key):
    v = rec.get(key)
    return float(v) if isinstance(v, (int, float)) else None


def check(history: dict, baselines: dict) -> list[dict]:
    """Latest record per (platform, check) vs the committed baseline.
    Returns findings; status 'fail' means regression.  A platform or
    check present in baselines but absent from history is 'skip'
    (missing-platform tolerance), and vice versa ('unbaselined' —
    run --update to adopt it)."""
    tol = dict(DEFAULT_TOLERANCES)
    tol.update(baselines.get("tolerances", {}))
    findings: list[dict] = []
    b_platforms = baselines.get("platforms", {})

    def floor_check(p, chk, metric, latest, base, frac):
        if base is None:
            return
        if latest is None:
            findings.append(_finding(p, chk, metric, None, base, None,
                                     "skip", "metric absent"))
            return
        limit = base * (1.0 - frac)
        ok = latest >= limit
        findings.append(_finding(
            p, chk, metric, latest, base, limit,
            "ok" if ok else "fail",
            "" if ok else f"{metric} fell below "
            f"{(1 - frac):.0%} of baseline"))

    def ceil_check(p, chk, metric, latest, base, frac_or_ratio,
                   ratio=False):
        if base is None:
            return
        if latest is None:
            findings.append(_finding(p, chk, metric, None, base, None,
                                     "skip", "metric absent"))
            return
        limit = (base * frac_or_ratio if ratio
                 else base * (1.0 + frac_or_ratio))
        ok = latest <= limit
        findings.append(_finding(
            p, chk, metric, latest, base, limit,
            "ok" if ok else "fail",
            "" if ok else f"{metric} rose past the baseline limit"))

    def zero_check(p, chk, metric, latest, why):
        if latest is None:
            return
        ok = latest == 0
        findings.append(_finding(p, chk, metric, latest, 0, 0,
                                 "ok" if ok else "fail",
                                 "" if ok else why))

    for p, checks in sorted(b_platforms.items()):
        h = history.get(p, {})
        for chk, base in sorted(checks.items()):
            recs = h.get(chk)
            if not recs:
                findings.append(_finding(p, chk, None, None, None,
                                         None, "skip",
                                         "no record on this box"))
                continue
            latest = recs[-1]
            if chk == "serve":
                floor_check(p, chk, "solves_per_s",
                            _num(latest, "solves_per_s"),
                            base.get("solves_per_s"),
                            tol["throughput_drop_frac"])
                for m in ("p95_ms", "p99_ms"):
                    ceil_check(p, chk, m, _num(latest, m),
                               base.get(m), tol["latency_rise_frac"])
                zero_check(p, chk, "recompiles_under_load",
                           _num(latest, "recompiles_under_load"),
                           "jit recompiled under load")
            elif chk == "flight_ab":
                v = _num(latest, "overhead_frac")
                if v is None:
                    findings.append(_finding(
                        p, chk, "overhead_frac", None, None, None,
                        "skip", "metric absent"))
                else:
                    limit = tol["flight_overhead_frac"]
                    ok = v <= limit
                    findings.append(_finding(
                        p, chk, "overhead_frac", v, 0.0, limit,
                        "ok" if ok else "fail",
                        "" if ok else "flight recorder overhead past "
                        "the declared budget"))
            elif chk == "export_ab":
                # same bar as flight_ab: telemetry export must not
                # cost the serving path more than the declared frac
                v = _num(latest, "overhead_frac")
                if v is None:
                    findings.append(_finding(
                        p, chk, "overhead_frac", None, None, None,
                        "skip", "metric absent"))
                else:
                    limit = tol["flight_overhead_frac"]
                    ok = v <= limit
                    findings.append(_finding(
                        p, chk, "overhead_frac", v, 0.0, limit,
                        "ok" if ok else "fail",
                        "" if ok else "telemetry export overhead past "
                        "the declared budget"))
            elif chk.startswith("plan."):
                # symbolic-pipeline walls (ROADMAP 5a): plan-build
                # and schedule-build per n, each ceiling-gated
                for m in ("t_plan_s", "t_schedule_s"):
                    ceil_check(p, chk, m, _num(latest, m),
                               base.get(m), tol["latency_rise_frac"])
            elif chk.startswith("solve."):
                ceil_check(p, chk, "per_rhs_ms",
                           _num(latest, "per_rhs_ms"),
                           base.get("per_rhs_ms"),
                           tol["latency_rise_frac"])
            elif chk.startswith("factor."):
                ceil_check(p, chk, "t_factor_s",
                           _num(latest, "t_factor_s"),
                           base.get("t_factor_s"),
                           tol["latency_rise_frac"])
                v = latest.get("bitwise_equal")
                if v is not None:
                    findings.append(_finding(
                        p, chk, "bitwise_equal", bool(v), True, True,
                        "ok" if v else "fail",
                        "" if v else "merged factor sweep diverged "
                        "from the legacy sweep bitwise"))
            elif chk == "cold_boot":
                zero_check(p, chk, "factorizations",
                           _num(latest, "factorizations"),
                           "the warm-artifact fresh process "
                           "re-factored instead of adopting the "
                           "store entry")
                zero_check(p, chk, "aot_misses",
                           _num(latest, "aot_misses"),
                           "a whole-phase program re-traced instead "
                           "of deserializing from the AOT cache")
                zero_check(p, chk, "aot_rejected",
                           _num(latest, "aot_rejected"),
                           "an AOT entry failed verification on the "
                           "warm boot")
                gate = latest.get("gate", {})
                ok = bool(gate.get("passed", True))
                findings.append(_finding(
                    p, chk, "gate.passed", ok, True, True,
                    "ok" if ok else "fail",
                    "" if ok else "the cold-boot drill gate itself "
                    "failed"))
            elif chk == "prec_ab":
                arms = latest.get("arms", {})
                for arm, b_arm in sorted(base.get("berr", {}).items()):
                    v = arms.get(arm, {}).get("berr")
                    ceil_check(p, chk, f"berr.{arm}",
                               float(v) if v is not None else None,
                               b_arm, tol["berr_class_ratio"],
                               ratio=True)
            elif chk == "chaos":
                zero_check(p, chk, "unresolved",
                           _num(latest, "unresolved"),
                           "a request hung (no status)")
                by = latest.get("by_status", {})
                zero_check(p, chk, "nonfinite",
                           float(by.get("nonfinite", 0)),
                           "a non-finite result was served")
                zero_check(p, chk, "error",
                           float(by.get("error", 0)),
                           "an untyped error escaped the taxonomy")
                gate = latest.get("gate", {})
                ok = bool(gate.get("passed", True))
                findings.append(_finding(
                    p, chk, "gate.passed", ok, True, True,
                    "ok" if ok else "fail",
                    "" if ok else "the chaos gate itself failed"))
            elif chk == "fleet":
                zero_check(p, chk, "lost", _num(latest, "lost"),
                           "a request was lost fleet-wide (no "
                           "replica produced an outcome)")
                zero_check(p, chk, "hung", _num(latest, "hung"),
                           "a drill worker hung")
                zero_check(p, chk, "unaccounted",
                           _num(latest, "unaccounted"),
                           "a drill worker died with requests "
                           "unaccounted for")
                zero_check(p, chk, "takeover_factorizations",
                           _num(latest, "takeover_factorizations"),
                           "a survivor re-factored a published key "
                           "instead of adopting it warm")
                v = _num(latest, "fleet_factorizations_per_cold_key")
                if v is None:
                    findings.append(_finding(
                        p, chk, "fleet_factorizations_per_cold_key",
                        None, 1.0, 1.0, "skip", "metric absent"))
                else:
                    ok = v == 1.0
                    findings.append(_finding(
                        p, chk, "fleet_factorizations_per_cold_key",
                        v, 1.0, 1.0, "ok" if ok else "fail",
                        "" if ok else "a cold key factored more (or "
                        "less) than exactly once across the pool — "
                        "cross-process single-flight broke"))
                gate = latest.get("gate", {})
                ok = bool(gate.get("passed", True))
                findings.append(_finding(
                    p, chk, "gate.passed", ok, True, True,
                    "ok" if ok else "fail",
                    "" if ok else "the fleet drill gate itself "
                    "failed"))
            elif chk == "fleet_day":
                zero_check(p, chk, "lost", _num(latest, "lost"),
                           "a request was lost during the day drill "
                           "(no replica produced an outcome through "
                           "a transition)")
                zero_check(p, chk, "hung", _num(latest, "hung"),
                           "a day-drill worker hung")
                zero_check(p, chk, "unaccounted",
                           _num(latest, "unaccounted"),
                           "a day-drill worker died with requests "
                           "unaccounted for")
                zero_check(p, chk, "takeover_factorizations",
                           _num(latest, "takeover_factorizations"),
                           "a survivor re-factored a published key "
                           "after the kill instead of adopting it "
                           "warm")
                by = latest.get("by_status", {})
                untyped = sum(
                    v for s, v in by.items()
                    if s not in ("ok", "degraded") and s != "lost"
                    and not s[:1].isupper())
                zero_check(p, chk, "untyped", float(untyped),
                           "a day-drill failure escaped the typed "
                           "taxonomy (an unshed, unexplained status)")
                v = _num(latest, "fleet_factorizations_per_cold_key")
                if v is None:
                    findings.append(_finding(
                        p, chk, "fleet_factorizations_per_cold_key",
                        None, 1.0, 1.0, "skip", "metric absent"))
                else:
                    ok = v == 1.0
                    findings.append(_finding(
                        p, chk, "fleet_factorizations_per_cold_key",
                        v, 1.0, 1.0, "ok" if ok else "fail",
                        "" if ok else "across the whole day — "
                        "prefactor, flash crowd, restarts, kill — a "
                        "cold key factored more (or less) than "
                        "exactly once"))
                gate = latest.get("gate", {})
                ok = bool(gate.get("passed", True))
                findings.append(_finding(
                    p, chk, "gate.passed", ok, True, True,
                    "ok" if ok else "fail",
                    "" if ok else "the day-in-the-life gate itself "
                    "failed"))
            elif chk == "stream":
                for m, why in (
                        ("lost", "a drill request was lost across "
                         "the kill -9 + restart (no journal "
                         "outcome)"),
                        ("hung", "a drill worker hung"),
                        ("unresolved", "an overlap-A/B request "
                         "never produced a status"),
                        ("guard_breaches", "a result was served "
                         "past the stream berr guard"),
                        ("stale_rejected", "stale-factor refinement "
                         "left the accuracy class under the drill's "
                         "calibrated drift")):
                    zero_check(p, chk, m, _num(latest, m), why)
                v = _num(latest, "swaps")
                if v is not None:
                    ok = v >= 1
                    findings.append(_finding(
                        p, chk, "swaps", v, 1, 1,
                        "ok" if ok else "fail",
                        "" if ok else "the background pipeline never "
                        "published a resident swap"))
                v = _num(latest, "overlap_ratio")
                if v is None:
                    findings.append(_finding(
                        p, chk, "overlap_ratio", None, None, None,
                        "skip", "metric absent"))
                else:
                    limit = tol["stream_overlap_ratio"]
                    ok = v <= limit
                    findings.append(_finding(
                        p, chk, "overlap_ratio", v, 1.0, limit,
                        "ok" if ok else "fail",
                        "" if ok else "background refactorization "
                        "stole the serving path's p99 (overlap "
                        "broken)"))
                gate = latest.get("gate", {})
                ok = bool(gate.get("passed", True))
                findings.append(_finding(
                    p, chk, "gate.passed", ok, True, True,
                    "ok" if ok else "fail",
                    "" if ok else "the stream drill gate itself "
                    "failed"))
            elif chk == "gauntlet":
                gate = latest.get("gate", {})
                zero_check(p, chk, "silent_wrong",
                           float(gate.get("silent_wrong", 0)),
                           "a hard-matrix case produced a plain "
                           "unstamped result with garbage backward "
                           "error — the silent wrong answer")
                zero_check(p, chk, "untyped",
                           float(gate.get("untyped", 0)),
                           "a gauntlet refusal escaped the typed "
                           "taxonomy")
                ok = bool(gate.get("passed", True))
                findings.append(_finding(
                    p, chk, "gate.passed", ok, True, True,
                    "ok" if ok else "fail",
                    "" if ok else "the hard-matrix gauntlet gate "
                    "itself failed"))
            elif chk == "multichip":
                floor_check(p, chk, "solves_per_s",
                            _num(latest, "solves_per_s"),
                            base.get("solves_per_s"),
                            tol["throughput_drop_frac"])
                ceil_check(p, chk, "p99_ms", _num(latest, "p99_ms"),
                           base.get("p99_ms"),
                           tol["latency_rise_frac"])
                zero_check(p, chk, "recompiles_under_load",
                           _num(latest, "recompiles_under_load"),
                           "the mesh replica's jit recompiled under "
                           "the batcher ladder load")
                v = latest.get("bitwise_vs_mesh_oracle")
                if v is not None:
                    findings.append(_finding(
                        p, chk, "bitwise_vs_mesh_oracle", bool(v),
                        True, True, "ok" if v else "fail",
                        "" if v else "the serve-path mesh solve "
                        "diverged from mesh_oracle_solve bitwise"))
                gate = latest.get("gate", {})
                ok = bool(gate.get("passed", True))
                findings.append(_finding(
                    p, chk, "gate.passed", ok, True, True,
                    "ok" if ok else "fail",
                    "" if ok else "the multichip serve A/B gate "
                    "itself failed"))
            elif chk == "grad":
                zero_check(p, chk, "factorizations",
                           _num(latest, "factorizations"),
                           "jax.grad paid a NEW factorization — the "
                           "adjoint stopped riding the resident "
                           "factors")
                v = _num(latest, "adjoint_over_forward")
                if v is None:
                    findings.append(_finding(
                        p, chk, "adjoint_over_forward", None, None,
                        None, "skip", "metric absent"))
                else:
                    limit = tol["grad_adjoint_ratio"]
                    ok = v <= limit
                    findings.append(_finding(
                        p, chk, "adjoint_over_forward", v, 1.0, limit,
                        "ok" if ok else "fail",
                        "" if ok else "the adjoint leg costs more "
                        "than its declared multiple of the forward "
                        "solve on the same handle"))
                gate = latest.get("gate", {})
                ok = bool(gate.get("passed", True))
                findings.append(_finding(
                    p, chk, "gate.passed", ok, True, True,
                    "ok" if ok else "fail",
                    "" if ok else "the grad gate itself failed (FD "
                    "oracle, recompile, or ratio)"))
            elif chk == "batch":
                v = _num(latest, "throughput_ratio")
                if v is None:
                    findings.append(_finding(
                        p, chk, "throughput_ratio", None, None, None,
                        "skip", "metric absent"))
                else:
                    limit = tol["batch_min_ratio"]
                    ok = v >= limit
                    findings.append(_finding(
                        p, chk, "throughput_ratio", v, limit, limit,
                        "ok" if ok else "fail",
                        "" if ok else "the batched arm stopped "
                        "beating the sequential arm by the declared "
                        "floor at the gated cell"))
                v = latest.get("bitwise")
                if v is not None:
                    findings.append(_finding(
                        p, chk, "bitwise", bool(v), True, True,
                        "ok" if v else "fail",
                        "" if v else "batched factor+solve diverged "
                        "from the shared-plan per-sample execution "
                        "bitwise"))
                zero_check(p, chk, "recompiles",
                           _num(latest, "recompiles"),
                           "a batch program recompiled after the "
                           "B-ladder warmup")
                gate = latest.get("gate", {})
                ok = bool(gate.get("passed", True))
                findings.append(_finding(
                    p, chk, "gate.passed", ok, True, True,
                    "ok" if ok else "fail",
                    "" if ok else "the batch A/B gate itself failed"))
            elif chk == "bench":
                floor_check(p, chk, "gflops",
                            _num(latest, "gflops"),
                            base.get("gflops"),
                            tol["gflops_drop_frac"])
    # history the baselines don't know about (informational only)
    for p, checks in sorted(history.items()):
        for chk in sorted(checks):
            if chk not in b_platforms.get(p, {}):
                findings.append(_finding(p, chk, None, None, None,
                                         None, "unbaselined",
                                         "run --update to adopt"))
    return findings


# --------------------------------------------------------------------
# baseline maintenance
# --------------------------------------------------------------------

def build_baselines(history: dict, tolerances: dict | None = None,
                    ts: str | None = None) -> dict:
    """Seed/refresh baselines from the committed history: per
    (platform, check), the median of the trailing _WINDOW records per
    metric.  Structural zero-gates (recompiles, chaos counters) carry
    no numbers — presence of the check is the declaration."""
    platforms: dict = {}
    for p, checks in sorted(history.items()):
        for chk, recs in sorted(checks.items()):
            win = recs[-_WINDOW:]
            dst = platforms.setdefault(p, {})
            if chk == "serve":
                dst[chk] = {
                    m: _median([v for r in win
                                if (v := _num(r, m)) is not None])
                    for m in ("solves_per_s", "p95_ms", "p99_ms")}
            elif chk == "flight_ab":
                dst[chk] = {}
            elif chk == "export_ab":
                dst[chk] = {}      # the ceiling is a tolerance
            elif chk.startswith("plan."):
                dst[chk] = {
                    m: _median([v for r in win
                                if (v := _num(r, m)) is not None])
                    for m in ("t_plan_s", "t_schedule_s")}
            elif chk.startswith("solve."):
                dst[chk] = {"per_rhs_ms": _median(
                    [v for r in win
                     if (v := _num(r, "per_rhs_ms")) is not None])}
            elif chk.startswith("factor."):
                dst[chk] = {"t_factor_s": _median(
                    [v for r in win
                     if (v := _num(r, "t_factor_s")) is not None])}
            elif chk == "cold_boot":
                dst[chk] = {}          # structural zero-gates only
            elif chk == "prec_ab":
                berr: dict = {}
                for r in win:
                    for arm, d in r.get("arms", {}).items():
                        if d.get("berr") is not None:
                            berr.setdefault(arm, []).append(
                                float(d["berr"]))
                dst[chk] = {"berr": {a: _median(v)
                                     for a, v in sorted(berr.items())}}
            elif chk == "chaos":
                dst[chk] = {}
            elif chk == "fleet":
                dst[chk] = {}          # structural zero-gates only
            elif chk == "fleet_day":
                dst[chk] = {}          # structural zero-gates only
            elif chk == "stream":
                dst[chk] = {}          # structural zero-gates only
            elif chk == "gauntlet":
                dst[chk] = {}          # structural zero-gates only
            elif chk == "grad":
                dst[chk] = {}          # structural gates only: the
                                       # ratio ceiling is a tolerance
            elif chk == "batch":
                dst[chk] = {}          # structural gates only: the
                                       # ratio floor is a tolerance
            elif chk == "multichip":
                dst[chk] = {
                    m: _median([v for r in win
                                if (v := _num(r, m)) is not None])
                    for m in ("solves_per_s", "p99_ms")}
            elif chk == "bench":
                dst[chk] = {"gflops": _median(
                    [v for r in win
                     if (v := _num(r, "gflops")) is not None])}
    return {"version": 1,
            "updated_ts": ts,
            "tolerances": dict(tolerances or DEFAULT_TOLERANCES),
            "platforms": platforms}


# --------------------------------------------------------------------
# driver surface
# --------------------------------------------------------------------

def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def check_repo(root: str | None = None,
               baselines_path: str | None = None) -> tuple[list, bool]:
    """(findings, passed) for the records in `root` — the importable
    gate serve_bench and the tier-1 test call."""
    root = root or repo_root()
    baselines_path = baselines_path or os.path.join(root,
                                                    "BASELINES.json")
    try:
        baselines = json.load(open(baselines_path))
    except OSError:
        return ([_finding(None, None, None, None, None, None, "skip",
                          f"no baselines at {baselines_path}")], True)
    except ValueError as e:
        return ([_finding(None, None, None, None, None, None, "fail",
                          f"corrupt baselines: {e}")], False)
    findings = check(gather(root), baselines)
    passed = not any(f["status"] == "fail" for f in findings)
    return findings, passed


def format_findings(findings) -> str:
    lines = []
    for f in findings:
        if f["status"] == "ok":
            continue
        loc = "/".join(str(x) for x in (f["platform"], f["check"],
                                        f["metric"]) if x)
        lines.append(f"[{f['status'].upper():5s}] {loc}: "
                     f"value={f['value']} baseline={f['baseline']} "
                     f"limit={f['limit']} {f['why']}")
    counts: dict = {}
    for f in findings:
        counts[f["status"]] = counts.get(f["status"], 0) + 1
    lines.append("regress: " + " ".join(
        f"{k}={v}" for k, v in sorted(counts.items())))
    return "\n".join(lines)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    root = repo_root()
    if "--root" in argv:
        i = argv.index("--root")
        root = argv[i + 1]
        del argv[i:i + 2]
    baselines_path = os.path.join(root, "BASELINES.json")
    if "--baselines" in argv:
        i = argv.index("--baselines")
        baselines_path = argv[i + 1]
        del argv[i:i + 2]
    if "--update" in argv:
        import time
        old_tol = None
        try:
            old_tol = json.load(open(baselines_path)).get("tolerances")
        except (OSError, ValueError):
            pass
        base = build_baselines(
            gather(root), tolerances=old_tol,
            ts=time.strftime("%Y-%m-%dT%H:%M:%S"))
        tmp = baselines_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(base, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, baselines_path)
        print(f"regress: baselines rewritten -> {baselines_path} "
              f"({sum(len(v) for v in base['platforms'].values())} "
              f"checks)")
        return 0
    findings, passed = check_repo(root, baselines_path)
    if "--json" in argv:
        print(json.dumps({"passed": passed, "findings": findings},
                         indent=1))
    else:
        print(format_findings(findings))
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
