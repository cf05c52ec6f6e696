"""Benchmark: sparse LU factorization + solve on the real device.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "platform": ..., "device_kind": ..., "device_count": N}

value       = numeric-phase throughput (true unpadded factorization
              flops / wall-clock of the fused device step, steady
              state).  The fused step is the WHOLE pdgssvx numeric
              pipeline in one XLA program: scale + assemble + f32
              factor + trisolve + on-device f64 iterative refinement.
vs_baseline = speedup of that step over scipy.sparse.linalg.splu+solve
              (SuperLU serial CPU, f64) at the same f64 accuracy — the
              same-accuracy time-to-solution comparison the
              mixed-precision design targets (SURVEY.md §2.6
              psgssvx_d2 strategy).

On an accelerator the metric string also reports MFU against the
chip's bf16 headline peak (the PStatPrint GFLOP/s contract,
SRC/util.c:331, plus the utilization frame the reference leaves to
papers).

Matrix: 7-point 3D Laplacian at n = 27 000 (the fill-heavy separator
population of the audikw_1-class baseline config #3; scipy SuperLU
needs ~5 s for its 14 GFLOP factorization, the regime where the MXU
flop advantage shows).  SLU_BENCH_SHAPE=2d switches to the 5-point
family of the reference TEST sweep (TEST/CMakeLists.txt NVAL);
SLU_BENCH_K overrides the grid edge; SLU_BENCH_NRHS covers the
many-RHS solve regime (ldoor nrhs=64 baseline config #5).

SLU_BENCH_SWEEP=1 additionally runs the secondary baseline configs
(nrhs=64 solve regime; n=110k and n=262k 3D problems) and appends one
JSON object per config to BENCH_SWEEP.jsonl next to this file; the
stdout contract stays one line.  The sweep runs in THIS process, one
config after another: a chip belongs to one process at a time, so a
parent that has touched jax cannot hand it to a child.

The default mode measures a device, so it needs one: with no
accelerator it exits 2 and prints no result.  Every record names the
platform and device kind it ran on.  The other modes (--prec,
--solve-sweep, --factor-ab, --gauntlet, --grad, --batch,
--plan-latency, --multichip-serve) are correctness drills that run
where jax puts them and stamp `platform` on their records; a record
stamped `cpu` is a count of work, never a speed.
"""

import contextlib
import json
import os
import re
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))

# bf16 headline peak per chip generation (TFLOP/s) — the MFU
# denominator.  The factor pins full-f32 matmul precision (_hi_prec),
# which the MXU executes as multiple bf16 passes, so MFU-vs-bf16-peak
# understates arithmetic efficiency by that pass count; it is still
# the honest utilization-of-the-chip-you-paid-for number.
_PEAK_TFLOPS = {
    "v4": 275.0, "v5e": 197.0, "v5 lite": 197.0, "v5p": 459.0,
    "v6e": 918.0, "v6 lite": 918.0,
}


def _jax_setup(cpu_devices: int | None = None):
    """The preamble every mode shares: the repo on sys.path, the
    XLA:CPU ISA cap for CPU runs (utils/cache.py), jax imported where
    jax puts it — no probe, no fallback — and the persistent compile
    cache placed by the one helper.  `cpu_devices` provisions a host
    mesh for the CPU rehearsal of a mesh drill.  Returns (jax, dev,
    on_accel)."""
    sys.path.insert(0, _REPO)
    from superlu_dist_tpu.utils.cache import (ensure_portable_cpu_isa,
                                              place_compile_cache)
    cpu_pinned = os.environ.get("JAX_PLATFORMS",
                                "").strip().lower() == "cpu"
    if cpu_pinned:
        os.environ["XLA_FLAGS"] = ensure_portable_cpu_isa(
            os.environ.get("XLA_FLAGS", ""))
    import jax
    if cpu_pinned and cpu_devices:
        from superlu_dist_tpu.utils.compat import set_cpu_devices
        set_cpu_devices(cpu_devices)
    dev = jax.devices()[0]
    place_compile_cache()
    return jax, dev, dev.platform != "cpu"


def _device_stamp(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _config_key(desc: str) -> str:
    """Scipy-baseline cache key: the tau/cap and staged annotations
    describe OUR solver arm, not the problem being solved — every arm
    shares one primed baseline entry."""
    return re.sub(r" tau=[^ ]+| staged| fdt=[^ ]+", "", desc)


def _staged_env_on() -> bool:
    """Mirror ops/batched.staged_enabled's truthy set — a run forced
    staged via any accepted spelling must be DISCLOSED as staged."""
    return os.environ.get("SLU_STAGED", "").strip().lower() \
        in ("1", "true", "on")


def _mfu_invalid(gflops: float, peak_tf: float) -> bool:
    """Plausibility gate: a measured rate above the chip's bf16
    headline peak (MFU > 100%) is a broken measurement — async
    dispatch escaping block_until_ready, a clock glitch — never a
    fast solver.  Gated records are zeroed and stamped MEASUREMENT
    INVALID."""
    return peak_tf > 0 and gflops > peak_tf * 1e3


def _device_peak_tflops(dev) -> float:
    """The bf16 peak of `dev`'s generation.  A device the table does
    not know is an error, not a silent 0.0 — an MFU against no peak
    would read as a measurement."""
    kind = dev.device_kind.lower()
    for k, v in _PEAK_TFLOPS.items():
        if k in kind:
            return v
    raise KeyError(f"no peak FLOP/s for device_kind "
                   f"{dev.device_kind!r}: add it to _PEAK_TFLOPS")


_SCIPY_CACHE_PATH = os.path.join(_REPO, "SCIPY_BASELINE.json")


def _host_fp() -> str:
    # include_isa=False: the scipy baseline never touches XLA, so the
    # --xla_cpu_max_isa cap must not split its cache (a primer run
    # without the cap and a bench run with it are the same machine)
    from superlu_dist_tpu.utils.cache import host_fingerprint
    return "fp-" + host_fingerprint(include_isa=False)


def _scipy_cache_load() -> dict:
    try:
        with open(_SCIPY_CACHE_PATH) as f:
            return json.load(f)
    except Exception:
        return {}


def _scipy_cache_get(desc: str):
    """(t_scipy, ref_relerr) from a prior measurement ON THIS HOST,
    else None.  The scipy baseline needs no accelerator, so chip time
    need not be spent on it — SLU_BENCH_PRIME_SCIPY=1 measures it
    ahead.  Host-fingerprinted: another machine re-measures instead
    of comparing a TPU run against a different host's CPU seconds."""
    rec = _scipy_cache_load().get(desc)
    if rec and rec.get("host") == _host_fp():
        return float(rec["t_scipy"]), float(rec["ref_relerr"])
    return None


def _scipy_cache_put(desc: str, t_scipy: float, ref_relerr: float):
    # flock around the read-modify-write: a primer and a bench
    # self-healing a miss may write concurrently, and a lost update
    # re-measures a 10+-minute baseline.  The lock target is the
    # cache's DIRECTORY fd —
    # stable across the os.replace below (locking the json itself
    # races: replace swaps the inode out from under a waiter), and it
    # leaves no lock file behind (the old `open(path + ".lock", "w")`
    # regenerated a stray SCIPY_BASELINE.json.lock on every write and
    # never unlinked it)
    import fcntl
    lock_fd = os.open(
        os.path.dirname(os.path.abspath(_SCIPY_CACHE_PATH)) or ".",
        os.O_RDONLY)
    try:
        fcntl.flock(lock_fd, fcntl.LOCK_EX)
        try:       # heal the stray the old scheme left in checkouts
            os.unlink(_SCIPY_CACHE_PATH + ".lock")
        except OSError:
            pass
        data = _scipy_cache_load()
        data[desc] = dict(t_scipy=t_scipy, ref_relerr=ref_relerr,
                          host=_host_fp(),
                          ts=time.strftime("%Y-%m-%dT%H:%M:%S"))
        tmp = _SCIPY_CACHE_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, _SCIPY_CACHE_PATH)
    finally:
        os.close(lock_fd)      # releases the flock


def _measure_scipy(a, b, xtrue):
    """The reference arm: scipy SuperLU (serial CPU, f64)."""
    import scipy.sparse.linalg as spla
    acsc = a.to_scipy().tocsc()
    t0 = time.perf_counter()
    lu_ref = spla.splu(acsc)
    x_ref = lu_ref.solve(b)
    t_scipy = time.perf_counter() - t0
    ref_relerr = np.linalg.norm(x_ref - xtrue) / np.linalg.norm(xtrue)
    return t_scipy, ref_relerr


def _prime_scipy():
    """SLU_BENCH_PRIME_SCIPY=1 entry: measure + cache the scipy
    baselines for the primary and sweep-ladder configs, touching no
    device (the n=262k scipy solve alone takes many minutes)."""
    sys.path.insert(0, _REPO)
    from superlu_dist_tpu.utils.testmat import (laplacian_2d,
                                                laplacian_3d,
                                                manufactured_rhs)
    # mirror EXACTLY what a sweep runs (main + its sweep extras):
    # primary (shape/k from env, main's per-shape default k), the
    # many-RHS variant of the primary, then the sweep-ladder ks —
    # which the sweep always runs as the 3D family regardless of the
    # primary's shape
    shape = os.environ.get("SLU_BENCH_SHAPE", "3d")
    k = int(os.environ.get("SLU_BENCH_K",
                           "30" if shape == "3d" else "160"))
    nrhs = int(os.environ.get("SLU_BENCH_NRHS", "1"))
    ladder = [(str(k), nrhs, shape)]
    for nr_extra in (1, 64):  # the sweep's many-RHS config + default
        if nr_extra != nrhs:
            ladder.append((str(k), nr_extra, shape))
    ladder += [(k2.strip(), 1, "3d") for k2 in os.environ.get(
        "SLU_BENCH_SWEEP_KS", "48,64").split(",") if k2.strip()]
    for kk, nr, shp in ladder:
        kk = int(kk)
        if shp == "3d":
            a = laplacian_3d(kk)
            desc = f"3D Laplacian n={kk ** 3}"
        else:
            a = laplacian_2d(kk)
            desc = f"2D Laplacian n={kk ** 2}"
        if nr > 1:
            desc += f" nrhs={nr}"
        if _scipy_cache_get(desc) is not None:
            print(json.dumps({"primed": desc, "cached": True}))
            continue
        xtrue, b = manufactured_rhs(a, nrhs=nr)
        t_scipy, ref_relerr = _measure_scipy(a, b, xtrue)
        _scipy_cache_put(desc, t_scipy, ref_relerr)
        print(json.dumps({"primed": desc,
                          "t_scipy": round(t_scipy, 3)}))
        sys.stdout.flush()


def _run_config(a, desc, nrhs, jnp):
    """Factor+solve one config; returns the result record."""
    from superlu_dist_tpu import Options
    from superlu_dist_tpu.ops.batched import make_fused_solver
    from superlu_dist_tpu.plan.plan import plan_factorization
    from superlu_dist_tpu.utils.testmat import manufactured_rhs

    from superlu_dist_tpu import obs

    xtrue, b = manufactured_rhs(a, nrhs=nrhs)
    if nrhs > 1:
        desc += f" nrhs={nrhs}"

    # --- baseline: scipy SuperLU, cached across runs on one host
    # (see _scipy_cache_get); a cache miss measures and writes back.
    # tau/cap annotations describe OUR solver arm, not the baseline —
    # strip them from the key so A/B arms share one entry ---
    cache_desc = _config_key(desc)
    cached = _scipy_cache_get(cache_desc)
    scipy_cached = cached is not None
    if scipy_cached:
        t_scipy, ref_relerr = cached
    else:
        t_scipy, ref_relerr = _measure_scipy(a, b, xtrue)
        _scipy_cache_put(cache_desc, t_scipy, ref_relerr)

    # --- ours: fused low-precision factor + f64 refine, ONE XLA
    # program.  SLU_BENCH_FACTOR_DTYPE (default float32) selects the
    # factor precision arm: bfloat16 runs the MXU single-pass (vs the
    # 6-pass full-f32 contract) at the cost of ~2-3x more refinement
    # sweeps — which regime wins is a question for the chip ---
    fdt = os.environ.get("SLU_BENCH_FACTOR_DTYPE", "float32")
    # low-precision arms pay in refinement sweeps (bf16 measured ~8
    # vs f32's ~3); headroom over the default cap so a 9th sweep
    # shows up as steps telemetry, not a silent accuracy failure
    opts = (Options(factor_dtype=fdt) if fdt == "float32"
            else Options(factor_dtype=fdt, max_refine_steps=16))
    t0 = time.perf_counter()
    plan = plan_factorization(a, opts, autotune=True)
    t_plan = time.perf_counter() - t0
    step = make_fused_solver(plan, dtype=fdt)
    vals = jnp.asarray(a.data)
    bb = jnp.asarray(b[:, None] if b.ndim == 1 else b)

    t0 = time.perf_counter()
    with obs.span("bench.warmup", cat="bench", args={"n": a.n}):
        x, berr, steps, tiny, nzero = step(vals, bb)   # compile + run
        x.block_until_ready()
    t_warm = time.perf_counter() - t0

    # steady state (SamePattern production loop: new values, same plan)
    best = np.inf
    for i in range(3):
        t0 = time.perf_counter()
        with obs.span("bench.step", cat="bench", args={"iter": i}):
            x, berr, steps, tiny, nzero = step(vals, bb)
            x.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    x = np.asarray(x)
    x = x[:, 0] if xtrue.ndim == 1 else x
    relerr = np.linalg.norm(x - xtrue) / np.linalg.norm(xtrue)
    rec = dict(desc=desc, t_scipy=t_scipy, ref_relerr=ref_relerr,
               t_plan=t_plan, t_warm=t_warm, best=best, relerr=relerr,
               gflops=plan.factor_flops / best / 1e9,
               refine_steps=int(steps), berr=float(berr),
               accuracy_ok=bool(relerr < 1e-9))
    if plan.true_factor_flops and \
            plan.true_factor_flops < plan.factor_flops:
        # executed flops include amalgamation padding (explicit zeros
        # traded for fewer sequential steps); true_gflops is the
        # useful-work rate on the unamalgamated structure — compare
        # THAT across implementations, and `best`/vs_baseline for wall
        rec["true_gflops"] = plan.true_factor_flops / best / 1e9
    if scipy_cached:
        # honesty marker: this record's baseline seconds are a prior
        # same-host measurement, not concurrent with the device run
        rec["scipy_cached"] = True
    return rec


def _prec_ab():
    """`bench.py --prec`: the mixed-precision A/B — fp32 factor +
    df64 (two-float fp32) iterative-refinement residual vs the same
    fp32 factor + native-f64 residual (which TPUs EMULATE).  Same
    plan, same matrix, two compiled programs; the record carries
    per-arm wall/GFLOP/s AND the final berr + refinement steps, so
    the accuracy cost of dropping fp64 from the jitted path is
    measured next to the speed gain, never assumed.  Appends one JSON
    line to SLU_PREC_AB_OUT (default PREC_AB.jsonl); CPU rehearsal
    with JAX_PLATFORMS=cpu measures the arithmetic overhead side
    (df64 is ~10× the f32 flops per residual term — the interesting
    number is how little of the fused step that is)."""
    os.environ.setdefault("SLU_STAGED", "0")
    jax, dev, on_accel = _jax_setup()
    import jax.numpy as jnp
    from superlu_dist_tpu import Options
    from superlu_dist_tpu.ops.batched import make_fused_solver
    from superlu_dist_tpu.plan.plan import plan_factorization
    from superlu_dist_tpu.utils.testmat import (laplacian_3d,
                                                manufactured_rhs)

    k = int(os.environ.get("SLU_BENCH_K", "16"))
    nrhs = int(os.environ.get("SLU_BENCH_NRHS", "1"))
    a = laplacian_3d(k)
    xtrue, b = manufactured_rhs(a, nrhs=nrhs)
    bb = b[:, None] if b.ndim == 1 else b
    opts = Options(factor_dtype="float32")
    plan = plan_factorization(a, opts, autotune=True)

    def arm(residual_mode):
        step = make_fused_solver(plan, dtype="float32",
                                 residual_mode=residual_mode)
        vals = jnp.asarray(a.data)
        t0 = time.perf_counter()
        x, berr, steps, tiny, nzero = step(vals, bb)
        if hasattr(x, "block_until_ready"):
            x.block_until_ready()
        warm = time.perf_counter() - t0
        best = np.inf
        for _ in range(3):
            t0 = time.perf_counter()
            x, berr, steps, tiny, nzero = step(vals, bb)
            if hasattr(x, "block_until_ready"):
                x.block_until_ready()
            best = min(best, time.perf_counter() - t0)
        x = np.asarray(x)
        xs = x[:, 0] if xtrue.ndim == 1 else x
        rel = float(np.linalg.norm(xs - xtrue)
                    / np.linalg.norm(xtrue))
        return {
            "residual_mode": residual_mode,
            "spmv_layout": step.spmv_layout,
            "t_warm": warm, "best": best,
            "gflops": plan.factor_flops / best / 1e9,
            "berr": float(berr), "refine_steps": int(steps),
            "relerr": rel,
        }

    dw = arm("doubleword")
    f64 = arm("fp64")
    rec = {
        "mode": "prec_ab",
        "n": a.n, "k": k, "nrhs": nrhs,
        "factor_dtype": "float32",
        "arms": {"df64_ir": dw, "fp64_ir": f64},
        "berr_ratio_df64_vs_fp64": dw["berr"] / max(f64["berr"],
                                                    1e-300),
        "speedup_df64_vs_fp64": f64["best"] / max(dw["best"], 1e-300),
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    # accuracy gate BEFORE the record is persisted: the df64 arm must
    # land in the df64 class (berr ≤ a few 2^-44) and both arms must
    # reconstruct the manufactured solution — a failed gate stamps
    # the line measurement_invalid (the bench.py MFU-gate convention)
    # and exits 1, and the invalid line is NEVER appended to the
    # tracked JSONL
    ok = (dw["berr"] < 1e-12 and np.isfinite(f64["berr"])
          and dw["relerr"] < 1e-9 and f64["relerr"] < 1e-9)
    if not ok:
        rec["measurement_invalid"] = True
    line = json.dumps(rec)
    print(line)
    if ok:
        out_path = os.environ.get("SLU_PREC_AB_OUT",
                                  os.path.join(_REPO, "PREC_AB.jsonl"))
        with open(out_path, "a") as f:
            f.write(line + "\n")
    else:
        print("# PREC AB ACCURACY FAILURE (record not persisted)",
              file=sys.stderr)
        raise SystemExit(1)


def _solve_sweep():
    """`bench.py --solve-sweep`: the per-nrhs trisolve A/B (ISSUE 9).

    Factors the SLU_SOLVE_K 3D Laplacian once (f32, the serve-tier
    dtype) and times the FACTORED-rung device solve at nrhs 1/8/64
    under each trisolve arm — `legacy` (the historical scatter-add
    level sweep) vs `merged` (the communication-avoiding lsum
    formulation, ops/trisolve.py) — same handle, same moment, same
    box.  One JSON line per (arm, nrhs) appends to
    SOLVE_LATENCY.jsonl with an `arm` field; tools/regress.py gates
    per-arm per-nrhs `per_rhs_ms` ceilings against BASELINES.json.

    Acceptance gate (ISSUE 9): merged must cut per-rhs wall ≥
    SLU_SOLVE_MIN_SPEEDUP (default 2.0) at nrhs=1 and never lose more
    than SLU_SOLVE_WORSE_TOL (default 1.10, timeshared-box noise) at
    nrhs=8/64.  A failed gate stamps every line measurement_invalid,
    persists NOTHING, and exits 1 (the --prec convention)."""
    jax, dev, on_accel = _jax_setup()
    if on_accel:
        from superlu_dist_tpu.utils.platform import (
            apply_accel_amalg_defaults)
        apply_accel_amalg_defaults()

    from superlu_dist_tpu import Options, factorize
    from superlu_dist_tpu.ops import batched
    from superlu_dist_tpu.utils.testmat import laplacian_3d

    k = int(os.environ.get("SLU_SOLVE_K", "20"))
    min_speedup = float(os.environ.get("SLU_SOLVE_MIN_SPEEDUP", "2.0"))
    worse_tol = float(os.environ.get("SLU_SOLVE_WORSE_TOL", "1.10"))
    a = laplacian_3d(k)
    t0 = time.perf_counter()
    lu = factorize(a, Options(factor_dtype="float32"), backend="jax")
    t_factor = time.perf_counter() - t0
    # the arm that produced t_factor_s (legacy|merged|merged+pallas):
    # serve/errors.factor_cost_hint_s filters on it so fleet lease
    # TTLs track the ACTIVE arm's measured cost (ISSUE 12)
    fct_arm = batched.factor_arm(lu.device_lu.schedule, np.float32)
    rng = np.random.default_rng(0)
    bs = {nrhs: rng.standard_normal((a.n, nrhs)).astype(np.float32)
          for nrhs in (1, 8, 64)}

    def run_arm(arm_env):
        os.environ["SLU_TRISOLVE"] = arm_env
        out = {}
        for nrhs, b in bs.items():
            xb = batched.solve_device(lu.device_lu, b)  # compile+run
            best = np.inf
            for _ in range(5):
                t0 = time.perf_counter()
                xb = batched.solve_device(lu.device_lu, b)
                best = min(best, time.perf_counter() - t0)
            out[nrhs] = (best, bool(np.all(np.isfinite(
                np.asarray(xb)))))
        return out

    # interleave arm passes so the box's monotonic drift hits both
    # arms, then keep the per-(arm, nrhs) best across three passes —
    # the flight-ab lesson (the timeshared box swings ~10% run to
    # run; the best-of of interleaved passes estimates each arm's
    # true floor)
    prior = os.environ.get("SLU_TRISOLVE")
    try:
        res = {"legacy": run_arm("legacy"),
               "merged": run_arm("merged")}
        for _ in range(2):
            leg2 = run_arm("legacy")
            mrg2 = run_arm("merged")
            for nrhs in bs:
                res["legacy"][nrhs] = (
                    min(res["legacy"][nrhs][0], leg2[nrhs][0]),
                    res["legacy"][nrhs][1] and leg2[nrhs][1])
                res["merged"][nrhs] = (
                    min(res["merged"][nrhs][0], mrg2[nrhs][0]),
                    res["merged"][nrhs][1] and mrg2[nrhs][1])
    finally:
        if prior is None:
            os.environ.pop("SLU_TRISOLVE", None)
        else:
            os.environ["SLU_TRISOLVE"] = prior

    speedup1 = res["legacy"][1][0] / max(res["merged"][1][0], 1e-12)
    ok = (speedup1 >= min_speedup
          and all(res["merged"][r][0]
                  <= worse_tol * res["legacy"][r][0]
                  for r in (8, 64))
          and all(f for arm in res.values() for _, f in arm.values()))
    # record the merged arm under its effective name so a
    # SLU_TRISOLVE_PALLAS=1 pass lands as arm="merged+pallas" with
    # its own regress ceiling, never overwriting plain-merged
    # history; resolved against the HANDLE (a staged or
    # non-Pallas-capable factorization must not claim the kernel)
    from superlu_dist_tpu.ops.trisolve import active_arm
    os.environ["SLU_TRISOLVE"] = "merged"
    arm_names = {"legacy": "legacy",
                 "merged": active_arm(lu.device_lu)}
    if prior is None:
        os.environ.pop("SLU_TRISOLVE", None)
    else:
        os.environ["SLU_TRISOLVE"] = prior
    lines = []
    for arm, per in res.items():
        for nrhs, (best, finite) in per.items():
            lines.append(dict(
                desc=f"solve-sweep 3D Laplacian n={k ** 3}",
                mode="solve_sweep", arm=arm_names[arm], nrhs=nrhs,
                solve_s=round(best, 5),
                per_rhs_ms=round(best / nrhs * 1e3, 3),
                vs_legacy=round(best / res["legacy"][nrhs][0], 3),
                finite=finite, t_factor_s=round(t_factor, 2),
                factor_arm=fct_arm,
                speedup_nrhs1=round(speedup1, 3),
                platform=dev.platform,
                device_kind=getattr(dev, "device_kind", ""),
                ts=time.strftime("%Y-%m-%dT%H:%M:%S")))
    for rec in lines:
        if not ok:
            rec["measurement_invalid"] = True
        print(json.dumps(rec))
    if ok:
        out_path = os.environ.get(
            "SLU_SOLVE_SWEEP_OUT",
            os.path.join(_REPO, "SOLVE_LATENCY.jsonl"))
        # a variant pass (SLU_TRISOLVE_PALLAS=1) re-runs the legacy
        # arm as its same-moment denominator but must not RE-PERSIST
        # legacy rows — the plain pass already recorded them, and
        # duplicates would double-weight rounds in the regress
        # baseline medians.  Keyed on the ENV flag, not the resolved
        # arm name: a variant pass whose kernel cannot engage
        # (staged handle, no Mosaic dtype) resolves to plain
        # "merged" and must then persist NOTHING — its rows would
        # duplicate plain-merged history under the same check key.
        variant = os.environ.get("SLU_TRISOLVE_PALLAS", "0") == "1"
        if variant and arm_names["merged"] == "merged":
            persist = []
            print("# variant pass resolved to plain merged "
                  "(kernel not engaged); rows not persisted",
                  file=sys.stderr)
        else:
            persist = [r for r in lines
                       if not variant or r["arm"] != "legacy"]
        with open(out_path, "a") as f:
            for rec in persist:
                f.write(json.dumps(rec) + "\n")
    else:
        print(f"# SOLVE SWEEP GATE FAILURE (speedup_nrhs1="
              f"{speedup1:.2f} < {min_speedup} or merged lost at "
              "wide nrhs); records not persisted", file=sys.stderr)
        raise SystemExit(1)


def _factor_ab():
    """`bench.py --factor-ab`: the staged factor-sweep A/B (ISSUE 12,
    the --solve-sweep sibling at the factor phase).

    Plans the SLU_SOLVE_K 3D Laplacian once (f32, the serve-tier
    dtype) and times the STAGED numeric factorization under each
    factor arm — `legacy` (one dispatch per group,
    SLU_FACTOR_MERGE_CELLS=0) vs `merged` (one dispatch per merged
    segment, ops/batched.get_factor_segments) — same plan, same
    moment, same box, SLU_STAGED=1 for both (the merged lever IS the
    staged dispatch chain; the fused one-program lane is identical
    under either arm).  One JSON line per arm appends to
    SOLVE_LATENCY.jsonl with mode="factor_ab" and an `arm` field
    (legacy|merged|merged+pallas — a SLU_TPU_PALLAS=1 pass lands
    under its own name, the --solve-sweep variant convention);
    tools/regress.py gates per-(arm, n) `t_factor_s` ceilings.

    Acceptance gate (ISSUE 12): the plain merged arm must be
    bitwise-identical to legacy (array_equal over every panel — the
    PR 7 bar, checked in-run at f32 and pinned at fp64 by
    tests/test_factor_merge.py; a Pallas-engaged pass gates on
    relative closeness instead — the kernel is equivalent, not
    bit-identical) and at least SLU_FACTOR_MIN_SPEEDUP faster
    (default 1.0 = never-lose; the timeshared CPU box hides dispatch
    wins inside scheduler noise, and the chip number is not
    measured).  A failed gate stamps every line measurement_invalid,
    persists NOTHING, and exits 1."""
    jax, dev, on_accel = _jax_setup()
    if on_accel:
        from superlu_dist_tpu.utils.platform import (
            apply_accel_amalg_defaults)
        apply_accel_amalg_defaults()

    from superlu_dist_tpu import Options
    from superlu_dist_tpu.ops import batched as B
    from superlu_dist_tpu.plan.plan import plan_factorization
    from superlu_dist_tpu.utils.testmat import laplacian_3d

    k = int(os.environ.get("SLU_SOLVE_K", "20"))
    min_speedup = float(os.environ.get("SLU_FACTOR_MIN_SPEEDUP",
                                       "1.0"))
    prior_staged = os.environ.get("SLU_STAGED")
    prior_cells = os.environ.get("SLU_FACTOR_MERGE_CELLS")
    os.environ["SLU_STAGED"] = "1"
    a = laplacian_3d(k)
    print(f"# factor-ab: planning n={a.n} (k={k}) ...",
          file=sys.stderr)
    plan = plan_factorization(a, Options(factor_dtype="float32"))
    vals = plan.scaled_values(a)
    sched = B.get_schedule(plan, 1)

    # the merged arm must actually MERGE regardless of the ambient
    # env: an operator running with SLU_FACTOR_MERGE_CELLS=0 (legacy
    # serving) prices the merged arm they are missing, not a second
    # legacy pass mislabeled "merged".  A nonzero ambient bound is an
    # operator tuning choice and is respected.
    merged_cells = (prior_cells
                    if prior_cells not in (None, "", "0")
                    else str(B.FACTOR_MERGE_CELLS_DEFAULT))

    def set_arm(arm):
        os.environ["SLU_FACTOR_MERGE_CELLS"] = (
            "0" if arm == "legacy" else merged_cells)

    def one(arm):
        set_arm(arm)
        t0 = time.perf_counter()
        lu = B.factorize_device(plan, vals, np.float32)
        return time.perf_counter() - t0, lu

    try:
        # warm both arms (compile), keep the handles for the bitwise
        # check, then interleave timed passes and keep the per-arm
        # best — the --solve-sweep discipline against the box's
        # monotonic drift
        _, lu_leg = one("legacy")
        _, lu_m = one("merged")
        # arm name + segmentation are env-dependent: resolve them
        # HERE, while the merged arm's env is in force, not after the
        # finally block restores the ambient (possibly legacy) value
        merged_name = B.factor_arm(sched, np.float32)
        segs = B.get_factor_segments(sched)
        best = {"legacy": np.inf, "merged": np.inf}
        for _ in range(3):
            for arm in ("legacy", "merged"):
                t, lu = one(arm)
                best[arm] = min(best[arm], t)
                del lu
    finally:
        for name, old in (("SLU_STAGED", prior_staged),
                          ("SLU_FACTOR_MERGE_CELLS", prior_cells)):
            if old is None:
                os.environ.pop(name, None)
            else:
                os.environ[name] = old

    # accuracy gate: the PLAIN merged arm must be BITWISE-identical to
    # legacy (the PR 7 bar — same bodies, same order, dispatch
    # granularity only).  When the Pallas panel-LU engages for some
    # segment member (merged_name != "merged": TPU auto-promotion or
    # SLU_TPU_PALLAS=1) the kernel's algebraically-equivalent block
    # formulation is NOT bit-identical to the XLA path (PALLAS_AB:
    # both at true-f32 accuracy vs the f64 truth), so that arm gates
    # on relative closeness instead — demanding bitwise there would
    # fail every hardware round by construction.
    pallas_engaged = merged_name != "merged"
    finite = all(bool(np.all(np.isfinite(np.asarray(x))))
                 for p in lu_m.panels for x in p)

    def rel_close(tol=1e-4):
        for p, q in zip(lu_leg.panels, lu_m.panels):
            for x, y in zip(p, q):
                x, y = np.asarray(x), np.asarray(y)
                scale = max(float(np.abs(x).max(initial=0.0)), 1.0)
                if float(np.abs(x - y).max(initial=0.0)) > tol * scale:
                    return False
        return True

    if pallas_engaged:
        bitwise = None
        acc_ok = len(lu_leg.panels) == len(lu_m.panels) and rel_close()
    else:
        bitwise = (len(lu_leg.panels) == len(lu_m.panels) and all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for p, q in zip(lu_leg.panels, lu_m.panels)
            for x, y in zip(p, q)))
        acc_ok = bitwise
    speedup = best["legacy"] / max(best["merged"], 1e-12)
    ok = acc_ok and finite and speedup >= min_speedup

    arm_names = {"legacy": "legacy", "merged": merged_name}
    lines = []
    for arm in ("legacy", "merged"):
        rec = dict(
            desc=f"factor-ab 3D Laplacian n={k ** 3}",
            mode="factor_ab", arm=arm_names[arm], n=k ** 3,
            t_factor_s=round(best[arm], 3),
            vs_legacy=round(best[arm] / best["legacy"], 3),
            speedup=round(speedup, 3),
            finite=finite, groups=len(sched.groups),
            segments=len(segs),
            platform=dev.platform,
            device_kind=getattr(dev, "device_kind", ""),
            ts=time.strftime("%Y-%m-%dT%H:%M:%S"))
        if pallas_engaged:
            rec["allclose"] = acc_ok
        else:
            rec["bitwise_equal"] = bitwise
        lines.append(rec)
    for rec in lines:
        if not ok:
            rec["measurement_invalid"] = True
        print(json.dumps(rec))
    if not ok:
        print(f"# FACTOR A/B GATE FAILURE (accuracy_ok={acc_ok} "
              f"bitwise={bitwise} speedup={speedup:.3f} < "
              f"{min_speedup}); records not persisted",
              file=sys.stderr)
        raise SystemExit(1)
    out_path = os.environ.get(
        "SLU_SOLVE_SWEEP_OUT",
        os.path.join(_REPO, "SOLVE_LATENCY.jsonl"))
    # variant persisting (the --solve-sweep convention): a
    # SLU_TPU_PALLAS=1 pass re-times legacy as its same-moment
    # denominator but persists only its own arm's rows, and persists
    # NOTHING when the kernel did not actually engage (the merged arm
    # then resolved to plain "merged" and would duplicate history)
    variant = os.environ.get("SLU_TPU_PALLAS", "0") == "1"
    if variant and merged_name == "merged":
        persist = []
        print("# variant pass resolved to plain merged (panel-LU "
              "kernel not engaged); rows not persisted",
              file=sys.stderr)
    else:
        persist = [r for r in lines
                   if not variant or r["arm"] != "legacy"]
    with open(out_path, "a") as f:
        for rec in persist:
            f.write(json.dumps(rec) + "\n")


def _gauntlet():
    """Hard-matrix gauntlet drill (ISSUE 15): run the numerics/
    corpus (kappa ladder to 1/eps, structural/numeric singularity,
    wild scaling, NaN/Inf poisoning, malformed shapes) through the
    one-call driver with the condition policy ON, and gate on ZERO
    silent-wrong answers and ZERO untyped failures.  Per-case lines +
    one mode="gauntlet" summary append to SLU_GAUNTLET_OUT
    (GAUNTLET.jsonl, regress-gated by tools/regress.py).  A failed
    gate stamps every line measurement_invalid, persists NOTHING, and
    exits 1 — the --factor-ab discipline."""
    # the drill runs with the whole defense in force: eager rcond
    # estimation + the (default) stamp policy.  An operator override
    # in the ambient env is respected — refuse mode must also gate.
    os.environ.setdefault("SLU_COND_ESTIMATE", "1")
    jax, dev, _ = _jax_setup()

    from superlu_dist_tpu.numerics.gauntlet import run_gauntlet
    print("# gauntlet: running the hard-matrix corpus ...",
          file=sys.stderr)
    t0 = time.perf_counter()
    records, summary = run_gauntlet()
    wall = time.perf_counter() - t0

    ts = time.strftime("%Y-%m-%dT%H:%M:%S")
    lines = []
    for r in records:
        rec = dict(r)
        rec.update(mode="gauntlet_case", platform=dev.platform,
                   ts=ts)
        lines.append(rec)
    lines.append(dict(
        mode="gauntlet", platform=dev.platform,
        device_kind=getattr(dev, "device_kind", ""),
        cases=summary["cases"], counts=summary["counts"],
        gate=summary["gate"], wall_s=round(wall, 3),
        cond_policy=os.environ.get("SLU_COND_POLICY", "stamp"),
        ts=ts))
    ok = summary["gate"]["passed"]
    for rec in lines:
        if not ok:
            rec["measurement_invalid"] = True
        print(json.dumps(rec))
    if not ok:
        print(f"# GAUNTLET GATE FAILURE (silent_wrong="
              f"{summary['gate']['silent_wrong']} untyped="
              f"{summary['gate']['untyped']}); records not persisted",
              file=sys.stderr)
        raise SystemExit(1)
    out_path = os.environ.get(
        "SLU_GAUNTLET_OUT", os.path.join(_REPO, "GAUNTLET.jsonl"))
    with open(out_path, "a") as f:
        for rec in lines:
            f.write(json.dumps(rec) + "\n")


def _grad():
    """`bench.py --grad`: the differentiable-solve gate (ISSUE 18).

    Factorizes one laplacian_3d(SLU_GRAD_K) at f64 on the jax
    backend, then gates on:

      * FD oracle — d/db and d/dA of a weighted-sum loss vs central
        differences at fp64 (rtol 1e-6 spot-check);
      * factorizations == 0 — jax.grad rides the RESIDENT factors;
      * zero recompiles — a second same-signature grad call misses
        no compile (obs.COMPILE_WATCH, phases grad_fwd/adjoint);
      * adjoint cost — median-of-SLU_GRAD_TRIALS adjoint-leg wall
        within SLU_GRAD_RATIO_MAX of the forward leg on the SAME
        handle.

    One mode="grad" line appends to SLU_GRAD_OUT (GRAD.jsonl,
    regress-gated by tools/regress.py).  A failed gate stamps the
    line measurement_invalid, persists NOTHING, and exits 1 — the
    --factor-ab discipline."""
    jax, dev, _ = _jax_setup()
    import jax.numpy as jnp

    from superlu_dist_tpu import (Options, factorize, obs,
                                  sparse_solve)
    from superlu_dist_tpu.autodiff import grad_context
    from superlu_dist_tpu.options import Trans
    from superlu_dist_tpu.utils.testmat import laplacian_3d

    k = int(os.environ.get("SLU_GRAD_K", "10"))
    trials = max(1, int(os.environ.get("SLU_GRAD_TRIALS", "5")))
    ratio_max = float(os.environ.get("SLU_GRAD_RATIO_MAX", "1.5"))

    a = laplacian_3d(k)
    print(f"# grad: factorizing laplacian_3d({k}) n={a.n} ...",
          file=sys.stderr)
    lu = factorize(a, Options(factor_dtype="float64"), backend="jax")
    rng = np.random.default_rng(0)
    b = rng.standard_normal(a.n)
    bj = jnp.asarray(b)
    vals = jnp.asarray(a.data)
    w = jnp.asarray(rng.standard_normal(a.n))

    def loss(v, bb):
        return (w * sparse_solve(v, bb, lu)).sum()

    fact_before = obs.HEALTH.factorizations
    gv, gb = jax.grad(loss, argnums=(0, 1))(vals, bj)
    jax.block_until_ready((gv, gb))
    factorizations = obs.HEALTH.factorizations - fact_before

    # FD oracle spot-check (central differences at fp64)
    eps = 1e-6
    fd_worst = 0.0
    for i in (0, a.n // 2):
        bp = b.copy(); bp[i] += eps
        bm = b.copy(); bm[i] -= eps
        fd = (float(loss(vals, jnp.asarray(bp)))
              - float(loss(vals, jnp.asarray(bm)))) / (2 * eps)
        fd_worst = max(fd_worst,
                       abs(float(gb[i]) - fd) / max(1.0, abs(fd)))
    nv = np.asarray(vals)
    for s in (0, len(nv) // 2):
        vp = nv.copy(); vp[s] += eps
        vm = nv.copy(); vm[s] -= eps
        fd = (float(loss(jnp.asarray(vp), bj))
              - float(loss(jnp.asarray(vm), bj))) / (2 * eps)
        fd_worst = max(fd_worst,
                       abs(float(gv[s]) - fd) / max(1.0, abs(fd)))
    fd_ok = fd_worst <= 1e-6

    # recompile pin: the second same-signature grad call above the
    # already-compiled legs must miss nothing
    miss_before = obs.COMPILE_WATCH.misses()
    jax.block_until_ready(
        jax.grad(loss, argnums=(0, 1))(vals, bj))
    recompiles = obs.COMPILE_WATCH.misses() - miss_before

    # per-leg walls on the SAME handle: forward solve leg vs adjoint
    # leg, median of `trials`, warmed above
    ctx = grad_context(lu)
    fwd_leg, adj_leg = ctx.leg_fns(Trans.NOTRANS)
    b2 = bj[:, None]
    x = fwd_leg(ctx.packs, vals, b2)
    xbar = jnp.asarray(w)[:, None]
    jax.block_until_ready(adj_leg(ctx.packs, xbar, x))
    t_fwd, t_adj = [], []
    for _ in range(trials):
        t0 = time.perf_counter()
        jax.block_until_ready(fwd_leg(ctx.packs, vals, b2))
        t_fwd.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(adj_leg(ctx.packs, xbar, x))
        t_adj.append(time.perf_counter() - t0)
    med_fwd = sorted(t_fwd)[len(t_fwd) // 2]
    med_adj = sorted(t_adj)[len(t_adj) // 2]
    ratio = (med_adj / med_fwd) if med_fwd > 0 else float("inf")

    gate = {
        "passed": bool(fd_ok and factorizations == 0
                       and recompiles == 0 and ratio <= ratio_max),
        "fd_ok": bool(fd_ok),
        "factorizations": int(factorizations),
        "recompiles": int(recompiles),
        "ratio_ok": bool(ratio <= ratio_max),
    }
    rec = dict(
        mode="grad", platform=dev.platform,
        device_kind=getattr(dev, "device_kind", ""),
        n=int(a.n), nnz=int(len(nv)), k=k, trials=trials,
        fd_worst_rel=float(fd_worst),
        factorizations=int(factorizations),
        recompiles=int(recompiles),
        forward_ms=round(med_fwd * 1e3, 4),
        adjoint_ms=round(med_adj * 1e3, 4),
        adjoint_over_forward=round(ratio, 4),
        ratio_max=ratio_max, gate=gate,
        refine_steps=int(os.environ.get("SLU_AD_REFINE", "1")),
        ts=time.strftime("%Y-%m-%dT%H:%M:%S"))
    ok = gate["passed"]
    if not ok:
        rec["measurement_invalid"] = True
    print(json.dumps(rec))
    if not ok:
        print(f"# GRAD GATE FAILURE (fd_worst={fd_worst:.3g} "
              f"factorizations={factorizations} "
              f"recompiles={recompiles} ratio={ratio:.3f}); "
              f"record not persisted", file=sys.stderr)
        raise SystemExit(1)
    out_path = os.environ.get(
        "SLU_GRAD_OUT", os.path.join(_REPO, "GRAD.jsonl"))
    with open(out_path, "a") as f:
        f.write(json.dumps(rec) + "\n")


def _batch():
    """`bench.py --batch`: the batched-factorization A/B gate (ISSUE 20).

    For each cell of n in {128 (random unsymmetric, density 0.05),
    512 (laplacian_3d(8))} x k in SLU_BATCH_K (default 64,256): plan
    ONE template per pattern, warm the full B-ladder
    (batch/serving.warmup_batch), then factor+solve k perturbed value
    sets two ways —

      sequential arm:  per_sample_factorize under the SHARED plan +
                       gssvx.solve per member (the per-sample
                       execution the bitwise contract names; NOT an
                       independent factorize(), which would re-
                       equilibrate from the member's values);
      batched arm:     top-rung chunks through batch_factorize +
                       batch_solve.

    Gates (the --factor-ab discipline — a failed gate stamps the line
    measurement_invalid, persists NOTHING, exits 1):

      * bitwise — batched solutions array_equal the sequential arm's
        at fp64, every member, every cell;
      * zero recompiles — COMPILE_WATCH misses on the batch_factor /
        batch_solve phases stay flat through every timed dispatch
        after warmup;
      * throughput — batch/sequential wall ratio at the k=256 / n=128
        cell >= SLU_BATCH_MIN_SPEEDUP (default 1.5).

    One mode="batch" line appends to SLU_BATCH_OUT (BATCH.jsonl,
    regress-gated by tools/regress.py)."""
    import importlib

    jax, _, _ = _jax_setup()

    from superlu_dist_tpu import obs
    from superlu_dist_tpu.batch import (batch_factorize, batch_ladder,
                                        batch_solve, bucket_for_batch,
                                        pad_values, per_sample_factorize,
                                        shared_plan, warmup_batch)
    from superlu_dist_tpu.options import IterRefine, Options
    from superlu_dist_tpu.sparse import CSRMatrix
    from superlu_dist_tpu.utils.stats import Stats
    from superlu_dist_tpu.utils.testmat import (laplacian_3d,
                                                random_unsymmetric)
    gssvx = importlib.import_module("superlu_dist_tpu.models.gssvx")
    dev = jax.devices()[0]

    ks = tuple(int(x) for x in os.environ.get(
        "SLU_BATCH_K", "64,256").split(",") if x.strip())
    min_ratio = float(os.environ.get("SLU_BATCH_MIN_SPEEDUP", "1.5"))
    opts = Options(iter_refine=IterRefine.NOREFINE)
    ladder = batch_ladder()
    top = ladder[-1]

    def member_handle(plan, a, vals_j):
        aj = CSRMatrix(a.m, a.n, a.indptr, a.indices, vals_j)
        lu = gssvx.LUFactorization(
            plan=plan, backend="jax",
            device_lu=per_sample_factorize(plan, vals_j),
            a=aj, stats=Stats())
        lu.options = opts
        return lu

    cells = []
    bitwise_all = True
    recompiles = 0
    for n, mk in ((128, lambda: random_unsymmetric(
            128, density=0.05, seed=1)),
                  (512, lambda: laplacian_3d(8))):
        a = mk()
        plan = shared_plan(a)
        rng = np.random.default_rng(n)
        print(f"# batch: warming ladder {ladder} on n={a.n} ...",
              file=sys.stderr)
        warmup_batch(plan, a.data, ladder=ladder)
        # warm the sequential arm too (its B=1 staged programs and the
        # packed trisolve are separate compiles)
        np.asarray(gssvx.solve(member_handle(plan, a, a.data),
                               np.ones(a.n)))
        for k in ks:
            vals = np.stack([
                a.data * (1.0 + 0.05 * rng.standard_normal(
                    a.data.shape)) for _ in range(k)])
            bb = rng.standard_normal((k, a.n))

            m0f = obs.COMPILE_WATCH.misses("batch_factor")
            m0s = obs.COMPILE_WATCH.misses("batch_solve")

            t0 = time.perf_counter()
            xs_seq = np.empty((k, a.n))
            for j in range(k):
                xs_seq[j] = np.asarray(gssvx.solve(
                    member_handle(plan, a, vals[j]), bb[j]))
            seq_wall = time.perf_counter() - t0

            t0 = time.perf_counter()
            xs_bat = np.empty((k, a.n))
            for s in range(0, k, top):
                chunk = vals[s:s + len(vals[s:s + top])]
                rung = bucket_for_batch(len(chunk), ladder)
                blu = batch_factorize(plan, pad_values(chunk, rung))
                x = np.asarray(batch_solve(
                    blu, pad_values(bb[s:s + len(chunk)], rung)))
                xs_bat[s:s + len(chunk)] = x[:len(chunk)]
            bat_wall = time.perf_counter() - t0

            cell_rec = (obs.COMPILE_WATCH.misses("batch_factor") - m0f
                        + obs.COMPILE_WATCH.misses("batch_solve")
                        - m0s)
            recompiles += cell_rec
            bitwise = bool(np.array_equal(xs_seq, xs_bat))
            bitwise_all = bitwise_all and bitwise
            ratio = (seq_wall / bat_wall) if bat_wall > 0 \
                else float("inf")
            cells.append(dict(
                n=int(a.n), k=int(k), nnz=int(len(a.data)),
                sequential_ms=round(seq_wall * 1e3, 3),
                batch_ms=round(bat_wall * 1e3, 3),
                throughput_ratio=round(ratio, 4),
                bitwise=bitwise, recompiles=int(cell_rec)))
            print(f"# batch: n={a.n} k={k} seq={seq_wall * 1e3:.1f}ms "
                  f"batch={bat_wall * 1e3:.1f}ms ratio={ratio:.2f} "
                  f"bitwise={bitwise} recompiles={cell_rec}",
                  file=sys.stderr)

    # the gated cell: n=128 at the largest requested k (256 by
    # default — the regime where the per-dispatch overhead amortizes)
    gate_cells = [c for c in cells if c["n"] == 128]
    gate_cell = max(gate_cells, key=lambda c: c["k"]) if gate_cells \
        else max(cells, key=lambda c: c["k"])
    gate_ratio = gate_cell["throughput_ratio"]
    gate = {
        "passed": bool(bitwise_all and recompiles == 0
                       and gate_ratio >= min_ratio),
        "bitwise": bool(bitwise_all),
        "recompiles": int(recompiles),
        "ratio_ok": bool(gate_ratio >= min_ratio),
    }
    rec = dict(
        mode="batch", platform=dev.platform,
        device_kind=getattr(dev, "device_kind", ""),
        ladder=list(ladder), ks=list(ks),
        gate_n=int(gate_cell["n"]), gate_k=int(gate_cell["k"]),
        throughput_ratio=float(gate_ratio),
        min_ratio=min_ratio, bitwise=bool(bitwise_all),
        recompiles=int(recompiles), cells=cells, gate=gate,
        solve_mode=os.environ.get("SLU_BATCH_SOLVE_MODE", "scan"),
        ts=time.strftime("%Y-%m-%dT%H:%M:%S"))
    ok = gate["passed"]
    if not ok:
        rec["measurement_invalid"] = True
    print(json.dumps(rec))
    if not ok:
        print(f"# BATCH GATE FAILURE (bitwise={bitwise_all} "
              f"recompiles={recompiles} ratio={gate_ratio:.3f} "
              f"min={min_ratio}); record not persisted",
              file=sys.stderr)
        raise SystemExit(1)
    out_path = os.environ.get(
        "SLU_BATCH_OUT", os.path.join(_REPO, "BATCH.jsonl"))
    with open(out_path, "a") as f:
        f.write(json.dumps(rec) + "\n")


def _plan_latency():
    """`bench.py --plan-latency`: the ROADMAP 5a record (ISSUE 19).

    Times the COLD symbolic pipeline across the standard 3D-Laplacian
    ladder (SLU_PLAN_LATENCY_KS, default 8,12,16,20): plan-build
    (plan_factorization — equilibrate/orderings/symbolic) and
    schedule-build (ops/batched.build_schedule) walls per n, each
    record carrying the pattern sha1, nnz, and the analytic
    plan_bytes_predicted (obs/memory.py) for the n>=1e6 capacity
    story.  One mode="plan_latency" line per n appends to
    SLU_PLAN_LATENCY_OUT (default PLAN_LATENCY.jsonl), gated by
    tools/regress.py (per-(platform, n) wall ceilings).

    Promote discipline (the --factor-ab convention): a non-finite or
    non-positive wall stamps the round measurement_invalid, persists
    NOTHING, and exits 1."""
    jax, dev, _ = _jax_setup()

    from superlu_dist_tpu import Options
    from superlu_dist_tpu.obs.memory import schedule_bytes_predicted
    from superlu_dist_tpu.ops.batched import build_schedule
    from superlu_dist_tpu.plan.plan import (pattern_sha1,
                                            plan_factorization)
    from superlu_dist_tpu.utils.testmat import laplacian_3d

    ks = [int(s) for s in os.environ.get(
        "SLU_PLAN_LATENCY_KS", "8,12,16,20").split(",") if s.strip()]
    opts = Options(factor_dtype="float64")
    out_path = os.environ.get(
        "SLU_PLAN_LATENCY_OUT", os.path.join(_REPO,
                                             "PLAN_LATENCY.jsonl"))

    recs = []
    ok = True
    for k in ks:
        a = laplacian_3d(k)
        t0 = time.perf_counter()
        plan = plan_factorization(a, opts)
        t_plan = time.perf_counter() - t0
        t0 = time.perf_counter()
        sched = build_schedule(plan, ndev=1)
        t_sched = time.perf_counter() - t0
        rec = {
            "mode": "plan_latency", "source": "bench",
            "n": int(a.n), "nnz": int(a.nnz), "k": int(k),
            "pattern_sha1": pattern_sha1(a),
            "t_plan_s": round(t_plan, 6),
            "t_schedule_s": round(t_sched, 6),
            "plan_bytes_predicted": int(
                schedule_bytes_predicted(sched, "float64")),
            "lu_nnz": int(plan.lu_nnz()),
            "platform": dev.platform,
            "device_kind": getattr(dev, "device_kind", ""),
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
        good = (np.isfinite(t_plan) and t_plan > 0
                and np.isfinite(t_sched) and t_sched > 0)
        rec["gate"] = {"passed": bool(good)}
        if not good:
            rec["measurement_invalid"] = True
            ok = False
        recs.append(rec)
        print(json.dumps(rec))
        print(f"# plan-latency n={a.n}: plan {t_plan*1e3:.1f} ms, "
              f"schedule {t_sched*1e3:.1f} ms", file=sys.stderr)
    if not ok:
        print("# PLAN LATENCY GATE FAILURE; records not persisted",
              file=sys.stderr)
        raise SystemExit(1)
    with open(out_path, "a") as f:
        for rec in recs:
            f.write(json.dumps(rec) + "\n")
    if os.environ.get("SLU_REGRESS", "1") != "0":
        from tools import regress
        findings, passed = regress.check_repo(_REPO)
        print(regress.format_findings(findings), file=sys.stderr)
        if not passed:
            raise SystemExit(1)


def _multichip_serve():
    """`bench.py --multichip-serve`: the mesh-resident serving A/B
    (ISSUE 17).

    Provisions a device mesh (the local accelerator complement, or a
    set_cpu_devices(8) host mesh on the CPU rehearsal box), builds TWO
    SolveServices over the SAME key set — one single-device, one
    mesh-resident (ServeConfig.mesh) — and drives the identical
    concurrent load through each arm's micro-batcher bucket ladder:
    same matrices, same moment, same box, SLU_TRISOLVE=merged for both
    (the row-partitioned merged mesh trisolve is the arm under test,
    and the bit-match oracle models exactly that layout).

    The record is ONE JSON object (the MULTICHIP_r* convention) at
    SLU_MULTICHIP_OUT (default MULTICHIP_r06.json): per-arm throughput
    and p99, the recompile pin (obs compile counter + jit cache growth,
    both), the serve-path-vs-mesh_oracle_solve bitwise verdict, and
    measure_comm's per-boundary collective-byte stamps.
    tools/regress.py gates mode="multichip_serve" records (check
    `multichip`): recompiles == 0, bitwise == True, solves/s floor and
    p99 ceiling vs the BASELINES.json median.

    Promote discipline (the --factor-ab convention): a failed gate
    stamps the record measurement_invalid, persists NOTHING, and exits
    1."""
    # the CPU rehearsal box exposes one device; provision a host mesh
    # BEFORE backend init (a no-op when a test-env XLA_FLAGS already
    # provides devices)
    jax, dev, on_accel = _jax_setup(cpu_devices=8)
    if on_accel:
        from superlu_dist_tpu.utils.platform import (
            apply_accel_amalg_defaults)
        apply_accel_amalg_defaults()

    ndev_avail = len(jax.devices())
    if ndev_avail < 2:
        print(json.dumps({"mode": "multichip_serve", "skipped": True,
                          "reason": f"{ndev_avail} device(s): no mesh "
                          "to serve on"}))
        return

    from superlu_dist_tpu import Options, obs
    from superlu_dist_tpu.parallel import factor_dist as fd
    from superlu_dist_tpu.parallel.grid import make_solver_mesh
    from superlu_dist_tpu.serve import (ServeConfig, SolveService,
                                        run_load, solve_jit_cache_size)
    from superlu_dist_tpu.utils.testmat import laplacian_3d

    shape = os.environ.get("SLU_MESH_SHAPE", "").strip()
    dims = ([int(d) for d in shape.lower().split("x")] if shape
            else [ndev_avail])
    dims = (dims + [1, 1])[:3]
    mesh = make_solver_mesh(*dims).mesh
    n_devices = int(np.asarray(mesh.devices).size)
    mesh_shape = "x".join(str(int(mesh.shape[a]))
                          for a in mesh.axis_names)

    k = int(os.environ.get("SLU_SERVE_K", "8"))
    concurrency = int(os.environ.get("SLU_SERVE_CONCURRENCY", "16"))
    requests = int(os.environ.get("SLU_SERVE_REQUESTS", "192"))
    linger_s = float(os.environ.get("SLU_SERVE_LINGER_MS", "2")) / 1e3
    # the SAME key set for both arms: distinct patterns so the load
    # exercises routing + residency, not one resident handle
    mats = [laplacian_3d(k), laplacian_3d(k - 1), laplacian_3d(k + 1)]
    opts = Options(factor_dtype="float64")

    prior_tsv = os.environ.get("SLU_TRISOLVE")
    os.environ["SLU_TRISOLVE"] = "merged"

    def run_arm(mesh_obj):
        svc = SolveService(ServeConfig(
            max_queue_depth=max(64, 4 * requests),
            max_linger_s=linger_s, mesh=mesh_obj))
        t0 = time.perf_counter()
        keys = [svc.prefactor(a, opts) for a in mats]
        warm_s = time.perf_counter() - t0
        lus = [svc.cache.peek(kk) for kk in keys]
        jit_before = [solve_jit_cache_size(lu) for lu in lus]
        misses_before = obs.COMPILE_WATCH.misses()
        report = run_load(svc, keys, requests=requests,
                          concurrency=concurrency, hot_fraction=1.0,
                          seed=0)
        misses_after = obs.COMPILE_WATCH.misses()
        jit_after = [solve_jit_cache_size(lu) for lu in lus]
        growth = (sum(a - b for a, b in zip(jit_after, jit_before))
                  if all(b >= 0 for b in jit_before) else None)
        return svc, keys, lus, {
            "backend": lus[0].backend,
            "warmup_s": round(warm_s, 3),
            "by_status": report["by_status"],
            "solves_per_s": report["solves_per_s"],
            "p50_ms": report.get("p50_ms"),
            "p95_ms": report.get("p95_ms"),
            "p99_ms": report.get("p99_ms"),
            "recompiles_under_load": misses_after - misses_before,
            "jit_cache_growth": growth,
        }

    try:
        print(f"# multichip-serve: one-device arm, {len(mats)} keys "
              f"(k={k}) ...", file=sys.stderr)
        svc1, _, _, arm1 = run_arm(None)
        svc1.close()
        print(f"# multichip-serve: mesh arm ({mesh_shape}, "
              f"{n_devices} devices) ...", file=sys.stderr)
        svcm, keys_m, lus_m, armm = run_arm(mesh)

        # serve-path bitwise pin against the sequential one-device
        # oracle of the mesh layout: the full request path (keyed
        # submit -> batcher -> dist_solve -> unscale) must reproduce
        # mesh_oracle_solve's bits under the plan's row/col
        # transforms.  The pin key serves with refinement OFF — the
        # oracle models the raw trisolve, and refinement sweeps are
        # float-contingent host arithmetic on top of it (the load
        # arms above keep the default refined serving)
        from superlu_dist_tpu.options import IterRefine
        key_pin = svcm.prefactor(mats[0], opts.replace(
            iter_refine=IterRefine.NOREFINE))
        lu0 = svcm.cache.peek(key_pin)
        dlu = lu0.device_lu
        plan = lu0.plan
        rng = np.random.default_rng(7)
        b = rng.standard_normal(mats[0].n)
        x_serve = np.asarray(svcm.solve(key_pin, b))
        bf = np.zeros(mats[0].n, np.float64)
        bf[plan.final_row] = b * plan.row_scale
        xo = fd.mesh_oracle_solve(dlu, bf[:, None])[:, 0]
        x_oracle = xo[plan.final_col] * plan.col_scale
        bitwise = bool(np.array_equal(x_serve, x_oracle))

        # collective inventory AFTER the timed windows (lowering
        # reuses the plan's cached programs, but the compile probes
        # must never sit inside a recompile-pin window)
        comm = fd.measure_comm(dlu, nrhs=1)
        svcm.close()
    finally:
        if prior_tsv is None:
            os.environ.pop("SLU_TRISOLVE", None)
        else:
            os.environ["SLU_TRISOLVE"] = prior_tsv

    ok_status = all(s == "ok" for s in armm["by_status"]) \
        and all(s == "ok" for s in arm1["by_status"])
    gate = {
        "passed": bool(ok_status and bitwise
                       and armm["recompiles_under_load"] == 0
                       and armm["jit_cache_growth"] in (0, None)),
        "all_ok": ok_status,
        "bitwise_vs_mesh_oracle": bitwise,
        "recompiles_under_load": armm["recompiles_under_load"],
        "jit_cache_growth": armm["jit_cache_growth"],
    }
    rec = {
        "mode": "multichip_serve",
        "n_devices": n_devices,
        "mesh_shape": mesh_shape,
        "axis_names": ",".join(str(a) for a in mesh.axis_names),
        "k": k, "keys": len(mats),
        "requests": requests, "concurrency": concurrency,
        "arms": {"one_device": arm1, "mesh": armm},
        # top-level mesh-arm figures: what tools/regress.py floors
        # and ceilings against the BASELINES.json median
        "solves_per_s": armm["solves_per_s"],
        "p99_ms": armm["p99_ms"],
        "mesh_vs_one_device": round(
            armm["solves_per_s"] / max(arm1["solves_per_s"], 1e-12),
            3),
        "recompiles_under_load": armm["recompiles_under_load"],
        "jit_cache_growth": armm["jit_cache_growth"],
        "bitwise_vs_mesh_oracle": bitwise,
        "comm": comm["MESH"],
        "comm_solve": comm["SOLVE"],
        "comm_factor": comm["FACT"],
        "gate": gate,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    if not gate["passed"]:
        rec["measurement_invalid"] = True
    print(json.dumps(rec, indent=1))
    if not gate["passed"]:
        print(f"# MULTICHIP SERVE GATE FAILURE (all_ok={ok_status} "
              f"bitwise={bitwise} recompiles="
              f"{armm['recompiles_under_load']} jit_growth="
              f"{armm['jit_cache_growth']}); record not persisted",
              file=sys.stderr)
        raise SystemExit(1)
    out_path = os.environ.get(
        "SLU_MULTICHIP_OUT", os.path.join(_REPO, "MULTICHIP_r06.json"))
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(rec, f, indent=1)
        f.write("\n")
    os.replace(tmp, out_path)


def main():
    # --trace PATH: export the run's phase spans + compile events as
    # a Chrome trace-event JSON (Perfetto-loadable) alongside the
    # BENCH json line — the observability twin of the metric.
    # Resolved before anything imports the solver so the tracer is on
    # for the whole pipeline (plan phases included).
    argv = sys.argv[1:]
    trace_path = None
    if "--trace" in argv:
        i = argv.index("--trace")
        if i + 1 >= len(argv):
            print("bench: --trace requires a path", file=sys.stderr)
            raise SystemExit(2)
        trace_path = argv[i + 1]
        from superlu_dist_tpu import obs
        obs.configure(enabled=True, trace_path=trace_path)
    if "--cold-boot" in sys.argv[1:]:
        # fresh-process cold-boot drill (ISSUE 12): two child
        # interpreters against one shared store + AOT cache; the
        # second must serve with factorizations==0 and zero AOT
        # misses (no whole-phase re-trace/re-compile); record to
        # SERVE_LATENCY.jsonl, gated by tools/regress.py
        import runpy
        runpy.run_path(
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tools", "serve_bench.py"),
            run_name="__main__")
        return
    if ("--serve" in sys.argv[1:]
            or "--stream" in sys.argv[1:]):
        # serve_bench dispatch: --serve is the serve-mode load
        # benchmark (factor once, concurrent solves through the
        # micro-batching service); --stream the streaming-
        # refactorization drift drill (ISSUE 13: transient-sim load
        # with per-step value drift — overlap A/B plus the mid-swap
        # kill -9 / warm-restart drill).  Both append to
        # SERVE_LATENCY.jsonl, gated by tools/regress.py
        import runpy
        runpy.run_path(
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tools", "serve_bench.py"),
            run_name="__main__")
        return
    if "--fleet" in sys.argv[1:]:
        # fleet drill (tools/fleet_drill.py): >=3 replica processes
        # on one shared store, chaos load, kill -9 mid-load — gates
        # zero lost/hung, warm takeover, exactly-one fleet-wide
        # factorization per cold key; appends to FLEET.jsonl
        import runpy
        runpy.run_path(
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tools", "fleet_drill.py"),
            run_name="__main__")
        return
    if "--prec" in sys.argv[1:]:
        # mixed-precision A/B (ISSUE 5): fp32 factor + df64-pair IR
        # residual vs fp32 factor + native-f64 IR residual, one JSON
        # line to PREC_AB.jsonl
        _prec_ab()
        return
    if "--solve-sweep" in sys.argv[1:]:
        # trisolve A/B (ISSUE 9): per-nrhs FACTORED-rung solve wall,
        # legacy level sweep vs merged lsum trisolve, records with an
        # `arm` field appended to SOLVE_LATENCY.jsonl
        _solve_sweep()
        return
    if "--gauntlet" in sys.argv[1:]:
        # hard-matrix gauntlet (ISSUE 15): numerical defense drill,
        # gate = zero silent-wrong answers + zero untyped failures;
        # appends to GAUNTLET.jsonl, gated by tools/regress.py
        _gauntlet()
        return
    if "--grad" in sys.argv[1:]:
        # differentiable-solve gate (ISSUE 18): FD oracle, zero new
        # factorizations under jax.grad, zero recompiles on the
        # second call, adjoint/forward wall ratio ceiling; appends
        # to GRAD.jsonl, gated by tools/regress.py
        _grad()
        return
    if "--batch" in sys.argv[1:]:
        # batched-factorization A/B (ISSUE 20): one schedule, one
        # warmup, k value sets through batch_factorize/batch_solve vs
        # the shared-plan per-sample arm — bitwise pin, zero-recompile
        # pin, throughput-ratio floor; appends to BATCH.jsonl, gated
        # by tools/regress.py
        _batch()
        return
    if "--plan-latency" in sys.argv[1:]:
        # symbolic-pipeline latency ladder (ROADMAP 5a / ISSUE 19):
        # cold plan-build + schedule-build walls per n, with pattern
        # sha1 and the analytic bytes prediction; appends to
        # PLAN_LATENCY.jsonl, gated by tools/regress.py
        _plan_latency()
        return
    if "--multichip-serve" in sys.argv[1:]:
        # mesh-resident serving A/B (ISSUE 17): one-device vs mesh
        # replica on the same key set — throughput/p99, recompile pin,
        # bitwise-vs-mesh-oracle, per-boundary collective bytes; ONE
        # JSON object to MULTICHIP_r06.json, gated by tools/regress.py
        _multichip_serve()
        return
    if "--factor-ab" in sys.argv[1:]:
        # staged factor-sweep A/B (ISSUE 12): per-group vs
        # level-merged segment dispatch, bitwise-gated, records with
        # mode="factor_ab" + `arm` appended to SOLVE_LATENCY.jsonl
        _factor_ab()
        return
    if os.environ.get("SLU_BENCH_PRIME_SCIPY") == "1":
        # baseline priming touches no device — safe anytime, cheap
        # no-op once every ladder config is cached
        _prime_scipy()
        return
    _default_mode(trace_path)


def _describe(shape: str, k: int, nrhs: int) -> str:
    """The record's `desc`: the problem plus every arm annotation
    that makes two records incomparable."""
    desc = (f"3D Laplacian n={k ** 3}" if shape == "3d"
            else f"2D Laplacian n={k * k}")
    if os.environ.get("SUPERLU_AMALG_TAU_PCT"):
        desc += (f" tau={os.environ['SUPERLU_AMALG_TAU_PCT']}%"
                 f"/cap={os.environ.get('SUPERLU_AMALG_CAP', 'dflt')}")
    if _staged_env_on():
        # staged per-group dispatch: disclosed — the wall includes the
        # per-group dispatch tax
        desc += " staged"
    fdt_arm = os.environ.get("SLU_BENCH_FACTOR_DTYPE", "float32")
    if fdt_arm != "float32":
        desc += f" fdt={fdt_arm}"
    return desc


@contextlib.contextmanager
def _env(**overrides):
    """Set environment variables for one sweep config, then restore."""
    old = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _default_mode(trace_path):
    """One fused factor+solve+refine measurement on the device jax
    chose — single process, no probe, no fallback: with no
    accelerator there is nothing to measure and the run exits 2;
    when the run raises, the process fails with it."""
    # fused one-program execution unless the caller says otherwise:
    # one dispatch, and its compile is one-time + persistently cached
    os.environ.setdefault("SLU_STAGED", "0")
    jax, dev, on_accel = _jax_setup()
    if not on_accel:
        print("bench: the default mode measures an accelerator and jax "
              f"found none (platform {dev.platform!r}); no result. "
              "chip_smoke.py --rehearse-cpu rehearses the main path "
              "on the CPU.", file=sys.stderr)
        raise SystemExit(2)
    import jax.numpy as jnp
    from superlu_dist_tpu.utils.platform import apply_accel_amalg_defaults
    from superlu_dist_tpu.utils.testmat import laplacian_2d, laplacian_3d

    # measured-best amalgamation for accelerator runs (user env wins);
    # the tau/cap annotation in `desc` keeps the record honest about
    # the config it measured
    apply_accel_amalg_defaults()
    stamp = _device_stamp(jax)
    peak_tf = _device_peak_tflops(dev)

    # default: 7-point 3D Laplacian (the fill-heavy separator
    # population of the audikw_1-class baseline config #3) — the
    # regime direct solvers are built for and where the MXU flops
    # dominate; SLU_BENCH_SHAPE=2d reverts to the 5-point family
    # (the reference TEST generator, TEST/CMakeLists.txt NVAL)
    shape = os.environ.get("SLU_BENCH_SHAPE", "3d")
    k = int(os.environ.get("SLU_BENCH_K",
                           "30" if shape == "3d" else "160"))
    nrhs = int(os.environ.get("SLU_BENCH_NRHS", "1"))
    fdt_arm = os.environ.get("SLU_BENCH_FACTOR_DTYPE", "float32")

    def run(shape, k, nrhs):
        a = laplacian_3d(k) if shape == "3d" else laplacian_2d(k)
        return _run_config(a, _describe(shape, k, nrhs), nrhs, jnp)

    r = run(shape, k, nrhs)

    if trace_path is not None:
        from superlu_dist_tpu import obs
        obs.export_trace(trace_path)
        print(f"bench: trace written to {trace_path}",
              file=sys.stderr)

    mfu = r["gflops"] / (peak_tf * 1e3) * 100.0
    mfu_txt = f"; {dev.device_kind} MFU {mfu:.2f}% of bf16 peak"
    mfu_invalid = _mfu_invalid(r["gflops"], peak_tf)
    if mfu_invalid:
        # a rate above the chip's peak is a broken measurement; zero
        # the value so no consumer can headline such a line
        mfu_txt += ("; MEASUREMENT INVALID: implied MFU exceeds "
                    "100% of bf16 peak")
    ok = r["accuracy_ok"] and not mfu_invalid
    true_txt = ""
    if r.get("true_gflops") is not None:
        true_txt = (f"; executed flops incl. amalgamation padding — "
                    f"useful-work rate {r['true_gflops']:.2f} GFLOP/s "
                    "on the unamalgamated structure")
    line = {
        "metric": "fused sparse LU solve throughput "
                  f"({r['desc']}, "
                  f"{'f32' if fdt_arm == 'float32' else fdt_arm} "
                  "factor + f64 device "
                  f"IR; relerr {r['relerr']:.1e} vs scipy "
                  f"{r['ref_relerr']:.1e}; "
                  f"plan {r['t_plan']:.2f}s warmup {r['t_warm']:.1f}s"
                  + mfu_txt + true_txt
                  + ("" if r["accuracy_ok"] else "; ACCURACY CHECK FAILED")
                  + ")",
        "value": round(r["gflops"], 3) if ok else 0.0,
        "unit": "GFLOP/s",
        "vs_baseline": (round(r["t_scipy"] / r["best"], 3)
                        if ok else 0.0),
        **stamp,
    }
    if mfu_invalid:
        line["measurement_invalid"] = True
    print(json.dumps(line))
    sys.stdout.flush()

    if os.environ.get("SLU_BENCH_SWEEP") == "1":
        # secondary configs run AFTER the primary stdout line is out,
        # in this process (the chip is ours; a child could not have
        # it).  Records append as each config lands.  Order is value
        # per minute: many-RHS (reuses the primary's matrix scale),
        # then n=110k, then the n=262k class — which runs STAGED: its
        # monolithic fused compile was killed at 2400 s on the old
        # records, while staged execution compiles bounded per-group
        # programs that land in the persistent cache one by one.
        path = os.environ.get("SLU_BENCH_SWEEP_PATH") or os.path.join(
            _REPO, "BENCH_SWEEP.jsonl")
        staged_min_k = int(os.environ.get("SLU_BENCH_STAGED_MIN_K",
                                          "64"))

        def emit(rec):
            rec = dict(stamp, **rec,
                       ts=time.strftime("%Y-%m-%dT%H:%M:%S"))
            with open(path, "a") as f:
                f.write(json.dumps(rec) + "\n")

        emit(r)
        extras = [(int(k2), 1, "3d") for k2 in os.environ.get(
            "SLU_BENCH_SWEEP_KS", "48,64").split(",") if k2.strip()]
        if nrhs != 64:  # skip if the primary already covered nrhs=64
            extras.insert(0, (k, 64, shape))
        for k2, nr2, shp2 in extras:
            with _env(**({"SLU_STAGED": "1"} if k2 >= staged_min_k
                         else {})):
                emit(run(shp2, k2, nr2))

    if not r["accuracy_ok"]:
        # the JSON line is printed either way, but an accuracy
        # regression must still fail the process for exit-code gates
        raise SystemExit(1)


if __name__ == "__main__":
    main()
