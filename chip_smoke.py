#!/usr/bin/env python3
"""chip_smoke.py — the solver's main path, once, on the chip.

The quickest proof that the system still starts on a TPU: one process
drives `gssvx`, the SamePattern_SameRowPerm / FACTORED reuse rungs and
a `SolveService` through the entry points a user calls, on the 7-point
3D Laplacian at k=30 (n=27,000 — the size of every primary chip
record), f32 factor + f64 refinement, and checks every answer against
the manufactured solution and scipy `splu`.

    python chip_smoke.py                  # one chip
    python chip_smoke.py --mesh 2x2x1     # four chips, one process

It FAILS (exit 2, no result line) when jax's default platform is not
`tpu`, or when the package is not importable.  It exits 1 when any
check fails; a phase that raises is never caught.  The last line of
stdout is one JSON object, `{"ok": true, "device": {...}}`; the lines
before it are one JSON record per phase, also written to `--out`.

`--rehearse-cpu` runs the same code at a tiny size on the CPU for the
test suite (Pallas kernels in interpret mode).  A rehearsal's last
line carries `"rehearsal": true` and never an `"ok"`: it is not a
statement about any device.

Where the compile cache goes: `JAX_COMPILATION_CACHE_DIR` if set,
else the checkout's `.jax_cache-accel`
(superlu_dist_tpu/utils/cache.place_compile_cache).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import statistics
import sys
import time

RELERR_MAX = 1e-9       # the benchmark's accuracy bar


def _parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--k", type=int, default=None,
                   help="grid edge of the 3D Laplacian (default 30; "
                        "6 under --rehearse-cpu)")
    p.add_argument("--seed", type=int, default=0,
                   help="seed of the right-hand sides and of the "
                        "refactorization's values")
    p.add_argument("--mesh", default=None, metavar="RxCxD",
                   help="run gssvx on a process grid over R*C*D TPU "
                        "devices (e.g. 2x2x1) instead of one chip")
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="tiny CPU rehearsal for the tests; never a "
                        "statement about a device")
    p.add_argument("--out", default=None,
                   help="where the full JSON report is written "
                        "(default chiprun_out/chip_smoke[_mesh].json)")
    return p.parse_args(argv)


class CompileCounters:
    """jax.monitoring listeners.  `backend_compiles` counts every
    jit-cache miss that reached the backend (served by the persistent
    cache or not); `cache_hits` / `cache_misses` are the persistent
    cache's own events (a miss is recorded when an entry is written,
    so programs under the 1 s write threshold count as neither)."""

    _EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache":
            "cache_requests",
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self, jax):
        self.n = {"backend_compiles": 0, "cache_requests": 0,
                  "cache_hits": 0, "cache_misses": 0}
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)

    def _event(self, event, **_kw):
        key = self._EVENTS.get(event)
        if key:
            self.n[key] += 1

    def _duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n["backend_compiles"] += 1
            self.compile_s += secs

    def snapshot(self) -> dict:
        return dict(self.n, compile_s=self.compile_s)


class Smoke:
    """One run: phase records, check outcomes, the report."""

    def __init__(self, jax, device: dict, rehearsal: bool):
        from superlu_dist_tpu import obs
        self.jax = jax
        self.obs = obs
        self.device = device
        self.rehearsal = rehearsal
        self.counters = CompileCounters(jax)
        self.records: list[dict] = []
        self.failed: list[str] = []

    @contextlib.contextmanager
    def phase(self, name: str):
        """Times the body (which must leave nothing in flight on the
        device) and attaches what compiled inside it.  The caller adds
        its checks to the yielded record AFTER the block — outside the
        timed bracket — and then calls emit()."""
        watch = self.obs.COMPILE_WATCH
        c0, w0 = self.counters.snapshot(), watch.misses()
        ev0 = len(watch.events())
        rec = {"phase": name}
        t0 = time.perf_counter()
        yield rec
        rec["wall_s"] = time.perf_counter() - t0
        c1 = self.counters.snapshot()
        rec["compile"] = {k: c1[k] - c0[k] for k in c1}
        rec["compile"]["watch_misses"] = watch.misses() - w0
        # operand dtypes of every watched whole-phase program that
        # compiled in this phase, as compile_watch saw them
        progs: dict[str, set] = {}
        for ev in watch.events()[ev0:]:
            progs.setdefault(ev["phase"], set()).update(ev["dtypes"])
        rec["programs_compiled"] = {k: sorted(v)
                                    for k, v in progs.items()}
        rec["peak_bytes"] = self.peak_bytes()

    def peak_bytes(self):
        """Per-device peak_bytes_in_use, or None where the backend
        reports no memory statistics (CPU)."""
        out = []
        for d in self.jax.devices():
            ms = d.memory_stats()
            if not ms:
                return None
            out.append(int(ms.get("peak_bytes_in_use", 0)))
        return out

    def check(self, rec: dict, name: str, ok: bool) -> None:
        rec.setdefault("checks", {})[name] = bool(ok)
        if not ok:
            self.failed.append(f"{rec['phase']}:{name}")

    def emit(self, rec: dict) -> None:
        self.records.append(rec)
        print(json.dumps(rec), flush=True)


def _answer_checks(A, absA, b, x, xtrue, xref, berr_max) -> dict:
    """The accuracy bar, computed on the host in f64, independent of
    the solver's own bookkeeping: componentwise backward error by the
    repo's formula (models/refine.py berr_of), relative error against
    the manufactured solution, agreement with scipy splu."""
    import numpy as np
    x = np.asarray(x)
    out = {"shape_ok": x.shape == xtrue.shape,
           "finite": bool(np.isfinite(x).all()),
           "x_dtype": str(x.dtype)}
    if not (out["shape_ok"] and out["finite"]):
        out["ok"] = False
        return out
    denom = absA @ np.abs(x) + np.abs(b)
    denom[denom == 0.0] = 1.0
    out["berr"] = float(np.max(np.abs(b - A @ x) / denom))
    out["relerr"] = float(np.linalg.norm(x - xtrue)
                          / np.linalg.norm(xtrue))
    out["vs_splu"] = float(np.linalg.norm(x - xref)
                           / np.linalg.norm(xref))
    out["ok"] = (out["berr"] <= berr_max
                 and out["relerr"] < RELERR_MAX
                 and out["vs_splu"] < RELERR_MAX)
    return out


def _block(jax, lu):
    """Wait for a factorization's device arrays (factorize() returns
    while the device is still working)."""
    jax.block_until_ready([v for v in vars(lu.device_lu).values()
                           if isinstance(v, (jax.Array, list, tuple))])


def _pallas_phase(smoke: Smoke, np, jnp) -> None:
    """Compile the Pallas panel-LU kernel through Mosaic (interpret
    mode only under --rehearse-cpu) at three buckets and compare each
    with its XLA oracle.  A kernel the compiler refuses is a failed
    check carrying the compiler's message; the run goes on so one call
    says everything."""
    from superlu_dist_tpu.ops import pallas_lu
    from superlu_dist_tpu.ops.dense_lu import partial_lu_batch
    interpret = smoke.rehearsal

    def lu_case(n, mb, wb):
        rng = np.random.default_rng(2)
        F = rng.standard_normal((n, mb, mb)).astype(np.float32)
        F += mb * np.eye(mb, dtype=np.float32)
        got, tiny, nzero = pallas_lu.partial_lu_batch_pallas(
            jnp.asarray(F), np.float32(1e-30), wb=wb,
            interpret=interpret)
        ref, _, _ = partial_lu_batch(jnp.asarray(F), jnp.float32(1e-30),
                                     wb=wb, pallas=False)
        err = float(np.abs(np.asarray(got) - np.asarray(ref)).max())
        return err, err < 2e-4 * mb and int(tiny) == int(nzero) == 0

    # (n, mb, wb): the column kernel at the bucket merged_eligible
    # turns on by default on a TPU, the column kernel at a mid bucket,
    # the blocked kernel at its smallest aligned panel
    cases = {f"lu_mb{mb}_wb{wb}": functools.partial(lu_case, n, mb, wb)
             for n, mb, wb in ((16, 16, 8), (2, 64, 32), (2, 256, 128))}
    results = {}
    with smoke.phase("pallas_kernels") as rec:
        for name, case in cases.items():
            try:
                err, ok = case()
                results[name] = {"max_err": err, "ok": ok}
            except Exception as e:  # noqa: BLE001 — the compiler's
                # refusal is the finding; recorded, and the run fails
                results[name] = {"ok": False,
                                 "error": f"{type(e).__name__}: {e}"[:3000]}
    rec["interpret"] = interpret
    rec["kernels"] = results
    for name, r in results.items():
        smoke.check(rec, name, r["ok"])
    smoke.emit(rec)


def _mesh_report(jax, lu) -> dict:
    """Where the factor slabs live and which solve programs ran."""
    d = lu.device_lu
    shard = {name: sorted(dev.id for dev in
                          getattr(d, name).sharding.device_set)
             for name in ("L_flat", "U_flat", "Li_flat", "Ui_flat")}
    # dist_solve caches its programs on the plan, keyed
    # (mesh, dtype, axis, trans, rhs_sharded, merged)
    arms = sorted({("rhs_sharded" if k[4] else
                    "merged" if k[5] else "legacy_psum")
                   for k in getattr(lu.plan, "_dist_solve_fns", {})})
    return {"slab_devices": shard, "trisolve_arms": arms,
            "mesh_devices": [
                {"id": dev.id, "coords": getattr(dev, "coords", None)}
                for dev in d.mesh.devices.flat]}


def run(args) -> int:
    try:
        import jax
        import numpy as np
        import scipy.sparse.linalg as spla
        import superlu_dist_tpu as slu
        from superlu_dist_tpu.models.gssvx import _ESC_BERR_SLACK
        from superlu_dist_tpu.ops import batched, trisolve
        from superlu_dist_tpu.utils import native
        from superlu_dist_tpu.utils.cache import place_compile_cache
        from superlu_dist_tpu.utils.testmat import laplacian_3d
    except ImportError as e:
        print(f"chip_smoke: cannot import the program: {e}",
              file=sys.stderr)
        return 2
    import jax.numpy as jnp

    devs = jax.devices()
    device = {"platform": devs[0].platform,
              "kind": devs[0].device_kind, "count": len(devs)}
    if args.rehearse_cpu:
        if device["platform"] != "cpu":
            print("chip_smoke: --rehearse-cpu is for JAX_PLATFORMS=cpu; "
                  f"jax chose {device['platform']}", file=sys.stderr)
            return 2
    elif device["platform"] != "tpu":
        print("chip_smoke: needs a TPU; jax's default platform is "
              f"{device['platform']!r} ({device['kind']}). "
              "No result.", file=sys.stderr)
        return 2
    mesh_dims = None
    if args.mesh:
        mesh_dims = tuple(int(d) for d in args.mesh.lower().split("x"))
        need = int(np.prod(mesh_dims))
        if len(mesh_dims) != 3 or len(devs) < need:
            print(f"chip_smoke: --mesh {args.mesh} needs RxCxD over "
                  f"{need} devices; jax found {len(devs)}",
                  file=sys.stderr)
            return 2

    k = args.k if args.k is not None else (6 if args.rehearse_cpu
                                           else 30)
    berr_max = _ESC_BERR_SLACK * float(np.finfo(np.float64).eps)
    smoke = Smoke(jax, device, args.rehearse_cpu)
    cache_dir = place_compile_cache()
    opts = slu.Options(factor_dtype="float32")
    header = {
        "phase": "setup", "device": device, "rehearsal": args.rehearse_cpu,
        "jax": jax.__version__, "k": k, "n": k ** 3, "seed": args.seed,
        "mesh": args.mesh, "compile_cache_dir": cache_dir,
        "berr_max": berr_max, "relerr_max": RELERR_MAX,
        # a library caller of gssvx: Options() defaults, NOT the
        # tau=400 %/cap=1024 that pddrive applies through
        # utils/platform.apply_accel_amalg_defaults
        "amalgamation": {
            "amalg_tau": opts.amalg_tau, "amalg_cap": opts.amalg_cap,
            "accel_amalg_defaults_applied": False,
            "env": {v: os.environ[v] for v in
                    ("SUPERLU_AMALG_TAU_PCT", "SUPERLU_AMALG_CAP")
                    if v in os.environ}},
    }
    t0 = time.perf_counter()
    header["native_library_loaded"] = bool(native.available())
    header["native_load_s"] = time.perf_counter() - t0
    smoke.check(header, "native_library_loaded",
                header["native_library_loaded"])
    smoke.emit(header)
    if not header["native_library_loaded"]:
        # a Python ordering at n=27,000 turns a 4 s plan into minutes
        return _finish(smoke, args)

    _pallas_phase(smoke, np, jnp)

    # ---- the systems: data from --seed, references outside any
    # timed bracket ----
    rng = np.random.default_rng(args.seed)
    a = laplacian_3d(k)
    n = a.n
    A = a.to_scipy()
    # the refactorization's values: same pattern, every row rescaled
    a2 = dataclasses.replace(
        a, data=a.data * np.repeat(rng.uniform(0.5, 1.5, n),
                                   np.diff(a.indptr)))
    A2 = a2.to_scipy()
    absA, absA2 = abs(A), abs(A2)
    splu1, splu2 = spla.splu(A.tocsc()), spla.splu(A2.tocsc())

    def system(A_, splu_, nrhs):
        xt = rng.standard_normal((n, nrhs) if nrhs > 1 else n)
        b_ = A_ @ xt
        return xt, b_, splu_.solve(b_)

    grid = slu.make_solver_mesh(*mesh_dims) if mesh_dims else None

    # ---- 1. gssvx cold: plan -> factor -> solve -> refine ----
    xt, b, xref = system(A, splu1, 1)
    with smoke.phase("gssvx_cold") as rec:
        x, lu, st = slu.gssvx(opts, a, b, grid=grid)
    rec.update(
        answer=_answer_checks(A, absA, b, x, xt, xref, berr_max),
        backend=lu.backend, stats_berr=st.berr,
        refine_steps=st.refine_steps, escalations=st.escalations,
        phase_walls_s={p: round(t, 4) for p, t in st.utime.items()},
        factor_dtype=str(lu.device_lu.dtype),
        # the operand dtype(s) the sweeps of this solve actually took
        # (Stats.sweeps: the factor's precision, whatever b's is)
        sweep_dtype="+".join(sorted(st.sweeps)),
        refine="host loop (models/refine.py), residual in "
               + lu.effective_options.refine_dtype,
        staged=hasattr(lu.device_lu, "panels"),
        groups=len(lu.device_lu.schedule.groups),
        # which of the paired arms ran: legacy|merged[+pallas] — on a
        # TPU "merged+pallas" means the panel-LU kernel compiled in
        # context (pallas_lu.merged_eligible, staged schedules only)
        factor_arm=(batched.factor_arm(lu.device_lu.schedule,
                                       lu.device_lu.dtype)
                    if hasattr(lu.device_lu, "panels") else "fused"),
        trisolve_arm=("mesh: see mesh_placement" if grid is not None
                      else trisolve.active_arm()),
        lu_nnz=int(st.lu_nnz), held_bytes=slu.query_space(lu)[
            "held_bytes"])
    smoke.check(rec, "answer", rec["answer"]["ok"])
    smoke.check(rec, "no_escalation", st.escalations == 0)
    smoke.emit(rec)

    # ---- 2. SamePattern_SameRowPerm refactorization on the held
    # plan, then FACTORED solves at nrhs 1 and 8 ----
    with smoke.phase("refactor_same_rowperm") as rec:
        lu2 = slu.factorize(a2, opts, plan=lu.plan, grid=grid)
        _block(jax, lu2)
    rec["factor_dtype"] = str(lu2.device_lu.dtype)
    smoke.check(rec, "zero_compile_misses",
                rec["compile"]["watch_misses"] == 0
                and rec["compile"]["backend_compiles"] == 0)
    smoke.emit(rec)

    for nrhs in (1, 8):
        xt, b, xref = system(A2, splu2, nrhs)
        with smoke.phase(f"solve_factored_nrhs{nrhs}_first") as rec:
            x = slu.solve(lu2, b)
        rec["answer"] = _answer_checks(A2, absA2, b, x, xt, xref,
                                       berr_max)
        smoke.check(rec, "answer", rec["answer"]["ok"])
        smoke.emit(rec)
        walls = []
        with smoke.phase(f"solve_factored_nrhs{nrhs}_warm") as rec:
            for _ in range(3):
                t0 = time.perf_counter()
                x = slu.solve(lu2, b)
                walls.append(time.perf_counter() - t0)
        rec["solve_walls_s"] = walls
        rec["solve_median_s"] = statistics.median(walls)
        rec["answer"] = _answer_checks(A2, absA2, b, x, xt, xref,
                                       berr_max)
        smoke.check(rec, "answer", rec["answer"]["ok"])
        smoke.check(rec, "zero_compile_misses",
                    rec["compile"]["watch_misses"] == 0
                    and rec["compile"]["backend_compiles"] == 0)
        smoke.emit(rec)

    if grid is not None:
        rec = {"phase": "mesh_placement", **_mesh_report(jax, lu2),
               "peak_bytes": smoke.peak_bytes()}
        need = int(np.prod(mesh_dims))
        smoke.check(rec, "slabs_cover_mesh", all(
            len(v) == need for v in rec["slab_devices"].values()))
        peaks = rec["peak_bytes"] or [0]
        smoke.check(rec, "every_device_holds_work",
                    args.rehearse_cpu or
                    (min(peaks) > 0 and max(peaks) < 10 * min(peaks)))
        smoke.emit(rec)
        # the served path stays on one chip: SolveService on a mesh is
        # PR 17's mesh-resident replica, a deployment of its own
        return _finish(smoke, args)

    # ---- 3. SolveService: prefactor, sequential and concurrent
    # requests, close ----
    # The default ladder is (1, 8, 16, 32, 64) and prefactor() compiles
    # every width; on this chip each is a 135-165 s compile of an
    # f64-emulated sweep at n=27,000 (PR 23, measured once: 474 s of
    # prefactor with the default ladder), which does not fit the
    # smoke's 1200 s.  The smoke's traffic needs widths 1 and 8.
    from superlu_dist_tpu.serve import ServeConfig, SolveService
    svc = SolveService(ServeConfig(ladder=(1, 8)))
    try:
        with smoke.phase("serve_prefactor") as rec:
            key = svc.prefactor(a, opts)
        rec["ladder"] = list(svc.config.ladder)
        smoke.emit(rec)

        reqs = [system(A, splu1, 1) for _ in range(9)]
        answers = []
        with smoke.phase("serve_requests") as rec:
            t_seq = []
            for xt, b, xref in reqs[:3]:
                t0 = time.perf_counter()
                answers.append(svc.solve(key, b))
                t_seq.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            futs = [svc.submit(key, b) for _, b, _ in reqs[3:]]
            answers += [f.result(timeout=600) for f in futs]
            t_burst = time.perf_counter() - t0
        snap = svc.metrics.snapshot()
        solved = snap["counters"].get("batcher.requests_solved", 0)
        dispatches = snap["histograms"].get(
            "serve.device_solve_s", {}).get("count", 0)
        rec.update(sequential_walls_s=t_seq, burst_wall_s=t_burst,
                   burst_size=len(futs), requests_solved=solved,
                   batches_dispatched=dispatches,
                   cache=svc.cache.stats())
        rec["answers"] = [
            _answer_checks(A, absA, b, x, xt, xref, berr_max)
            for (xt, b, xref), x in zip(reqs, answers)]
        smoke.check(rec, "every_answer",
                    all(r["ok"] for r in rec["answers"]))
        smoke.check(rec, "all_requests_solved", solved == len(reqs))
        smoke.check(rec, "batcher_coalesced", dispatches < solved)
        smoke.check(rec, "zero_compile_misses",
                    rec["compile"]["watch_misses"] == 0
                    and rec["compile"]["backend_compiles"] == 0)
        smoke.emit(rec)
    finally:
        svc.close()
    return _finish(smoke, args)


def _finish(smoke: Smoke, args) -> int:
    ok = not smoke.failed
    total = smoke.counters.snapshot()
    summary = {"phase": "summary", "device": smoke.device,
               "rehearsal": smoke.rehearsal, "failed": smoke.failed,
               "compile_totals": total,
               "peak_bytes": smoke.peak_bytes(),
               "walls_s": {r["phase"]: r["wall_s"]
                           for r in smoke.records if "wall_s" in r}}
    smoke.emit(summary)
    out = args.out or os.path.join(
        "chiprun_out",
        "chip_smoke_mesh.json" if args.mesh else "chip_smoke.json")
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(smoke.records, f, indent=1)
    if smoke.rehearsal:
        # never a pass under a device's name
        print(json.dumps({"rehearsal": True, "checks_passed": ok,
                          "device": smoke.device}), flush=True)
    else:
        print(json.dumps({"ok": ok, "device": smoke.device}),
              flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(run(_parse(sys.argv[1:])))
