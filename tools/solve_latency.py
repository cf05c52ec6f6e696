"""Solve-only latency vs nrhs on the FACTORED rung (the ldoor /
config-#5 measurement, VERDICT r4 item 7).

The fused-step bench measures factor+solve; the production many-RHS
regime (reference TEST/pdtest.c -s 64, dlsum mrhs kernels
SRC/pdgstrs_lsum_cuda.cu:1002) is repeated SOLVES against held
factors.  This tool factors once (f32, accelerator amalgamation
defaults) and times the one-dispatch device solve per nrhs, printing
one JSON line per nrhs:

  {"nrhs": N, "solve_s": best, "per_rhs_ms": ..., "platform": ...}

The headline contract: per-RHS cost at nrhs=64 within 2x of the
amortized ideal — the sweep chain is O(#groups) regardless of R, so
wide RHS blocks amortize it and the einsums grow on the MXU's free
axis.  One process; appends to SOLVE_LATENCY.jsonl with the platform
it ran on (a `cpu` line is a rehearsal, never a speed).
"""

import json
import os
import sys
import time

import numpy as np


def main():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from superlu_dist_tpu.utils.cache import (ensure_portable_cpu_isa,
                                              place_compile_cache)
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        os.environ["XLA_FLAGS"] = ensure_portable_cpu_isa(
            os.environ.get("XLA_FLAGS", ""))
    import jax
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    dev = jax.devices()[0]
    on_accel = dev.platform != "cpu"
    place_compile_cache()
    if on_accel:
        from superlu_dist_tpu.utils.platform import (
            apply_accel_amalg_defaults)
        apply_accel_amalg_defaults()

    from superlu_dist_tpu import Options, factorize
    from superlu_dist_tpu.ops import batched
    from superlu_dist_tpu.utils.testmat import laplacian_3d

    k = int(os.environ.get("SLU_SOLVE_K", "30"))
    a = laplacian_3d(k)
    t0 = time.perf_counter()
    lu = factorize(a, Options(factor_dtype="float32"), backend="jax")
    t_factor = time.perf_counter() - t0
    rng = np.random.default_rng(0)
    base = None
    for nrhs in (1, 8, 64):
        b = rng.standard_normal((a.n, nrhs)).astype(np.float32)
        xb = batched.solve_device(lu.device_lu, b)      # compile+run
        best = np.inf
        for _ in range(3):
            t0 = time.perf_counter()
            xb = batched.solve_device(lu.device_lu, b)
            best = min(best, time.perf_counter() - t0)
        per_rhs_ms = best / nrhs * 1e3
        if base is None:
            base = best                                 # nrhs=1 cost
        rec = dict(desc=f"solve-only 3D Laplacian n={k ** 3}",
                   nrhs=nrhs, solve_s=round(best, 5),
                   per_rhs_ms=round(per_rhs_ms, 3),
                   vs_nrhs1_wall=round(best / base, 3),
                   finite=bool(np.all(np.isfinite(np.asarray(xb)))),
                   t_factor_s=round(t_factor, 2),
                   platform=dev.platform,
                   device_kind=getattr(dev, "device_kind", ""),
                   ts=time.strftime("%Y-%m-%dT%H:%M:%S"))
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
