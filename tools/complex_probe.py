"""Does complex arithmetic compile and run on jax's default backend?

Two tiny jitted programs, the factor path's core ops without the
solver around them (ROADMAP R3/D4 decide the complex path from what
these report on the chip):

  c128_kernel       one 48x48 complex128 partial LU + one complex GEMM
  c64_kernel        the same in complex64 (no 64-bit rewriting on a TPU)
  c128_pair_kernel  the same math on stacked real/imag planes
                    (ops/pair_lu) — an all-real program

    python tools/complex_probe.py c128_kernel [--limit SECONDS]

One probe per process: a compile that never returns is killed by the
alarm (exit 142 from SIGALRM's default action), and one that aborts
the process (native complex128 on a TPU v5e does, PR 23) takes nothing
else with it.  Prints one JSON line naming the platform it ran on.
"""

import argparse
import json
import os
import signal
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("probe", choices=("c128_kernel", "c64_kernel",
                                      "c128_pair_kernel"))
    ap.add_argument("--limit", type=int, default=240)
    args = ap.parse_args()
    signal.alarm(args.limit)

    import numpy as np
    import jax
    import jax.numpy as jnp
    from superlu_dist_tpu.ops import pair_lu
    from superlu_dist_tpu.ops.dense_lu import partial_lu

    dev = jax.devices()[0]
    rng = np.random.default_rng(3)
    F = (rng.standard_normal((48, 48))
         + 1j * rng.standard_normal((48, 48)))
    F += np.diag(np.full(48, 16.0 + 0j))
    t0 = time.perf_counter()
    if args.probe != "c128_pair_kernel":
        Fd = jnp.asarray(F, dtype=jnp.complex64 if args.probe ==
                         "c64_kernel" else jnp.complex128)
        lu, _, _ = jax.jit(lambda m: partial_lu(m, 1e-30, wb=24))(Fd)
        gemm = jax.jit(lambda a, b: a @ b)(Fd, Fd)
        lu, gemm = np.asarray(lu), np.asarray(gemm)
    else:
        # planes split on the host: no complex array touches the device
        Fp = jnp.asarray(np.stack([F.real, F.imag]))
        lu, _, _ = jax.jit(
            lambda m: pair_lu.partial_lu_pair(m, 1e-30, wb=24))(Fp)
        gemm = jax.jit(pair_lu.pmatmul)(Fp, Fp)
        lu, gemm = np.asarray(lu), np.asarray(gemm)
        gemm = gemm[0] + 1j * gemm[1]
    out = dict(probe=args.probe, platform=dev.platform,
               device_kind=dev.device_kind,
               secs=round(time.perf_counter() - t0, 2),
               lu_finite=bool(np.isfinite(lu).all()),
               gemm_relerr=float(np.linalg.norm(gemm - F @ F)
                                 / np.linalg.norm(F @ F)))
    out["ok"] = out["lu_finite"] and out["gemm_relerr"] < 1e-4
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
