"""A/B the Pallas VMEM LU kernel vs the XLA dense_lu path on hardware.

Times `partial_lu_batch` (XLA fori_loop formulation, ops/dense_lu.py)
against `partial_lu_batch_pallas` (VMEM-resident blocked kernel,
ops/pallas_lu.py) per bucket shape on the ambient accelerator, checks
elementwise agreement, and prints one JSON line per (mb, wb, N)
config.  This is the measurement VERDICT round-1 item 3 asks for: the
`SLU_TPU_PALLAS` default must resolve by hardware numbers, not hope.

Run on the chip:   python tools/pallas_ab.py   (from the repo root)
Run interpreted:   JAX_PLATFORMS=cpu python tools/pallas_ab.py  (slow)

Agreement is judged against an f64 numpy ground truth, not mutually:
the two formulations accumulate f32 rounding differently (on TPU the
XLA path's MXU matmuls round differently again), so their mutual diff
measures rounding, not correctness.  `agree` = the Pallas error is
within 2x the XLA path's own distance from the f64 factorization.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the "XLA" arm calls partial_lu_batch, whose dispatch honors
# SLU_TPU_PALLAS — with the flag exported the A/B would compare the
# Pallas kernel against itself; pin it off for this process
os.environ["SLU_TPU_PALLAS"] = "0"

import jax
import jax.numpy as jnp


def ref_partial_lu(F, wb):
    """f64 unpivoted partial LU ground truth (leading wb columns),
    vectorized over the batch dimension."""
    F = F.astype(np.float64).copy()
    for k in range(wb):
        F[:, k + 1:, k] /= F[:, k, k][:, None]
        F[:, k + 1:, k + 1:] -= np.einsum(
            "bi,bj->bij", F[:, k + 1:, k], F[:, k, k + 1:])
    return F


_CHAIN = int(os.environ.get("SLU_AB_CHAIN", "8"))
# in-jit repetitions per dispatch; SLU_AB_CHAIN=1 for interpret-mode
# smoke runs where the chain's cost swamps the measurement anyway


def time_fn(fn, F, reps=4):
    """Amortized per-op time: per-dispatch overhead swamps
    µs-to-ms-scale kernels, so the op is
    CHAINED _CHAIN times inside ONE jitted program (each output front
    feeds the next factorization — same shapes, sequential dependency
    defeats DCE) and the chain's wall time is divided out."""
    single = jax.jit(fn)
    out = single(F)                      # correctness output (1 apply)
    jax.block_until_ready(out)

    def chain(F):
        def body(c, _):
            return fn(c)[0], None
        return jax.lax.scan(body, F, None, length=_CHAIN)[0]

    chained = jax.jit(chain)
    jax.block_until_ready(chained(F))    # compile
    best = np.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(chained(F))
        best = min(best, time.perf_counter() - t0)
    return best / _CHAIN, out


def main():
    from superlu_dist_tpu.ops.dense_lu import partial_lu_batch
    from superlu_dist_tpu.ops.pallas_lu import (partial_lu_batch_pallas,
                                                usable)

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    print(f"# device: {dev.device_kind or dev.platform}", file=sys.stderr)
    rng = np.random.default_rng(0)
    # bucket shapes spanning the schedule's range: (wb, mb, batch);
    # SLU_AB_CONFIGS="wb,mb,N;wb,mb,N" overrides (interpret smoke)
    cfg_env = os.environ.get("SLU_AB_CONFIGS", "")
    if cfg_env:
        configs = [tuple(int(v) for v in c.split(","))
                   for c in cfg_env.split(";") if c]
    else:
        configs = [(8, 16, 512), (16, 32, 256), (32, 64, 128),
                   (64, 128, 64), (128, 256, 16), (256, 512, 4),
                   (512, 512, 2)]
    results = []
    for wb, mb, N in configs:
        if not usable(mb, np.float32):
            continue
        F = rng.standard_normal((N, mb, mb)).astype(np.float32)
        # diagonally dominant pivot block: no tiny-pivot replacements,
        # so both paths run their arithmetic main line
        F[:, np.arange(wb), np.arange(wb)] += 2.0 * mb
        Fd = jnp.asarray(F)
        thresh = np.float32(1e-30)

        xla = lambda F: partial_lu_batch(F, thresh, wb=wb)
        t_xla, (Fx, tx, zx) = time_fn(xla, Fd)

        pal = lambda F: partial_lu_batch_pallas(
            F, thresh, wb=wb, interpret=not on_tpu)
        try:
            t_pal, (Fp, tp, zp) = time_fn(pal, Fd)
        except Exception as e:
            results.append(dict(wb=wb, mb=mb, N=N, error=repr(e)[:200]))
            print(json.dumps(results[-1]), flush=True)
            continue

        # accuracy of each path vs the f64 ground truth over the FULL
        # batch (a bug hitting only grid steps i > 0 must not hide
        # behind element 0), and counter agreement (the tiny/nzero
        # outputs ride per-program_id SMEM slots — check them)
        R = ref_partial_lu(F, wb)
        scale = np.abs(R) + 1.0
        err_x = float((np.abs(np.asarray(Fx) - R) / scale).max())
        err_p = float((np.abs(np.asarray(Fp) - R) / scale).max())
        counters_ok = (int(tp) == int(tx)) and (int(zp) == int(zx))
        # true flops of one batched partial LU (no padding correction:
        # every front here is exactly (mb, mb) with wb live columns)
        flops = N * sum((mb - k - 1) + 2 * (mb - k - 1) ** 2
                        for k in range(wb))
        rec = dict(wb=wb, mb=mb, N=N,
                   t_xla_ms=round(t_xla * 1e3, 3),
                   t_pallas_ms=round(t_pal * 1e3, 3),
                   speedup=round(t_xla / t_pal, 3),
                   gflops_xla=round(flops / t_xla / 1e9, 1),
                   gflops_pallas=round(flops / t_pal / 1e9, 1),
                   err_xla=err_x, err_pallas=err_p,
                   counters_ok=counters_ok,
                   agree=bool(counters_ok
                              and err_p <= max(2.0 * err_x, 1e-5)))
        results.append(rec)
        print(json.dumps(rec), flush=True)
    wins = [r for r in results if r.get("agree") and r["speedup"] > 1.1]
    print(json.dumps({"summary": "pallas_wins",
                      "configs": [(r["wb"], r["mb"]) for r in wins]}),
          flush=True)


if __name__ == "__main__":
    main()
