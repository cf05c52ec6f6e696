"""Prove compile-boundedness at audikw_1 scale without the memory.

VERDICT round-1 item 4: the fused one-program formulation Python-
inlines every (level, bucket) group, so compile cost grows with tree
depth; staged mode (ops/batched.py `staged_enabled`) replaces it with
one cached jitted program per DISTINCT group signature.  This tool
measures the thing that actually bounds staged compile at n≈10⁶ —
the signature population and the wall-clock to AOT-compile all of it
— WITHOUT allocating the ~34.5 GB of factor slabs a real K=100
factorization needs (compile works from ShapeDtypeStructs).

Prints one JSON line:
  {k, n, groups, factor_signatures, sweep_signatures, plan_s,
   schedule_s, compile_s, platform}

Run:  python tools/compile_scale.py          (SLU_SCALE_K=100 default)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax


def main():
    from superlu_dist_tpu import Options
    from superlu_dist_tpu.ops import batched as B
    from superlu_dist_tpu.plan.plan import plan_factorization
    from superlu_dist_tpu.utils.testmat import laplacian_3d

    from superlu_dist_tpu.utils.warmup import warmup_staged

    k = int(os.environ.get("SLU_SCALE_K", "100"))

    t0 = time.perf_counter()
    a = laplacian_3d(k)
    plan = plan_factorization(a, Options(factor_dtype="float32"))
    t_plan = time.perf_counter() - t0

    t0 = time.perf_counter()
    sched = B.build_schedule(plan, ndev=1)
    t_sched = time.perf_counter() - t0

    # the signature sweep IS the warmup utility (one copy of the
    # dispatch-matching lowering recipe lives in utils/warmup.py);
    # workers=1 so compile_s stays a sequential-cost measurement
    rep = warmup_staged(plan, dtype="float32", rhs_dtype="float32",
                        workers=1, force=True)

    print(json.dumps({
        "k": k, "n": a.n, "groups": len(sched.groups),
        "factor_programs": rep["factor_programs"],
        "sweep_programs": rep["sweep_programs"],
        "plan_s": round(t_plan, 1), "schedule_s": round(t_sched, 1),
        "compile_s": rep["secs"],
        "platform": jax.devices()[0].platform,
    }), flush=True)


if __name__ == "__main__":
    main()
