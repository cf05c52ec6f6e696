"""pddrive — solve A·X = B from a matrix file (EXAMPLE/pddrive.c:51).

Reads Harwell-Boeing (.rua/.cua), Rutherford-Boeing (.rb), MatrixMarket
(.mtx), triples (.dat) or raw binary (.bin) by filename postfix like
the reference's dcreate_matrix_postfix, manufactures a known solution
(dGenXtrue_dist/dFillRHS_dist analog), runs the full gssvx pipeline and
prints the inf-norm error (EXAMPLE/pddrive.c:323 pdinf_norm_error) plus
the PStatPrint-style phase report.

    python -m superlu_dist_tpu.drivers.pddrive g20.rua
    python -m superlu_dist_tpu.drivers.pddrive -r 2 -c 2 -d 2 big.rua
    python -m superlu_dist_tpu.drivers.pddrive --fused --dtype float32 A.mtx

The -r/-c/-d grid flags mirror pddrive's; with a product > 1 the solve
runs the distributed shard_map path on an (r, c, z) device mesh.
"""

from __future__ import annotations

import argparse
import sys

import numpy as np

from .. import Options, gssvx
from ..options import ColPerm, IterRefine, RowPerm, Trans
from ..utils.io import read_matrix
from ..utils.stats import Stats


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="pddrive",
        description="TPU-native distributed sparse LU solve of A·X=B")
    p.add_argument("matrix", help="matrix file (.rua/.cua/.rb/.mtx/"
                                  ".dat/.datnh/.bin)")
    p.add_argument("-r", "--nprow", type=int, default=1,
                   help="process grid rows (mesh axis 'r')")
    p.add_argument("-c", "--npcol", type=int, default=1,
                   help="process grid cols (mesh axis 'c')")
    p.add_argument("-d", "--npdep", type=int, default=1,
                   help="grid depth (mesh axis 'z', the 3D algorithm)")
    p.add_argument("-s", "--nrhs", type=int, default=1)
    p.add_argument("--dtype", default=None,
                   help="factor dtype (default: matrix dtype; use "
                        "float32 for the mixed-precision strategy)")
    p.add_argument("--backend", default="auto",
                   choices=["auto", "jax", "host"])
    p.add_argument("--fused", action="store_true",
                   help="run the fused one-program device solver")
    p.add_argument("--colperm", default="METIS_AT_PLUS_A",
                   choices=[m.name for m in ColPerm])
    p.add_argument("--rowperm", default="LARGE_DIAG_MC64",
                   choices=[m.name for m in RowPerm])
    p.add_argument("--refine", default="SLU_DOUBLE",
                   choices=[m.name for m in IterRefine])
    p.add_argument("--trans", default="NOTRANS",
                   choices=[m.name for m in Trans])
    p.add_argument("--no-equil", action="store_true")
    p.add_argument("--autotune", action="store_true",
                   help="refit padding bucket grids to this pattern "
                        "(one extra symbolic pass)")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--profile", metavar="DIR", default=None,
                   help="capture a jax.profiler trace of the solve "
                        "into DIR (the PROFlevel/VTune-hook analog; "
                        "view with tensorboard or xprof)")
    p.add_argument("--stats", action="store_true",
                   help="also print measured collective traffic from "
                        "the compiled HLO next to the schedule's "
                        "prediction (SCT_print3D analog; distributed "
                        "runs only)")
    p.add_argument("-q", "--quiet", action="store_true")
    p.add_argument("-v", "--verbose", action="store_true",
                   help="echo the effective options "
                        "(print_options_dist analog)")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    a = read_matrix(args.matrix)
    n = a.n
    if not args.quiet:
        print(f"matrix: {args.matrix}  n={n}  nnz={a.nnz}  "
              f"dtype={a.dtype}")

    from ..models.gssvx import effective_factor_dtype

    complex_sys = np.issubdtype(a.dtype, np.complexfloating)
    fdt = args.dtype or ("complex128" if complex_sys else "float64")
    eff = effective_factor_dtype(a.dtype, fdt).name
    if eff != fdt:
        if not args.quiet:
            print(f"complex matrix: factor dtype mapped to {eff}")
        fdt = eff
    # accelerator-resolved runs get the measured-best amalgamation
    # env defaults (utils/platform.py ladder); the CLI is about to
    # drive this backend anyway, so resolving it here costs nothing
    # extra.  User env always wins.  NOT applied when the numeric
    # phase will actually run on CPU: an explicit --backend host, or
    # a complex system the platform gate reroutes off-TPU — the
    # accelerator trade is measured WORSE there.
    from ..utils.platform import (apply_accel_amalg_defaults,
                                  complex_needs_cpu)
    if args.backend != "host" and not complex_needs_cpu(np.dtype(fdt)):
        import jax
        if jax.default_backend() != "cpu":
            apply_accel_amalg_defaults()

    opts = Options(
        factor_dtype=fdt,
        equil=not args.no_equil,
        col_perm=ColPerm[args.colperm],
        row_perm=RowPerm[args.rowperm],
        iter_refine=IterRefine[args.refine],
        trans=Trans[args.trans],
        # only override when the flag is given so the SUPERLU_AUTOTUNE
        # env default (options.py) still applies without it
        **({"autotune": True} if args.autotune else {}),
    )

    if args.verbose:
        print(opts.describe())

    # manufactured solution (dGenXtrue_dist / dFillRHS_dist)
    rng = np.random.default_rng(args.seed)
    xtrue = rng.standard_normal((n, args.nrhs))
    if complex_sys:
        xtrue = xtrue + 1j * rng.standard_normal((n, args.nrhs))
    asp = a.to_scipy()
    op = {Trans.NOTRANS: asp, Trans.TRANS: asp.T,
          Trans.CONJ: asp.conj().T}[opts.trans]
    b = op @ xtrue

    stats = Stats()
    nproc = args.nprow * args.npcol * args.npdep

    import contextlib
    prof: contextlib.AbstractContextManager = contextlib.nullcontext()
    if args.profile:
        import jax
        prof = jax.profiler.trace(args.profile)

    with prof:
        if nproc > 1:
            if args.backend != "auto" or args.fused:
                raise SystemExit("-r/-c/-d > 1 selects the distributed "
                                 "backend; drop --backend/--fused")
            x = _solve_distributed(a, b, opts, args, stats)
        elif args.fused:
            x = _solve_fused(a, b, opts, stats)
        else:
            x, _, stats = gssvx(opts, a, b, stats=stats,
                                backend=args.backend)

    err = np.max(np.abs(x - xtrue)) / max(np.max(np.abs(xtrue)), 1e-300)
    if not args.quiet:
        print(stats.report())
    print(f"inf-norm error: {err:.3e}")
    relres = (np.linalg.norm(op @ x - b)
              / max(np.linalg.norm(b), 1e-300))
    print(f"relative residual: {relres:.3e}")
    return 0 if relres < 1e-6 else 1


def _solve_fused(a, b, opts, stats):
    from ..ops.batched import make_fused_solver
    from ..plan.plan import plan_factorization

    if opts.trans != Trans.NOTRANS:
        raise SystemExit("fused solver is NOTRANS-only; drop --fused "
                         "for transpose solves")
    from ..models.gssvx import (_should_escalate_fused,
                                effective_factor_dtype)

    plan = plan_factorization(a, opts, stats=stats)

    def run(dtype_name, phase="FACT"):
        # uniform accounting per run; the escalated rerun reports
        # under its own FACT_ESC phase so FACT's GFLOP/s never blends
        # two differently-precisioned factorizations
        from ..utils.platform import complex_device_gate
        fdt = effective_factor_dtype(a.dtype, dtype_name)
        # the fused solver is pair-capable (make_fused_solver pair
        # mode), so the default gate applies: on a TPU the rule
        # (utils/platform.complex_lowering) gives it the pair
        # lowering and the complex pipeline compiles complex-free
        with complex_device_gate(fdt, a.dtype, stats=stats,
                                 phase=phase):
            step = make_fused_solver(plan, dtype=fdt)
            with stats.timer(phase):
                # host arrays in: the pair-mode wrapper must encode
                # BEFORE anything touches the device (a complex
                # device buffer would defeat the gate), and the
                # non-pair jitted step transfers its operands itself
                x, berr, steps, tiny, _ = step(a.data, b)
                if hasattr(x, "block_until_ready"):
                    x.block_until_ready()   # pair mode returns numpy
        stats.add_ops(phase, plan.factor_flops)
        stats.berr = float(berr)
        stats.refine_steps += int(steps)
        stats.tiny_pivots += int(tiny)
        return x

    x = run(opts.factor_dtype)
    # same safety net as gssvx (models/gssvx ladder walk): the
    # low-precision factor failed its refinement contract — rebuild
    # the whole fused program one precision rung up on the SAME plan
    # and rerun, climbing bf16 → fp32 → refine precision until the
    # contract holds (precision/policy.py; bounded by the ladder)
    from .. import obs
    from ..precision.policy import classify_trigger, next_factor_dtype
    import jax.numpy as jnp
    cur = opts.factor_dtype
    while _should_escalate_fused(opts.replace(factor_dtype=cur),
                                 stats):
        nxt = next_factor_dtype(cur, ceiling=opts.refine_dtype)
        if nxt is None:
            break
        stats.escalations += 1
        # stall attribution mirrors the fused loop's own stop rule: a
        # finite berr with step budget left means the loop quit
        # because berr stopped halving (the device twin of the host
        # loop's stalled bit); no lu handle exists here, so the
        # pivot-growth probe is unavailable by construction
        stalled = (np.isfinite(stats.berr)
                   and stats.refine_steps < opts.max_refine_steps)
        obs.HEALTH.record_escalation(
            berr=stats.berr, factor_dtype=cur,
            refine_dtype=opts.refine_dtype, to_dtype=nxt,
            trigger=classify_trigger(
                stats.berr, stalled=stalled,
                factor_eps=float(jnp.finfo(jnp.dtype(cur)).eps)))
        x = run(nxt, phase="FACT_ESC")
        cur = nxt
    return np.asarray(x)


def _solve_distributed(a, b, opts, args, stats):
    from ..parallel.grid import make_solver_mesh

    g = make_solver_mesh(args.nprow, args.npcol, args.npdep)
    x, lu, _ = gssvx(opts, a, b, stats=stats, grid=g)
    if getattr(args, "stats", False):
        from ..parallel.factor_dist import measure_comm
        import numpy as _np
        # re-state the prediction at the ACTUAL nrhs and the EFFECTIVE
        # factor dtype (complex systems promote, lu.device_lu.dtype is
        # what the factors actually move) so the side-by-side report
        # compares like with like
        stats.comm_predicted = lu.device_lu.schedule.comm_summary(
            _np.dtype(lu.device_lu.dtype), nrhs=b.shape[1])
        stats.comm_measured = measure_comm(lu.device_lu,
                                           nrhs=b.shape[1])
    return x


if __name__ == "__main__":
    sys.exit(main())
