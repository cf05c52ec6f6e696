"""Registry of every `SLU_`-prefixed environment flag.

This table holds every `SLU_*` name the package reads: it is the
single place they are all named and described.  The audit lives
in tools/slulint (rules/envreads.flag_audit): it scans the package
and tools/ for `SLU_[A-Z_0-9]+` tokens and fails when a
read is undocumented here (or when an entry here no longer
corresponds to any read) — tests/test_flags.py is a thin wrapper
over it, and `python -m tools.slulint` gates on it too.  The
accessors below are the package's ONLY legal way to read these
flags (slulint's env-read rule enforces that), and they refuse
undocumented names at runtime — so the table cannot rot in either
direction.

Convention: boolean flags take "1"/"0"; numeric flags parse int/float;
unset means the documented default.  SUPERLU_*-prefixed knobs are the
reference's sp_ienv analog chain and live on Options fields
(options.py), not here.
"""

from __future__ import annotations

import os

# flag name -> one-line description (scope: where it is read)
FLAGS: dict[str, str] = {
    # --- execution-mode selection (ops/batched.py) ---
    "SLU_STAGED": "1/0 force per-group staged execution on/off (default: auto past SLU_STAGED_MIN_GROUPS groups)",
    "SLU_STAGED_MIN_GROUPS": "group count past which staged execution turns on automatically (default 96)",
    # --- extend-add lanes (ops/batched.py) ---
    "SLU_EA_BLOCK": "1/0 block-copy extend-add lane for contiguous child runs (default on)",
    "SLU_EA_BLOCK_MIN_RUN": "minimum contiguous run length routed to the block lane (default 8)",
    # --- blocked trisolve (ops/trisolve.py) ---
    "SLU_TRISOLVE": "auto|merged|legacy: the one-device sweep only (a mesh reads nothing here).  merged = the communication-avoiding lsum trisolve (packed panels, dense lsum buffers, zero scatters; the legacy sweep's arithmetic in its order, agreement to 4 eps pinned); auto = merged; legacy = the scatter-add sweep",
    "SLU_TRISOLVE_MERGE_CELLS": "panel-cell bound (trim*mb*wb) under which a group joins a merged dispatch segment (default 65536); larger groups stand alone",
    "SLU_TRISOLVE_SEG_CELLS": "total panel-cell budget of one merged segment (default 1048576) — bounds per-segment staged program size",
    # --- level-merged factor sweep (ops/batched.py) ---
    "SLU_FACTOR_MERGE_CELLS": "front-cell bound (n_loc*mb*ncols) at or below which a factor group joins a merged staged dispatch segment (default 65536); 0 = legacy per-group staged dispatch (the A/B arm).  Merging is dispatch granularity only — factors are bitwise-identical to the legacy sweep",
    "SLU_FACTOR_SEG_CELLS": "total front-cell budget of one merged factor segment (default 1048576) — bounds per-segment staged program size so segment compiles stay in the per-group compile class",
    # --- residual SpMV layout (ops/spmv.py) ---
    "SLU_SPMV_LAYOUT": "auto|ell|coo residual SpMV layout (ell = scatter-free padded rows)",
    "SLU_SPMV_ELL_WASTE": "max ELL padding ratio over true nnz before falling back to COO (default 4)",
    # --- complex storage / platform gates (ops, utils/platform.py) ---
    "SLU_COMPLEX_PAIR": "TEST HOOK: 1 = force the pair lowering of complex (factors as stacked real/imag planes, an all-real program) on a backend that would run native, i.e. XLA:CPU.  Decides nothing on a TPU, where pair is what a complex factor dtype takes with no variable set (utils/platform.complex_lowering)",
    "SLU_COMPLEX_TPU": "1 = run NATIVE complex on a TPU: no pair lowering, no CPU gate, the complex mesh block lifted — for whoever repairs the native lowering (native complex128 aborts today's TPU compiler)",
    "SLU_MATMUL_PREC": "default|high|highest jax matmul precision pin applied at import (__init__.py)",
    # --- cooperative mesh factorization (ops/coop_lu.py, coop_sharded.py) ---
    "SLU_COOP_SHARDED": "1/0 sharded cooperative mesh path vs legacy replicated coop",
    "SLU_COOP_B": "round-robin block size for group-to-device ownership (default 1)",
    "SLU_COOP_MB": "front-size cap for cooperative factorization tiles (default 256)",
    "SLU_COOP_SOLVE_ROTATE": "1 = rotate solve ownership across devices instead of device 0",
    "SLU_RHS_SHARDED": "auto|1|0 shard wide RHS blocks over the mesh for the dist solve",
    # --- Pallas kernel (ops/pallas_lu.py) ---
    "SLU_TPU_PALLAS": "1 = enable the Pallas diagonal-LU kernel (validated, retired to opt-in)",
    "SLU_TPU_PALLAS_COLUMN": "1 = force the per-column rank-1 Pallas LU variant",
    # --- planning / ordering (parallel/ordering_dist.py) ---
    "SLU_DORDER_CLUSTER": "distributed-ordering aggregation block size (default 16)",
    # --- observability (obs/tracer.py, obs/compile_watch.py) ---
    "SLU_OBS": "1/0 master observability switch: span tracer + pivot-growth capture (default off unless SLU_TRACE*/SLU_TRACE_JSONL set; off costs one pointer check per span — no gssvx tax, pinned by tests/test_obs_trace.py)",
    "SLU_TRACE": "Chrome trace-event JSON export path, written at process exit (1 = ./last.trace.json; implies SLU_OBS; ~1 µs + one dict per span while on)",
    "SLU_TRACE_JSONL": "JSONL event-log path, appended through as spans close (implies SLU_OBS; adds one file write per span)",
    # --- request-scoped flight recorder + SLO engine (obs/flight.py, obs/slo.py) ---
    "SLU_FLIGHT": "1/0 per-request flight recorder: every SolveService request gets a monotonic rid and a stage-event record (admit/cache/queue/solve/refine + resilience events) in a bounded ring; off = ONE module-global pointer check on the request path (zero growth); on costs a few dict/list appends per request",
    "SLU_FLIGHT_JSONL": "flight-record JSONL sink path, one line per RETAINED record as it finishes (implies SLU_FLIGHT; adds one file write per retained request; self-disables on I/O error; tools/trace_export.py renders it as per-request Perfetto tracks)",
    "SLU_FLIGHT_RING": "flight-record ring capacity (default 256): completed records kept for obs.snapshot()/lookup; non-ok outcomes are always retained until displaced by newer records",
    "SLU_FLIGHT_SAMPLE": "keep 1-in-N of `ok` flight records (default 1 = all); failures are ALWAYS retained regardless — sampling bounds sink volume under sustained healthy traffic, never traceability",
    "SLU_SLO": "SLO declaration: '1' = defaults (p99_ms=100, avail=0.99, window_s=60); 'p99_ms=50,avail=0.999,window_s=60[;scope:field=v]' with n-bucket/dtype-tier scoped overrides; sliding-window burn-rate accounting per (n-bucket, dtype tier) with exemplar rids on violated windows; off = one pointer check per request completion",
    # --- fleet telemetry export + aggregation (obs/export.py, obs/aggregate.py, obs/memory.py) ---
    "SLU_OBS_EXPORT": "telemetry export listener address ('unix:/path/sock', 'host:port', or a bare port on 127.0.0.1): serves the versioned obs snapshot as JSON (/snapshot) and Prometheus-style text (/metrics) over a minimal HTTP loop; unset/0 (default) = no listener, and the serve path pays ONE module-global pointer check (nothing per request — export reads snapshots on its own threads)",
    "SLU_OBS_EXPORT_JSONL": "periodic export write-through path: one schema-stamped snapshot line per period appended beside the durable store (tracer sink discipline: self-disables on I/O error, never throws into serving); implies the exporter is on even without a listener",
    "SLU_OBS_EXPORT_PERIOD_S": "export write-through period in seconds (default 5.0); each tick costs one registry snapshot + one file append on the exporter's own thread",
    "SLU_OBS_MEM": "1 = live device-memory probes (jax device.memory_stats live/peak bytes) on every factorization's watermark record; off (default) = the analytic slab-extent bytes model only (free: a few int multiplies from the schedule), so every factorization record still carries plan_bytes_predicted",
    "SLU_PLAN_LATENCY_OUT": "plan-build latency record sink (ROADMAP 5a): plan/plan.py appends one mode=plan_latency line (t_plan_s, pattern sha1, n, nnz) per cold plan build when set; self-disabling sink, one file append per plan build",
    # --- mixed precision (precision/, options.py, serve/service.py) ---
    "SLU_PREC_RESIDUAL": "auto|plain|doubleword|fp64 default Options.residual_mode: how the IR residual accumulates (doubleword = two-float fp32 df64, ~25 f32 flops/term vs 2 — noise next to fp64 EMULATION on TPU, and zero f64 ops in the jitted path; host loop uses native f64 either way)",
    "SLU_PREC_LADDER": "comma dtype list overriding the escalation ladder (default bfloat16,float32,float64; sorted by eps, climbed one rung per failed refinement contract — each rung re-pays one factorization)",
    "SLU_PREC_TIERS": "1 = serve-layer dtype-TIER serving: a cold high-precision request rides resident lower-rung factors via df64 refinement (saves a cold factorization; costs ~2-3 extra refinement sweeps per solve, berr-guarded with automatic re-key on miss)",
    # --- numerical trust layer (numerics/, models/gssvx.py, serve/) ---
    "SLU_COND_ESTIMATE": "1 = eager Hager-Higham rcond estimation after every driver/serve factorization (numerics/gscon.py): at most 2*SLU_COND_MAXITER+2 refinement-free packed-trisolve solves per factorization, ZERO extra factorizations; off (default) = rcond stays lazy via ensure_rcond and the condition policy never engages",
    "SLU_COND_MAXITER": "Hager-Higham iteration cap per rcond estimate (default 5; each iteration is one forward + one transpose solve)",
    "SLU_COND_FLOOR": "rcond refusal floor: an estimated rcond at or below this raises typed SingularMatrixError instead of serving a garbage solve (default 0 = auto: eps(refine_dtype)); only engaged when an estimate exists",
    "SLU_COND_POLICY": "serve|stamp|refuse condition-aware serving policy for ill-conditioned (above-floor) keys: serve = silent, stamp (default) = results ride a PerturbedResult/ill-conditioned label, refuse = typed SingularMatrixError; floor refusal applies in every mode",
    "SLU_COND_STAMP": "ill-conditioned classification threshold on rcond (default 0 = auto: sqrt(eps(refine_dtype))); below it the policy mode engages, the serve berr guard tightens by SLU_COND_SLACK_DIV, and the escalation ladder climbs a rung before first serve",
    "SLU_COND_SLACK_DIV": "divisor applied to the 64-eps berr guard slack for keys classified ill-conditioned (default 8: guard tightens to 8*eps) — high-kappa keys get less refinement slack, not more",
    # --- resilience (resilience/, serve/factor_cache.py) ---
    "SLU_BREAKER_THRESHOLD": "per-key circuit-breaker failure threshold (resilience/breaker.py; default 3): this many consecutive lead-factorization failures open the circuit; 0 at the ServeConfig layer disables the breaker entirely",
    "SLU_BREAKER_COOLDOWN_S": "circuit-breaker open-state cooldown seconds (default 30): requests during the cooldown get an immediate FactorPoisoned, then ONE half-open probe is admitted — success closes, failure re-opens for another cooldown",
    "SLU_FT_STORE": "durable factor-store directory: FactorCache write-through/read-through persistence tier (atomic rename + sha256 framing + per-array ABFT checksum; corrupt entries quarantined to *.quarantined, never served; a restarted replica boots warm)",
    "SLU_CHAOS": "fault-injection spec 'site=prob[:param],...' — sites: factor_raise, factor_nan, store_flip, flusher_raise, latency (param = sleep seconds), store_latency, lease_steal, replica_kill, refactor_raise, refactor_slow, swap_kill (the stream pipeline's background-failure + mid-swap-crash sites), near_singular (param = skew strength: deterministic value-skew of incoming stream values toward rank deficiency, the rcond-drift drill's fault); deterministic per-site seeded streams; every site is one pointer check when unset",
    "SLU_CHAOS_SEED": "chaos RNG seed (default 0): same spec+seed replays the identical failure sequence",
    # --- fleet coordination (fleet/, serve/) ---
    "SLU_FLEET": "1 = fleet-wide single-flight over the shared factor store (fleet/lease.py): a cold key elects ONE leader across every replica process sharing SLU_FT_STORE via an O_EXCL lease file; followers poll-with-backoff and adopt the published entry; a dead leader's expired lease is stolen.  Off = the in-process single-flight only",
    "SLU_FLEET_TTL_S": "fleet lease TTL override in seconds (0/unset = 120 s) — the bound on how long a dead leader blocks a key before its lease is stolen",
    "SLU_FLEET_POLL_S": "fleet follower poll interval seconds (default 0.05), growing 1.5x per round to a 1 s cap — the cadence followers re-probe the store for the leader's published entry",
    "SLU_FLEET_VNODES": "virtual nodes per replica on the consistent-hash ring (default 64): smooths per-replica keyspace shares; membership changes still move only the joined/left replica's arc",
    # --- elastic fleet controller (fleet/policy.py, fleet/controller.py) ---
    "SLU_FLEET_BURN_HIGH": "SLO burn rate at or above which the controller scales up and sheds low-weight tenants (default 2.0 — the window is burning error budget at twice the allowed rate)",
    "SLU_FLEET_BURN_LOW": "SLO burn rate at or below which the controller may retire a surplus replica (default 0.25); between the low and high marks the fleet holds steady (hysteresis)",
    "SLU_FLEET_MIN_REPLICAS": "floor on live replica count — the controller never retires below it (default 1)",
    "SLU_FLEET_MAX_REPLICAS": "ceiling on live replica count — the controller never spawns past it (default 8)",
    "SLU_FLEET_SCALE_COOLDOWN_S": "minimum seconds between controller scaling actions in either direction (default 60) — capacity transitions are scheduled events, never oscillation",
    "SLU_FLEET_PREFACTOR_MIN": "demand count at which a non-resident pattern key becomes a prefactor target (default 2): the controller schedules warming at the key's ring home through the lease single-flight path",
    "SLU_SERVE_BLAS_THREADS": "host BLAS pool size pinned by the first SolveService, process-wide (default 1; 0 = leave the pool alone; needs threadpoolctl, silently no-op without it) — a multi-threaded OpenBLAS pool's spin-wait barriers let one caller monopolize every core, so a background refactorization's host BLAS stalls concurrent solves (stream overlap A/B measured 1.45x p99 before the pin, 1.05x after); zero per-request overhead (one-time pool resize)",
    # --- streaming refactorization (stream/) ---
    "SLU_STREAM_TRIP": "stream cadence escalation threshold as a fraction of the hard berr-guard limit (default 0.25): a stale solve's refined berr past trip_frac x 64·eps(refine_dtype) fires the stream_drift health escalation and requests a background refactorization; the hard limit itself always withholds the result (typed StaleFactorError, never served past the guard)",
    "SLU_STREAM_INTERVAL_SCALE": "minimum seconds between background refactor starts as a multiple of the measured factorization cost (default 1.0) — bounds the pipeline's background duty cycle; the cost estimate is the handle's own refactor-wall EWMA (1 s before the first wall is measured)",
    "SLU_STREAM_MAX_LAG": "steps the live values may trail the resident generation before a refactor is forced regardless of berr (default 0 = disabled; drift in the measured berr is the primary cadence signal)",
    "SLU_STREAM_PROBE": "1/0 probe solve before a generation publishes (default 1): one refined solve on the fresh factors — builds the PackSet, warms the nrhs=1 program, and refuses a factorization whose solve path is broken; costs one solve per refactorization, zero on the serve path",
    "SLU_STREAM_RCOND_DRIFT": "stream cadence rcond-drift trigger ratio (default 100): a background refactorization is requested when the latest generation's estimated rcond fell below baseline/ratio — conditioning decay caught alongside the berr trajectory; inert unless rcond estimates flow (SLU_COND_ESTIMATE)",
    # --- native library (utils/native.py) ---
    "SLU_TPU_NO_NATIVE": "1 = never build/load the native helper .so (pure-python fallbacks)",
    # --- differentiable solve (autodiff/solve.py) ---
    "SLU_AD_REFINE": "differentiable-forward refinement steps (default 1): sparse_solve returns the k-step refined solution while its VJP stays the exact-fixed-point adjoint (DESIGN.md §24); 0 = raw resident apply — the primal then carries NO A_values dependence (d/dA finite differences read 0 while the VJP still answers the implicit-function question)",
    "SLU_AD_JIT": "1 (default) = dispatch the autodiff forward/adjoint legs through the cached compile-watched jits (obs phases grad_fwd/adjoint — the zero-recompile and HLO-contract surface); 0 = trace them op-by-op eager (debug lane)",
    # --- mesh-resident serving (serve/service.py, parallel/factor_dist.py) ---
    "SLU_SERVE_MESH": "1 = mesh-resident serving: ServeConfig.mesh defaults to a device mesh (SLU_MESH_SHAPE), the factor cache factors through the shard_map'd dist backend, and every request key carries an Options.mesh_shape leg.  Off (default) = single-device serving, one env read of overhead at ServeConfig construction",
    "SLU_MESH_SHAPE": "mesh grid for SLU_SERVE_MESH=1 ('2x2x2', '8'; default: all local devices on one flat axis) — resolved once per ServeConfig construction, zero per-request overhead",
    # --- batch engine (batch/, serve/coalescer.py) ---
    "SLU_BATCH_LADDER": "batch-size bucket ladder for the batch engine and factor coalescer, comma ints ascending (default '1,4,8,16,32'); sizes quantize UP a rung (short batches pad by replicating a live member), so after warmup the compiled-program population is bounded by the rung count — the zero-recompile contract.  Read once per warmup/coalescer construction",
    "SLU_BATCH_COALESCE": "1 = serve-layer factor coalescing (serve/coalescer.py): same-pattern cold factor requests arriving within the coalesce window merge into one batch_factorize dispatch up the B-ladder, results fanned back into ordinary per-key cache residents; off (default) = every cold key factors solo (zero overhead: the serve path checks this once per SolveService construction)",
    "SLU_BATCH_WINDOW_MS": "factor-coalescer max linger (ms, default 2): how long the first cold request of a pattern waits for same-pattern siblings before the flusher dispatches the batch — the factor-side twin of SLU_SERVE_LINGER_MS; latency cost is bounded by the window, throughput gain by the rung reached",
    "SLU_BATCH_MEMBER_POLICY": "coalescer member-failure policy: 'refuse' (default) = a singular/ill batch member gets its typed per-index refusal (ZeroDivisionError analog) and ONLY that member fails; 'fallback' = failed members retry solo through the ordinary unbatched factor path (costs one extra factorization for the failed member; siblings are untouched either way)",
}

# Tokens the registry test's grep will hit that are NOT env flags:
# enum member names and docstring mentions of reference storage
# formats / flag-family prefixes.
NON_FLAG_TOKENS: frozenset = frozenset({
    "SLU_SINGLE",    # IterRefine enum member (options.py)
    "SLU_DOUBLE",    # IterRefine enum member (options.py)
    "SLU_NC",        # reference SuperMatrix storage format name
    "SLU_COOP_",     # prefix shorthand in a batched.py comment
    "SLU_AD_",       # prefix shorthand in autodiff/solve.py docstrings
    "SLU_",          # the bare prefix itself (docstrings)
})

# --------------------------------------------------------------------
# the package's ONE env gateway
# --------------------------------------------------------------------
#
# Every environment read inside superlu_dist_tpu/ goes through these
# accessors (tools/slulint's `env-read` rule fails any direct
# os.environ read outside this module), which refuse names the FLAGS
# table does not document — so an undocumented knob fails at its
# first read, not just in the registry audit.  Non-SLU names the
# package legitimately reads are declared below: external toolchain
# knobs and the reference's sp_ienv SUPERLU_* chain (documented on
# Options fields, options.py, per the module docstring).

EXTERNAL_OK: frozenset = frozenset({
    "XLA_FLAGS",                  # utils/compat.py, utils/cache.py
    "JAX_COMPILATION_CACHE_DIR",  # utils/warmup.py
})
EXTERNAL_PREFIXES: tuple = ("SUPERLU_",)


def _known(name: str) -> str:
    if (name in FLAGS or name in EXTERNAL_OK
            or name.startswith(EXTERNAL_PREFIXES)):
        return name
    raise KeyError(
        f"undocumented env flag {name!r}: document it in "
        "superlu_dist_tpu/flags.py FLAGS before reading it")


def env_opt(name: str) -> str | None:
    """Raw documented-flag read: the value, or None when unset (for
    call sites that distinguish unset from empty, e.g. SLU_FLIGHT)."""
    return os.environ.get(_known(name))


def env_str(name: str, default: str = "") -> str:
    """Documented-flag read with a default ('' unless given)."""
    return os.environ.get(_known(name), default)


def env_int(name: str, default: int) -> int:
    """Int-valued documented flag; empty/unset -> default."""
    v = os.environ.get(_known(name))
    return int(v) if v else default


def env_float(name: str, default: float) -> float:
    """Float-valued documented flag; empty/unset -> default."""
    v = os.environ.get(_known(name))
    return float(v) if v else default
