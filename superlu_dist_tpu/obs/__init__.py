"""obs/ — the unified observability spine.

One place answers the three runtime questions the PStatPrint report
(SRC/util.c:331) answers offline and a multi-tenant service must
answer live:

  * where did this solve's time go? — `tracer`: thread-safe nested
    phase spans (equilibrate → rowperm → colperm → symbolic →
    distribute → factor → solve → refine, plus the serve
    queue/assemble/batch/solve stages), exported as Chrome
    trace-event JSON (Perfetto-loadable; `tools/trace_export.py`)
    and/or a JSONL event log.  Gated by SLU_OBS / SLU_TRACE /
    SLU_TRACE_JSONL with a no-op singleton fast path when off.
  * did XLA recompile, and what did the start cost? —
    `compile_watch`: per-jitted-phase cache-miss counters with
    shape/dtype/static-arg attribution, and the start-up ledger: one
    row a new program of the process (watched or eager) with its
    seconds split into trace, lower, compile and persistent-cache
    load from `jax.monitoring`, beside the plan's and the schedule's
    phases (`COMPILE_WATCH.ledger()`).  Always on; it writes only
    when a program is new.
  * are the numerics drifting? — `health`: tiny-pivot replacement
    counts, pivot-growth estimates, berr/ferr trajectories and
    escalation events — the GESP runtime-watch obligation.
  * what happened to THIS request? — `flight`: per-request flight
    records (monotonic rid, stage events through admission → cache →
    batcher → solve → refine → resilience, bounded ring +
    SLU_FLIGHT_JSONL sink, per-request Perfetto tracks via
    tools/trace_export.py).  Gated by SLU_FLIGHT; one pointer check
    when off.
  * are we meeting what we sold? — `slo`: declared
    latency/availability objectives per (n-bucket, dtype tier) with
    sliding-window burn rates and exemplar rids on violated windows
    (SLU_SLO).

Everything registers into ONE `Registry` (`REGISTRY`): per-run phase
stats (utils/stats.py), serve metrics (serve/metrics.py), the compile
watcher, the health monitor and the tracer, so `obs.snapshot()` is
the single structured view and `obs.dump_text()` the single
Prometheus-style text dump (wired into `SolveService`).
"""

from . import aggregate, export, flight, memory, slo
from .aggregate import FLEET_SCHEMA, FLEET_VERSION
from .compile_watch import (COMPILE_WATCH, CompileWatch, stamp_cost,
                            take_cost, watch_jit)
from .export import (EXPORT_SCHEMA, EXPORT_VERSION, export_enabled,
                     export_snapshot, export_text)
from .flight import FlightRecord, FlightRecorder
from .health import HEALTH, HealthMonitor, pivot_growth
from .memory import MEMWATCH, MemoryWatch
from .registry import REGISTRY, Registry
from .slo import Objective, SloEngine
from .tracer import (NULL_SPAN, Tracer, complete, configure, enabled,
                     export_trace, get_tracer, instant,
                     resolve_trace_path, span)

__all__ = [
    "COMPILE_WATCH", "CompileWatch", "EXPORT_SCHEMA", "EXPORT_VERSION",
    "FLEET_SCHEMA", "FLEET_VERSION", "FlightRecord", "FlightRecorder",
    "HEALTH", "HealthMonitor", "MEMWATCH", "MemoryWatch", "NULL_SPAN",
    "Objective", "REGISTRY", "Registry", "SloEngine", "Tracer",
    "aggregate", "complete", "configure", "dump_text", "enabled",
    "export", "export_enabled", "export_snapshot", "export_text",
    "export_trace", "flight", "get_tracer", "instant", "memory",
    "pivot_growth", "resolve_trace_path", "slo", "snapshot", "span",
    "stamp_cost", "take_cost", "watch_jit",
]


class _TracerProvider:
    """Registry shim: snapshots whichever tracer is currently live
    (the tracer object itself is swapped by configure())."""

    @staticmethod
    def snapshot() -> dict:
        t = get_tracer()
        return t.snapshot() if t is not None else {"enabled": False}


REGISTRY.register("compile", COMPILE_WATCH)
REGISTRY.register("health", HEALTH)
REGISTRY.register("trace", _TracerProvider())
REGISTRY.register("memory", MEMWATCH)


def snapshot() -> dict:
    """One dict over every registered telemetry surface."""
    return REGISTRY.snapshot()


def dump_text() -> str:
    """One flat Prometheus-style text dump of the same."""
    return REGISTRY.dump_text()
