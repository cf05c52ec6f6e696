"""Chrome trace-event export / validation CLI for obs traces.

The span tracer (superlu_dist_tpu/obs/tracer.py) emits events in the
Chrome trace-event format — the schema Perfetto (ui.perfetto.dev) and
chrome://tracing load natively.  This tool validates, summarizes and
converts those artifacts:

    python -m tools.trace_export last.trace.json
        validate the Chrome trace JSON + print a per-span summary

    python -m tools.trace_export events.jsonl -o last.trace.json
        convert a JSONL event log (SLU_TRACE_JSONL) into a
        Perfetto-loadable Chrome trace JSON

    python -m tools.trace_export flight.jsonl -o flight.trace.json
        convert a flight-recorder log (SLU_FLIGHT_JSONL,
        obs/flight.py) into PER-REQUEST tracks: one pid per request
        (process name "request <rid> [<outcome>]"), the request's
        e2e span plus each stage event laid on its timeline — a
        failed request's failing stage is visible at a glance.  The
        format is auto-detected per line ("rid" + "events" keys).

    python -m tools.trace_export export.jsonl -o obs.trace.json
        convert a periodic obs-export log (SLU_OBS_EXPORT_JSONL,
        obs/export.py) into per-replica COUNTER tracks: one pid per
        replica, one ph="C" series per numeric provider leaf —
        the replica's counters over the run.  Auto-detected per line
        (the "slu.obs.snapshot" schema stamp).
"""

from __future__ import annotations

import json
import os
import sys

# keys every trace event must carry; "X" (complete) events add "dur".
REQUIRED_KEYS = ("name", "ph", "ts", "pid", "tid")


def validate_events(events) -> None:
    """Raise ValueError on the first schema violation (the pinned
    ph/ts/dur/pid/tid contract of tests/test_obs_trace.py)."""
    if not isinstance(events, list):
        raise ValueError("traceEvents must be a list")
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(f"event {i} is not an object")
        if ev.get("ph") == "M":
            continue                    # metadata events: name/pid only
        for k in REQUIRED_KEYS:
            if k not in ev:
                raise ValueError(f"event {i} missing key {k!r}: {ev}")
        if not isinstance(ev["ts"], (int, float)):
            raise ValueError(f"event {i} ts not numeric")
        if ev["ph"] == "X":
            if "dur" not in ev or not isinstance(
                    ev["dur"], (int, float)) or ev["dur"] < 0:
                raise ValueError(
                    f"event {i} 'X' without a valid dur: {ev}")


def is_flight_record(obj) -> bool:
    """One SLU_FLIGHT_JSONL line: a per-request flight record
    (obs/flight.py), not a raw trace event."""
    return (isinstance(obj, dict) and "rid" in obj
            and isinstance(obj.get("events"), list))


def is_export_snapshot(obj) -> bool:
    """One SLU_OBS_EXPORT_JSONL line: a periodic obs export snapshot
    (obs/export.py), not a trace event or flight record.  The schema
    stamp is matched literally so this tool stays import-free of the
    package."""
    return (isinstance(obj, dict)
            and obj.get("schema") == "slu.obs.snapshot"
            and isinstance(obj.get("obs"), dict))


def snapshots_to_chrome(records: list) -> list:
    """Export-snapshot lines -> per-replica Chrome COUNTER tracks:
    one pid per replica (process name "replica <id>"), one ph="C"
    counter series per numeric leaf of each registered provider
    (serve.requests, cache.hits, health.factorizations, ...), stamped
    at the snapshot's wall time.  A periodic SLU_OBS_EXPORT_JSONL
    thus opens in Perfetto as the replica's counters over the run.
    Raises ValueError on a malformed record (CLI hygiene: corrupt
    input is a clean rc=1 error, never a certified-valid trace)."""
    events: list = []
    replica_block: dict[str, int] = {}
    for i, rec in enumerate(records):
        if not is_export_snapshot(rec):
            raise ValueError(
                f"record {i} is not an export snapshot: {rec!r}")
        replica = str(rec.get("replica") or "?")
        ts = rec.get("ts")
        if not isinstance(ts, (int, float)):
            raise ValueError(f"record {i} ts not numeric: {ts!r}")
        pid = replica_block.get(replica)
        if pid is None:
            pid = replica_block[replica] = len(replica_block)
            events.append({"name": "process_name", "ph": "M",
                           "pid": pid, "tid": 0,
                           "args": {"name": f"replica {replica}"}})
        ts_us = int(ts * 1e6)
        for provider, surf in sorted(rec["obs"].items()):
            if not isinstance(surf, dict):
                continue
            for k, v in sorted(surf.items()):
                if isinstance(v, bool):
                    v = int(v)
                if not isinstance(v, (int, float)):
                    continue        # lists/dicts/strings: not counters
                events.append({"name": f"{provider}.{k}", "cat": "obs",
                               "ph": "C", "ts": ts_us, "pid": pid,
                               "tid": 0, "args": {"value": v}})
    return events


# replicas are spaced at least this far apart in the pid namespace:
# a fleet trace (N replicas appending to one SLU_FLIGHT_JSONL) groups
# per-replica — pids cluster by replica, and a rid that collides
# across replicas (per-process counters both start at 1) still maps
# to a distinct track.  The actual stride grows past the log's
# largest rid so a long-running replica can never wrap into its
# neighbour's block.
_REPLICA_PID_STRIDE = 1_000_000


def flight_to_chrome(records: list) -> list:
    """Flight records -> per-request Chrome tracks: one pid per
    request, named by rid and outcome; tid 0 carries the request's
    e2e span, tid 1 the stage events (spans where the event carries
    its own duration — queue wait, solve — instants otherwise).
    A MERGED fleet log (records from two or more replicas, each
    carrying the `replica` id obs/flight.py stamps) is GROUPED per
    replica: each replica gets its own pid block, so colliding
    per-process rids render one track per (replica, rid), named by
    both.  Single-replica logs keep the historical pid == rid
    mapping.  Raises ValueError on a malformed record (same CLI
    hygiene as the span-JSONL path)."""
    events: list = []
    replica_block: dict[str, int] = {}
    fleet = len({str(r.get("replica")) for r in records
                 if isinstance(r, dict) and r.get("replica")}) > 1
    stride = _REPLICA_PID_STRIDE
    if fleet:
        max_rid = max((r["rid"] for r in records
                       if isinstance(r, dict)
                       and isinstance(r.get("rid"), int)),
                      default=0)
        while stride <= max_rid:
            stride *= 10
    for i, rec in enumerate(records):
        if not is_flight_record(rec):
            raise ValueError(f"record {i} is not a flight record: "
                             f"{rec!r}")
        rid = rec["rid"]
        if not isinstance(rid, int):
            raise ValueError(f"record {i} rid not an int: {rid!r}")
        t0 = rec.get("t0_us", 0)
        if not isinstance(t0, (int, float)):
            raise ValueError(f"record {i} t0_us not numeric")
        outcome = rec.get("outcome") or "?"
        replica = rec.get("replica")
        if fleet and replica:
            block = replica_block.setdefault(
                str(replica), len(replica_block))
            rid = (block + 1) * stride + rid
            name = (f"replica {replica} request {rec['rid']} "
                    f"[{outcome}]")
        else:
            name = f"request {rid} [{outcome}]"
        if rec.get("failed_stage"):
            name += f" @{rec['failed_stage']}"
        events.append({"name": "process_name", "ph": "M", "pid": rid,
                       "tid": 0, "args": {"name": name}})
        meta = dict(rec.get("meta") or {})
        meta["error"] = rec.get("error")
        events.append({"name": f"request.{outcome}", "cat": "flight",
                       "ph": "X", "ts": t0,
                       "dur": max(0, int(rec.get("e2e_us") or 0)),
                       "pid": rid, "tid": 0, "args": meta})
        for ev in rec["events"]:
            if not isinstance(ev, dict) or "stage" not in ev:
                raise ValueError(
                    f"record {i} (rid {rid}) has a malformed "
                    f"event: {ev!r}")
            ts = t0 + int(ev.get("t_us", 0))
            args = {k: v for k, v in ev.items()
                    if k not in ("stage", "t_us")}
            wait = ev.get("wait_us")
            solve = ev.get("solve_us", ev.get("dur_us"))
            if isinstance(wait, (int, float)) and wait >= 0 \
                    and isinstance(solve, (int, float)) and solve >= 0:
                # the combined batcher event stamps its END after the
                # solve: [.. wait ..][.. solve ..]<ts
                events.append({"name": "queue.wait", "cat": "flight",
                               "ph": "X",
                               "ts": ts - int(solve) - int(wait),
                               "dur": int(wait), "pid": rid, "tid": 1,
                               "args": args})
                events.append({"name": "solve", "cat": "flight",
                               "ph": "X", "ts": ts - int(solve),
                               "dur": int(solve), "pid": rid,
                               "tid": 1, "args": args})
                continue
            dur = solve if solve is not None else wait
            if isinstance(dur, (int, float)) and dur >= 0:
                # the event stamps its END; the span covers [ts-dur, ts]
                events.append({"name": ev["stage"], "cat": "flight",
                               "ph": "X", "ts": ts - int(dur),
                               "dur": int(dur), "pid": rid, "tid": 1,
                               "args": args})
            else:
                events.append({"name": ev["stage"], "cat": "flight",
                               "ph": "i", "ts": ts, "pid": rid,
                               "tid": 1, "s": "t", "args": args})
    return events


def load(path: str) -> list:
    """Events from a Chrome trace JSON ({"traceEvents": [...]} or a
    bare array), a JSONL event log, or a flight-recorder JSONL
    (auto-detected; converted to per-request tracks).  Raises
    ValueError for content that is not a trace (a validator that
    certifies corrupt or empty artifacts as valid is worse than
    none)."""
    with open(path) as f:
        head = f.read(1)
        f.seek(0)
        if path.endswith(".jsonl"):
            events = [json.loads(line) for line in f if line.strip()]
            if not events:
                raise ValueError(f"{path}: empty JSONL event log")
            if any(is_export_snapshot(e) for e in events):
                # all-or-nothing, like the flight branch below
                return snapshots_to_chrome(events)
            if any(is_flight_record(e) for e in events):
                # all-or-nothing: a mixed log is corrupt, and
                # flight_to_chrome raises on the stragglers
                return flight_to_chrome(events)
            return events
        if head not in ("{", "["):
            raise ValueError(
                f"{path}: not a trace JSON "
                f"({'empty file' if not head else f'starts with {head!r}'})")
        doc = json.load(f)
    if isinstance(doc, dict):
        if "traceEvents" not in doc:
            raise ValueError(
                f"{path}: JSON object without a 'traceEvents' key")
        return doc["traceEvents"]
    return doc


def write_chrome(events: list, path: str, other: dict | None = None) -> str:
    doc = {"traceEvents": events, "displayTimeUnit": "ms",
           "otherData": dict(other or {},
                             producer="superlu_dist_tpu.obs")}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return path


def summarize(events: list) -> dict:
    """Per-span-name {count, total_ms}, compile-event count, tids."""
    by_name: dict[str, dict] = {}
    compiles = 0
    tids = set()
    for ev in events:
        if ev.get("ph") == "M":
            continue
        tids.add(ev.get("tid"))
        if ev.get("cat") == "compile":
            compiles += 1
        if ev.get("ph") != "X":
            continue
        rec = by_name.setdefault(ev["name"], {"count": 0,
                                              "total_ms": 0.0})
        rec["count"] += 1
        rec["total_ms"] = round(rec["total_ms"]
                                + ev.get("dur", 0) / 1e3, 3)
    return {"events": len(events), "threads": len(tids),
            "compile_events": compiles, "spans": by_name}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    out = None
    if "-o" in argv:
        i = argv.index("-o")
        if i + 1 >= len(argv):
            argv = []               # fall through to the usage path
        else:
            out = argv[i + 1]
            del argv[i:i + 2]
    if len(argv) != 1:
        print("usage: python -m tools.trace_export "
              "<trace.json|events.jsonl> [-o out.trace.json]",
              file=sys.stderr)
        return 2
    try:
        events = load(argv[0])
        validate_events(events)
    except (ValueError, json.JSONDecodeError, OSError) as e:
        print(f"trace_export: {argv[0]}: {e}", file=sys.stderr)
        return 1
    if out:
        write_chrome(events, out, other={"source": argv[0]})
    print(json.dumps(dict(summarize(events),
                          **({"wrote": out} if out else {})),
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
