"""From a profiler trace to numbers: device busy time, the longest
device operations, idle gaps by what the host was doing, collective
time, and the device time of the programs launched inside a host span.

Two stages, so that the arithmetic can be checked on a small recorded
trace without a profiler: `load_events` turns an `.xplane.pb` into
plain tuples, `reduce_events` does the rest.

An event is (plane, line, name, start_ns, dur_ns).  Device planes are
named `/device:TPU:<i>`; on each, the line `XLA Ops` holds one event
per operation that ran, `Async XLA Ops` one per copy or collective in
flight, and `XLA Modules` one per program execution.  Host planes hold
the benchmark's own `bench.*` TraceAnnotation spans, on the same clock.
The program has no `jax.named_scope`, so an operation's name is its
HLO instruction; `short_name` keeps the result's name and the opcode.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"      # copies and collectives in flight
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "all-to-all", "reduce-scatter", "collective-broadcast")


_OPCODE = re.compile(r"[\s)}\]]([a-z][a-z0-9\-]*)\(")


def short_name(name: str) -> str:
    """`%fusion.7 = f32[8]{0} fusion(...), kind=kLoop` -> `%fusion.7
    fusion`: an HLO instruction cut to its result and its opcode."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:120]
    m = _OPCODE.search(rhs)
    return f"{lhs} {m.group(1)}" if m else lhs


def load_events(xplane_path: str, span_prefix: str = "bench."):
    """Device-plane events of the three lines above and host events
    whose name starts with `span_prefix`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    out = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, ASYNC_LINE,
                                            MODULES_LINE):
                continue
            short: dict = {}
            for ev in line.events:
                name = ev.name
                if device or name.startswith(span_prefix):
                    if device and line.name != MODULES_LINE:
                        name = short.setdefault(name, short_name(name))
                    out.append((plane.name, line.name, name,
                                int(ev.start_ns),
                                int(ev.duration_ns)))
    return out


def _union(intervals):
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _overlap(a, b) -> int:
    """Total overlap of two sorted disjoint interval lists."""
    i = j = 0
    total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _is_collective(name: str) -> bool:
    base = name.lstrip("%")
    return any(base.startswith(c) for c in COLLECTIVES)


def _ops(lines: dict):
    """A device's operations: synchronous ones and those in flight."""
    return lines.get(OPS_LINE, []) + lines.get(ASYNC_LINE, [])


def reduce_events(events, n_devices: int) -> dict:
    """busy_s: seconds in which an operation ran, averaged over the
    device planes seen (at most n_devices), copies and collectives in
    flight included.  device_ops: the first device's synchronous
    operations by total seconds.  idle_gaps: the first
    device's idle seconds inside the traced span, by the `bench.*` host
    span that covered them (`no_span` where none did).  collective_s:
    seconds of collective operations on the first device.  span_device_s:
    for each host span name, the busy seconds of the first device
    inside the program executions that started during such a span."""
    by_plane: dict = {}
    host_spans: dict = {}
    for plane, line, name, start, dur in events:
        if plane.startswith(DEVICE_PREFIX):
            by_plane.setdefault(plane, {}).setdefault(line, []).append(
                (name, start, start + dur))
        else:
            host_spans.setdefault(name, []).append((start, start + dur))
    planes = sorted(by_plane, key=lambda p: int(p[len(DEVICE_PREFIX):]
                                                .split()[0]))[:n_devices]
    if not planes:
        return {"busy_s": None, "device_ops": [], "idle_gaps": [],
                "collective_s": None, "span_device_s": {},
                "n_planes": 0}
    busy = []
    for p in planes:
        u = _union([(s, e) for _, s, e in _ops(by_plane[p])])
        busy.append(sum(e - s for s, e in u))
    first = by_plane[planes[0]]
    ops = _ops(first)
    busy0 = _union([(s, e) for _, s, e in ops])

    by_name: dict = {}
    for name, s, e in first.get(OPS_LINE, []):
        by_name[name] = by_name.get(name, 0) + (e - s)
    device_ops = sorted(([n, t / 1e9] for n, t in by_name.items()),
                        key=lambda r: -r[1])

    # idle gaps between the first and the last operation seen
    gaps = [[busy0[i][1], busy0[i + 1][0]]
            for i in range(len(busy0) - 1)]
    idle_total = sum(e - s for s, e in gaps)
    idle = []
    for name, spans in host_spans.items():
        t = _overlap(gaps, _union(spans))
        if t:
            idle.append([name, t / 1e9])
    # spans on different threads may overlap one another, so what no
    # span covers is counted against their union
    all_spans = _union([iv for v in host_spans.values() for iv in v])
    idle.append(["no_span", (idle_total - _overlap(gaps, all_spans))
                 / 1e9])
    idle.sort(key=lambda r: -r[1])

    coll = _union([(s, e) for n, s, e in ops if _is_collective(n)])
    coll_s = sum(e - s for s, e in coll)

    modules = first.get(MODULES_LINE, [])
    span_device: dict = {}
    for span_name, spans in host_spans.items():
        inside = [[s, e] for _, s, e in modules
                  if any(hs <= s < he for hs, he in spans)]
        if inside:
            span_device[span_name] = _overlap(_union(inside), busy0) / 1e9
    return {"busy_s": sum(busy) / len(busy) / 1e9,
            "device_ops": device_ops, "idle_gaps": idle,
            "collective_s": coll_s / 1e9,
            "span_device_s": span_device, "n_planes": len(planes)}


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_trace_dir(trace_dir: str, n_devices: int) -> dict:
    return reduce_events(load_events(find_xplane(trace_dir)), n_devices)
