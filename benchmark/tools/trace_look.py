#!/usr/bin/env python3
"""A first look at a traced run by hand: the trace's layout (planes,
lines, event counts), its programs by device seconds, and an excerpt of
its events, written beside the trace as `layout.json` and
`excerpt.json`.  `tests/data/step_excerpt.json` was made this way.

    python3 benchmark/tools/trace_look.py .bench_out/trace/<cell>
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import tracered  # noqa: E402


def layout(xplane_path: str) -> list:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    return [[plane.name, [[line.name, sum(1 for _ in line.events)]
                          for line in plane.lines]]
            for plane in data.planes]


def modules(events, cap: int = 20) -> list:
    """Program executions by name: [name, count, seconds]."""
    by_name: dict = {}
    for _, line, name, _, dur in events:
        if line == tracered.MODULES_LINE:
            rec = by_name.setdefault(name, [0, 0])
            rec[0] += 1
            rec[1] += dur
    return sorted(([n, c, t / 1e9] for n, (c, t) in by_name.items()),
                  key=lambda r: -r[2])[:cap]


def excerpt(events, span_s: float = 1.0, cap: int = 1500) -> list:
    """The events of the first `span_s` seconds after the first device
    operation, at most `cap` of them."""
    dev = [e for e in events if e[0].startswith(tracered.DEVICE_PREFIX)]
    if not dev:
        return [list(e) for e in events[:cap]]
    t0 = min(e[3] for e in dev)
    keep = [e for e in events if t0 <= e[3] < t0 + span_s * 1e9
            or (e[3] < t0 < e[3] + e[4])]
    keep.sort(key=lambda e: e[3])
    return [list(e) for e in keep[:cap]]


def main(argv) -> int:
    trace_dir = argv[0]
    path = tracered.find_xplane(trace_dir)
    events = tracered.load_events(path)
    with open(os.path.join(trace_dir, "layout.json"), "w") as f:
        json.dump({"layout": layout(path), "modules": modules(events),
                   "n_events": len(events)}, f)
    with open(os.path.join(trace_dir, "excerpt.json"), "w") as f:
        json.dump(excerpt(events), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
