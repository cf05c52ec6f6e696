#!/usr/bin/env python3
"""A look by hand at the program's spans and scopes in a traced run:
where the chip's trace carries them (`prog_layout.json`: host threads
with their `slu.*` spans, and some device operations with every stat
they hold), what `progspans` reduces them to, its own programs and
spans by time, and an excerpt in `progspans`' loaded form around both
ends of the first `slu.solve.pack` (`prog_excerpt.json`;
`tests/data/prog_excerpt.json` was made this way).

    python3 benchmark/tools/prog_look.py .bench_out/trace/<cell> [kind]
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import progspans  # noqa: E402
import tracered  # noqa: E402


def raw_look(xplane_path: str, per_line: int = 12) -> dict:
    """Planes, lines and a few events of each device line with all
    their stats, as ProfileData shows them."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        lines = []
        for li, line in enumerate(plane.lines):
            n, sample, slu = 0, [], {}
            for ev in line.events:
                n += 1
                if ev.name.startswith("slu."):
                    slu[ev.name] = slu.get(ev.name, 0) + 1
                if plane.name.startswith(tracered.DEVICE_PREFIX) \
                        and len(sample) < per_line:
                    sample.append([ev.name[:400],
                                   {k: str(v)[:300]
                                    for k, v in ev.stats}])
            lines.append({"line": li, "name": line.name, "events": n,
                          "slu_spans": slu, "sample": sample})
        out.append({"plane": plane.name,
                    "stats": {k: str(v)[:200] for k, v in plane.stats},
                    "lines": lines})
    return {"planes": out}


def excerpt(loaded: dict, around: str = "slu.solve.pack",
            before: int = 300, after: int = 400) -> dict:
    """The loaded form cut to the device operations around both ends
    of the first span named `around`: `before` of them before each
    end and `after` after it (the factor program's last kernels, the
    first and the last packing programs, the first sweep's first)."""
    ops = sorted(loaded["ops"], key=lambda o: o[1])
    span = next((h for h in sorted(loaded["host"], key=lambda h: h[2])
                 if h[1] == around), None)
    if span is None or not ops:
        return {k: v[:before + after] for k, v in loaded.items()
                if k != "scope_stats"}
    keep, windows = [], []
    for edge in (span[2], span[3]):
        i = next((i for i, o in enumerate(ops) if o[1] >= edge),
                 len(ops))
        cut = [o for o in ops[max(0, i - before):i + after]
               if o not in keep]
        if cut:
            keep += cut
            windows.append((cut[0][1], max(o[2] for o in cut)))

    def inside(s, e):
        return any(s < t1 and e > t0 for t0, t1 in windows)

    return {
        "host": [h for h in loaded["host"] if inside(h[2], h[3])],
        "ops": keep,
        "inflight": [x for x in loaded["inflight"] if inside(*x)],
        "modules": [m for m in loaded["modules"]
                    if inside(m[1], m[2])],
    }


def main(argv) -> int:
    trace_dir = argv[0]
    kind = argv[1] if len(argv) > 1 else "step"
    path = tracered.find_xplane(trace_dir)
    with open(os.path.join(trace_dir, "prog_layout.json"), "w") as f:
        json.dump(raw_look(path), f)
    loaded = progspans.load(path)
    if loaded is None:
        print("no TPU plane in the trace")
        return 1
    scoped = sum(1 for o in loaded["ops"] if o[3])
    red = progspans.reduce_loaded(loaded, kind, 1)
    t0 = min(h[2] for h in loaded["host"]) if loaded["host"] else 0
    print(json.dumps({"ops": len(loaded["ops"]), "ops_scoped": scoped,
                      "scope_stats": loaded["scope_stats"],
                      # the program's own programs and spans by time,
                      # in ms after the first span
                      "slu_programs": [
                          [m[0].split("(")[0], (m[1] - t0) / 1e6,
                           (m[2] - t0) / 1e6]
                          for m in loaded["modules"] if "slu_" in m[0]
                      ][:60],
                      "slu_spans": [
                          [h[1], (h[2] - t0) / 1e6, (h[3] - t0) / 1e6]
                          for h in sorted(loaded["host"],
                                          key=lambda h: h[2])][:80],
                      "modules": len(loaded["modules"]),
                      "host_spans": len(loaded["host"]),
                      "threads": sorted({h[0] for h in loaded["host"]}),
                      "reduction": red}))
    with open(os.path.join(trace_dir, "prog_excerpt.json"), "w") as f:
        json.dump(excerpt(loaded), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
