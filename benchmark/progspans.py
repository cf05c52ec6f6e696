"""The program's own spans and scopes in a traced run.

While a profiler session is live the program writes `slu.*` host spans
(`obs.span`) into the profiler's trace, on the clock of the device
operations, and its kernels carry `jax.named_scope` names (`slu.fwd`,
`slu.partial_lu`, ...) in their operations' metadata.  This file reads
both from the `.xplane.pb` and reduces them:

  * host seconds and self seconds by span name;
  * the first device's idle seconds by the innermost `slu.*` span that
    covers them on its thread (for a parent span that is its self
    time);
  * the first device's busy seconds inside the programs that run
    during a span (`tracered.reduce_events`' `span_device_s`, with one
    difference: a program is the span's when most of it lies inside,
    not when its first instant does; the host's and the device's
    clocks agree to about a millisecond, and a sweep's program starts
    half a millisecond after its span opens);
  * device seconds by scope, for the programs run during a span.

Two stages, as in tracered.py: `load` turns the trace into plain lists
(kept small enough to record an excerpt as JSON), the rest is
arithmetic on them.  A program without spans or scopes (the parent of
the PR that brought them) gives empty tables, never an error; a trace
without a `/device:TPU:` plane gives None.

Loaded form:
    {"host": [[thread, name, start_ns, end_ns, stats], ...],
     "ops": [[name, start_ns, end_ns, scope or None], ...],
     "inflight": [[start_ns, end_ns], ...],
     "modules": [[name, start_ns, end_ns], ...],
     "scope_stats": [name of the stat that held a scope, ...]}
`thread` is "<plane>#<line index>": a thread's line has no other
identity in ProfileData.  `ops`, `inflight` and `modules` are the first
device's `XLA Ops`, `Async XLA Ops` and `XLA Modules` lines.

Where the chip's trace carries a scope (TPU v5 lite, jax 0.9.0, no HLO
protos): a device event's name is its HLO instruction without
metadata, and its own stats are times only.  The instruction's
`op_name` (`jit(slu_factor)/slu.partial_lu/while/body/...`) is a text
stat of the event's METADATA entry in the plane, which ProfileData
does not show; `event_scopes` reads that table from the file's bytes.
"""

from __future__ import annotations

import re

import tracered
from tracered import _overlap, _union

SPAN_PREFIXES = ("slu.", "bench.")
UNIT_SPAN = {"serve_open": "slu.serve.batch"}   # by generator kind
NO_SPAN = "no_span"
UNNAMED = "unnamed"

_SCOPE = re.compile(r"slu\.[a-z_]+")


def scope_of(op_name: str) -> str | None:
    """The innermost `slu.<kernel>` scope of an operation's name:
    `jit(slu_factor)/slu.partial_lu/slu.schur/dot_general` ->
    `slu.schur`."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


# -- the plane's event-metadata table, from the file's bytes ----------
# XSpace.planes=1; XPlane: name=2, event_metadata=4 (map: key=1,
# value=2), stat_metadata=5; XEventMetadata: name=2, stats=5;
# XStat: metadata_id=1, str_value=5; XStatMetadata: name=2.

def _varint(buf, i: int):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: a varint's
    number, the bytes of a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} in an XSpace")
            value, i = buf[i:i + size], i + size
        yield tag >> 3, value


def _field(buf, number: int):
    return next((v for f, v in _fields(buf) if f == number), None)


def event_scopes(xplane_path: str, plane_name: str) -> dict:
    """{event name: scope} for the plane's event metadata whose text
    stats hold a `slu.` scope, and under "" the names of the stats
    that held one.  An instruction text that two programs share keeps
    the first scope seen."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for number, plane in _fields(space):
        if number != 1 or bytes(_field(plane, 2) or b"") \
                != plane_name.encode():
            continue
        stat_names, held = {}, set()
        for f, entry in _fields(plane):
            if f == 5:
                stat_names[_field(entry, 1)] = bytes(
                    _field(_field(entry, 2), 2) or b"").decode()
        for f, entry in _fields(plane):
            if f != 4:
                continue
            meta = _field(entry, 2)
            name = None
            for mf, mv in _fields(meta):
                if mf == 2:
                    name = bytes(mv).decode(errors="replace")
                elif mf == 5:
                    text = _field(mv, 5)
                    scope = text is not None and scope_of(
                        bytes(text).decode(errors="replace"))
                    if scope and name is not None:
                        out.setdefault(name, scope)
                        held.add(stat_names.get(_field(mv, 1), "?"))
        out[""] = sorted(held)
    return out


def _first_device(planes):
    devs = [p for p in planes
            if p.name.startswith(tracered.DEVICE_PREFIX)]
    if not devs:
        return None
    return min(devs, key=lambda p: int(
        p.name[len(tracered.DEVICE_PREFIX):].split()[0]))


def load(xplane_path: str) -> dict | None:
    """The loaded form above, or None without a device plane."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(xplane_path).planes)
    dev = _first_device(planes)
    if dev is None:
        return None
    out: dict = {"host": [], "ops": [], "inflight": [], "modules": []}
    for plane in planes:
        if plane.name.startswith(tracered.DEVICE_PREFIX):
            continue
        for li, line in enumerate(plane.lines):
            thread = f"{plane.name}#{li}"
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIXES):
                    s = int(ev.start_ns)
                    out["host"].append(
                        [thread, ev.name, s, s + int(ev.duration_ns),
                         {k: v for k, v in ev.stats
                          if isinstance(v, (int, float, str))}])
    lines = {line.name: line for line in dev.lines}

    def events(line_name):
        for ev in getattr(lines.get(line_name), "events", ()):
            s = int(ev.start_ns)
            yield ev.name, s, s + int(ev.duration_ns)

    out["modules"] = sorted(([n, s, e] for n, s, e in
                             events(tracered.MODULES_LINE)),
                            key=lambda m: m[1])
    out["inflight"] = [[s, e] for _, s, e in
                       events(tracered.ASYNC_LINE)]
    scopes = event_scopes(xplane_path, dev.name)
    out["scope_stats"] = scopes.pop("", [])
    short: dict = {}
    for name, s, e in events(tracered.OPS_LINE):
        if name not in short:
            short[name] = tracered.short_name(name)
        out["ops"].append([short[name], s, e, scopes.get(name)])
    out["ops"].sort(key=lambda o: o[1])
    return out


# -- arithmetic on the loaded form -----------------------------------

def by_thread(host, prefix: str = "slu.") -> dict:
    """{thread: [(start, end, name), ...]} of the spans under a
    prefix, outer spans before the ones they hold."""
    out: dict = {}
    for thread, name, s, e, _ in host:
        if name.startswith(prefix):
            out.setdefault(thread, []).append((s, e, name))
    for spans in out.values():
        spans.sort(key=lambda t: (t[0], -t[1]))
    return out


def innermost(spans) -> list:
    """One thread's nested spans (sorted as `by_thread` gives them)
    flattened: disjoint (start, end, name) pieces, each named for the
    innermost span open over it."""
    out, stack = [], []          # stack of (end, name)
    cursor = 0

    def emit(until):
        nonlocal cursor
        if stack and until > cursor:
            out.append((cursor, until, stack[-1][1]))
        cursor = max(cursor, until)

    for s, e, name in spans:
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append((e, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def host_seconds(host) -> dict:
    """{name: [seconds, self seconds, count]} of the `slu.*` spans."""
    out: dict = {}
    for spans in by_thread(host).values():
        for s, e, name in spans:
            rec = out.setdefault(name, [0.0, 0.0, 0])
            rec[0] += (e - s) / 1e9
            rec[2] += 1
        for s, e, name in innermost(spans):
            out[name][1] += (e - s) / 1e9
    return out


def inside(host, parent: str) -> list:
    """The host spans that lie inside a `parent` span of their own
    thread, the parents themselves included."""
    parents: dict = {}
    for thread, name, s, e, _ in host:
        if name == parent:
            parents.setdefault(thread, []).append((s, e))
    return [ev for ev in host
            if any(ps <= ev[2] and ev[3] <= pe
                   for ps, pe in parents.get(ev[0], ()))]


def busy(loaded) -> list:
    """Sorted disjoint intervals in which the first device ran an
    operation, copies and collectives in flight included."""
    return _union([(s, e) for _, s, e, _ in loaded["ops"]]
                  + [(s, e) for s, e in loaded["inflight"]])


def idle_by_span(loaded, b=None) -> dict:
    """The first device's idle seconds between its first and last
    operation, by the innermost `slu.*` span covering them:
    {"idle_s", "attributed_s" (under any such span), "by_span":
    [[name, seconds], ...] largest first, NO_SPAN among them}.  Spans
    of two threads may cover one gap; each thread's span is then
    given the seconds, and `attributed_s` counts them once.  `b`:
    `busy(loaded)`, where the caller has it."""
    if b is None:
        b = busy(loaded)
    gaps = [[b[i][1], b[i + 1][0]] for i in range(len(b) - 1)]
    total = sum(e - s for s, e in gaps)
    by_name: dict = {}
    pieces = []
    for spans in by_thread(loaded["host"]).values():
        flat = innermost(spans)
        pieces += [(s, e) for s, e, _ in flat]
        for name in {n for _, _, n in flat}:
            t = _overlap(gaps, [[s, e] for s, e, n in flat
                                if n == name])
            if t:
                by_name[name] = by_name.get(name, 0) + t
    covered = _overlap(gaps, _union(pieces))
    rows = [[n, t / 1e9] for n, t in by_name.items()]
    rows.append([NO_SPAN, (total - covered) / 1e9])
    rows.sort(key=lambda r: -r[1])
    return {"idle_s": total / 1e9, "attributed_s": covered / 1e9,
            "by_span": rows}


def run_inside(loaded, spans) -> list:
    """The program executions of the first device of which more than
    half lies inside the (start, end) host intervals, as sorted
    disjoint [start, end]."""
    cover = _union(spans)
    return _union([(s, e) for _, s, e in loaded["modules"]
                   if 2 * _overlap([[s, e]], cover) > e - s])


def span_device_s(loaded, host, name: str, b=None) -> float | None:
    """Busy seconds of the first device inside the programs that ran
    during a span of this name among `host`; None where no such span
    is there.  `b`: `busy(loaded)`, where the caller has it."""
    spans = [(s, e) for _, n, s, e, _ in host if n == name]
    if not spans:
        return None
    if b is None:
        b = busy(loaded)
    return _overlap(run_inside(loaded, spans), b) / 1e9


def scope_seconds(loaded, name: str) -> dict:
    """{scope: seconds} of the operations inside the programs that
    ran during a host span of this name, each operation counted for
    the time no operation nested in it (a loop's body) runs; UNNAMED
    for operations without a scope."""
    spans = [(s, e) for _, n, s, e, _ in loaded["host"] if n == name]
    progs = run_inside(loaded, spans)
    out: dict = {}
    stack: list = []             # [end, scope, self_ns]

    def close():
        end, scope, self_ns = stack.pop()
        key = scope or UNNAMED
        out[key] = out.get(key, 0) + self_ns / 1e9

    pi = 0
    for _, s, e, scope in sorted(loaded["ops"],
                                 key=lambda o: (o[1], -o[2])):
        while pi < len(progs) and progs[pi][1] <= s:
            pi += 1
        if pi == len(progs) or s < progs[pi][0]:
            continue
        while stack and stack[-1][0] <= s:
            close()
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, scope, e - s])
    while stack:
        close()
    return out


# -- one run ----------------------------------------------------------

def reduce_loaded(loaded, kind: str, traced_steps) -> dict:
    """What the readers read.  A unit is a traced step, or for a
    served run a `slu.serve.batch` span; spans outside every batch
    (a batch cut by the trace's start) are left out of `unit_*`."""
    host = loaded["host"]
    unit_span = UNIT_SPAN.get(kind)
    if unit_span:
        units = sum(1 for ev in host if ev[1] == unit_span)
        unit_host = inside(host, unit_span)
    else:
        units, unit_host = traced_steps, host
    b = busy(loaded)             # a sort of every operation: once
    host_s = host_seconds(host)
    return {
        "units": units,
        "host_s": host_s,
        "unit_host_s": (host_s if unit_host is host
                        else host_seconds(unit_host)),
        "unit_sweep_device_s": span_device_s(loaded, unit_host,
                                             "slu.solve.sweep", b),
        "idle": idle_by_span(loaded, b),
        "factor_scopes": scope_seconds(loaded, "bench.factorize"),
    }


def reduction(run) -> dict | None:
    """The traced run's reduction, made once and kept in
    `run.readings`; None where the trace holds no TPU plane."""
    if "progspans" not in run.readings:
        loaded = load(tracered.find_xplane(run.trace_dir()))
        run.readings["progspans"] = None if loaded is None else \
            reduce_loaded(loaded, run.traffic["kind"],
                          run.readings.get("traced_steps"))
    return run.readings["progspans"]


def unit_seconds(run, name: str) -> float | None:
    """Host seconds in spans of this name per step or batch; None
    where the trace has no such span (or no TPU plane)."""
    red = reduction(run)
    rec = red and red["unit_host_s"].get(name)
    return rec[0] / red["units"] if rec and red["units"] else None
