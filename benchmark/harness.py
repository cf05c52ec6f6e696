"""The harness: finds a cell's files by the names in BENCHMARK.json,
prepares the process (device check, compile cache, compile counters),
records spans, and reduces a run to the result line.

Everything that belongs to one configuration, one traffic mix, one
generator kind or one per-layer metric is a file of its own:

    configs/<config>.json        sizes, dtypes, options, grid, guarantees
    configs/gen_<generator>.py   the matrix generator a config names
    traffic/<traffic>.json       the mix's parameters, with its `kind`
    kinds/<kind>.py              the one general generator of that kind
    metrics/<metric>.py          read(run) -> number, or None; a
                                 metric split by what it moves
                                 (`x.step`, `x.serve`) may share
                                 metrics/x.py

so a later PR adds a cell by adding files and entries only.
"""

from __future__ import annotations

import contextlib
import enum
import gc
import importlib.util
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Refused(Exception):
    """The run cannot measure (no TPU, too few chips, no program): the
    command exits with a code other than 0 and prints no result."""


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(name: str, *parts):
    """Import one of the benchmark's own files by path (metric names
    hold dots, so these are not importable by name)."""
    path = os.path.join(HERE, *parts)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The reader of a per-layer metric: metrics/<name>.py, or, for a
    quantity that BENCHMARK.json splits by the end-to-end metric it
    moves (`window_compiles.step`, `window_compiles.serve`), the one
    file of the name before its last dot."""
    for stem in (name, name.rpartition(".")[0]):
        if stem and os.path.exists(os.path.join(HERE, "metrics",
                                                stem + ".py")):
            return load_module("metric_" + stem, "metrics", stem + ".py")
    raise FileNotFoundError(f"no reader for the metric {name!r} under "
                            "benchmark/metrics")


def load_cell(workload: str) -> dict:
    """The cell's entry, configuration, traffic and metric entries."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json; "
                      f"there are {sorted(cells)}")
    cell = cells[workload]
    centry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, centry["file"])) as f:
        config = json.load(f)

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell, "config": config,
        "traffic": load_json("traffic", cell["traffic"] + ".json"),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


class CompileCounters:
    """jax.monitoring listeners (copied from chip_smoke.py).
    `backend_compiles` counts every jit-cache miss that reached the
    backend, served by the persistent cache or not; `cache_hits` /
    `cache_misses` are the persistent cache's own events."""

    _EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache":
            "cache_requests",
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self, jax):
        self.n = {"backend_compiles": 0, "cache_requests": 0,
                  "cache_hits": 0, "cache_misses": 0}
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)

    def _event(self, event, **_kw):
        key = self._EVENTS.get(event)
        if key:
            self.n[key] += 1

    def _duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n["backend_compiles"] += 1
            self.compile_s += secs

    def snapshot(self) -> dict:
        return dict(self.n, compile_s=self.compile_s)

    @staticmethod
    def delta(after: dict, before: dict) -> dict:
        return {k: after[k] - before[k] for k in after}


class GcWatch:
    """Pauses of Python's cyclic collector inside the window, for the
    result line's notes: a full collection over the millions of
    objects that tracing leaves behind stalls every thread."""

    def __init__(self):
        self.pauses: list[float] = []
        self._t0 = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t0)

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def notes(self) -> dict:
        return {"count": len(self.pauses),
                "total_s": sum(self.pauses),
                "max_s": max(self.pauses, default=0.0)}


def place_cache(jax) -> str:
    """JAX's persistent compilation cache: where
    JAX_COMPILATION_CACHE_DIR says, else the fixed in-checkout path the
    program's own helper uses.  The thresholds are lowered in this
    process so that the ~800 small programs of a start are cached too
    and only a cell's first run in a checkout compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache-accel")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def make_options(slu, fields: dict):
    """Options from a configuration's `options`: enum fields by name."""
    base = slu.Options()
    kw = {}
    for k, v in fields.items():
        cur = getattr(base, k)      # AttributeError names a bad key
        kw[k] = type(cur)[v] if isinstance(cur, enum.Enum) else v
    return base.replace(**kw)


class Spans:
    """Host spans on the host clock, kept in memory, and written into
    the profiler's trace as well (TraceAnnotation) so that idle gaps on
    the device can be laid at what the host was doing."""

    def __init__(self, jax):
        self._annot = jax.profiler.TraceAnnotation
        self.by_name: dict[str, list[float]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with self._annot(name):
            yield
        self.by_name.setdefault(name, []).append(
            time.perf_counter() - t0)

    def median(self, name: str):
        v = self.by_name.get(name)
        return statistics.median(v) if v else None

    def total(self, name: str):
        v = self.by_name.get(name)
        return sum(v) if v else None


class Run:
    """One run's shared state, handed to the kind and to the readers."""

    def __init__(self, spec: dict, seed: int, seconds: float,
                 trace: bool, rehearse: bool, t_start: float,
                 control: str | None = None):
        self.spec = spec
        self.cell = spec["cell"]
        self.config = spec["config"]
        self.traffic = spec["traffic"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.rehearse = rehearse
        self.control = control
        self.t_start = t_start
        self.readings: dict = {}      # what the readers read
        self.notes: dict = {}         # extra keys of the result line

    # -- the process ------------------------------------------------
    def open(self):
        """Import jax and the program, check the device, place the
        compile cache.  Raises Refused where nothing may be measured."""
        try:
            import jax
            import superlu_dist_tpu as slu
        except ImportError as e:
            raise Refused(f"cannot import jax or the program: {e}")
        self.jax, self.slu = jax, slu
        devs = jax.devices()
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        need = int(self.cell["chips"])
        if self.rehearse:
            if self.device["platform"] != "cpu":
                raise Refused("--rehearse-cpu is for JAX_PLATFORMS=cpu")
        elif self.device["platform"] != "tpu":
            raise Refused("needs a TPU; jax's default platform is "
                          f"{self.device['platform']!r}")
        if len(devs) < need:
            raise Refused(f"the cell needs {need} chips; jax found "
                          f"{len(devs)}")
        self.devices = devs[:need]
        self.cache_dir = None if self.rehearse else place_cache(jax)
        self.counters = CompileCounters(jax)
        self.spans = Spans(jax)
        peaks = load_json("peaks.json")
        if not self.rehearse and self.device["kind"] not in peaks:
            raise Refused(f"device kind {self.device['kind']!r} is not "
                          "in peaks.json")
        self.peaks = peaks.get(self.device["kind"])
        from superlu_dist_tpu.utils import native
        with self.spans.span("bench.native_library"):
            if not native.available():
                raise Refused("the native host library did not build")

    def options(self):
        fields = dict(self.config["options"])
        if self.control:
            fields.update(self.config["controls"][self.control])
        return make_options(self.slu, fields)

    def grid(self):
        g = self.config.get("grid")
        return self.slu.make_solver_mesh(*g, devices=self.devices) \
            if g else None

    def matrix(self):
        m = self.config["matrix"]
        args = dict(m["args"])
        if self.rehearse:
            args.update(self.config["rehearsal_matrix_args"])
        gen = load_module("gen_" + m["generator"], "configs",
                          "gen_" + m["generator"] + ".py")
        return gen.generate(**args)

    def memory_peak_bytes(self):
        peaks = []
        for d in self.devices:
            ms = d.memory_stats()
            if ms:
                peaks.append(int(ms.get("peak_bytes_in_use", 0)))
        return max(peaks) if peaks else None

    # -- the traced sub-window ---------------------------------------
    def trace_dir(self) -> str:
        return os.path.join(ROOT, ".bench_out", "trace",
                            self.cell["name"])

    def start_trace(self):
        import shutil
        shutil.rmtree(self.trace_dir(), ignore_errors=True)
        os.makedirs(self.trace_dir(), exist_ok=True)
        # no Python call tracer (it slows the host it measures) and no
        # HLO protos (the factor program's is tens of megabytes)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        self.jax.profiler.start_trace(self.trace_dir(),
                                      profiler_options=opts)
        self._trace_t0 = time.perf_counter()

    def stop_trace(self):
        # writing the trace out takes many seconds: not the window's
        self.readings["trace_window_s"] = (time.perf_counter()
                                           - self._trace_t0)
        self.jax.profiler.stop_trace()


def percentile(values, p: float) -> float:
    """Nearest-rank percentile of all values."""
    s = sorted(values)
    rank = max(1, -(-len(s) * p // 100))
    return float(s[int(rank) - 1])


def result_line(run: Run, verdict: dict, metrics: dict) -> dict:
    device = dict(run.device,
                  memory_peak_bytes=run.memory_peak_bytes())
    red = run.readings.get("trace")
    out = {
        "correct": bool(verdict["failed"] == 0
                        and verdict["attempted"] > 0
                        and verdict["splu_compared"] > 0),
        "attempted": verdict["attempted"], "failed": verdict["failed"],
        "metrics": metrics, "device": device,
        "compared": verdict["compared"],
        "workload": run.cell["name"], "seed": run.seed,
        "notes": run.notes,
    }
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = run.readings["trace_window_s"]
        out["breakdown"] = {"device_ops": red["device_ops"][:10],
                            "idle_gaps": red["idle_gaps"][:10]}
    return out


def start(workload: str, seed: int, seconds: float, *, t_start: float,
          trace: bool = False, rehearse: bool = False,
          control: str | None = None):
    """A run opened on its device, and its generator kind's module."""
    run = Run(load_cell(workload), seed, seconds, trace, rehearse,
              t_start, control)
    run.open()
    kind = load_module("kind_" + run.traffic["kind"], "kinds",
                       run.traffic["kind"] + ".py")
    return run, kind


def histogram(run: Run, name: str, stat: str):
    """One statistic of one of the service's histograms, or None
    where it holds no sample."""
    h = run.readings["serve_snapshot"]["histograms"].get(name)
    return h[stat] if h and h.get("count") else None


def settle(run: Run) -> None:
    """Ends set-up.  Tracing and compiling leave millions of objects
    behind, and the first full pass of Python's collector over them
    stops every thread for as long as it takes: that pass is set-up's
    to pay, not a request's.  How long it took rides the notes."""
    t0 = time.perf_counter()
    gc.collect()
    run.notes["setup_gc_collect_s"] = time.perf_counter() - t0
    run.notes["objects_after_setup"] = len(gc.get_objects())


def execute(run: Run, kind) -> dict:
    """Set-up, the window, the check, the metrics: one run."""
    c0 = run.counters.snapshot()
    state = kind.setup(run)
    settle(run)
    c1 = run.counters.snapshot()
    run.readings["setup_s"] = time.perf_counter() - run.t_start
    with GcWatch() as watch:
        kind.window(run, state)
    run.notes["gc_pauses_in_window"] = watch.notes()
    run.readings["setup_compile"] = run.notes["setup_compile"] = \
        CompileCounters.delta(c1, c0)
    run.readings["window_compile"] = run.notes["window_compile"] = \
        CompileCounters.delta(run.counters.snapshot(), c1)
    verdict = kind.check(run, state)
    for c in verdict["compared"]:
        print(f"compared {c['name']}: {c['value']:.6g} "
              f"(limit {c['limit']:.6g})", flush=True)
    if run.trace:
        from tracered import reduce_trace_dir
        run.readings["trace"] = reduce_trace_dir(
            run.trace_dir(), len(run.devices))
        values = {m["name"]: metric_reader(m["name"]).read(run)
                  for m in run.spec["per_layer"]}
    else:
        # end-to-end metrics are the benchmark's own readings
        values = {m["name"]: run.readings.get(m["name"])
                  for m in run.spec["end_to_end"]}
    units = {m["name"]: m["unit"] for m in
             run.spec["per_layer"] + run.spec["end_to_end"]}
    metrics = {name: {"value": v, "unit": units[name]}
               for name, v in values.items() if v is not None}
    kind.close(run, state)
    return result_line(run, verdict, metrics)


def main(argv, t_start: float) -> int:
    import argparse
    p = argparse.ArgumentParser(description="one run of one cell")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="tiny CPU rehearsal for the benchmark's own "
                        "tests: prints no metric")
    p.add_argument("--control", default=None,
                   help="run one of the configuration's `controls` "
                        "(a lower precision) in the program's place; "
                        "for the control tests only")
    args = p.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, ROOT)
    try:
        run, kind = start(args.workload, args.seed, args.seconds,
                          t_start=t_start, trace=bool(args.trace),
                          rehearse=args.rehearse_cpu,
                          control=args.control)
        line = execute(run, kind)
    except Refused as e:
        print(f"benchmark: {e}. No result.", file=sys.stderr)
        return 2
    if args.rehearse_cpu:
        # never a metric under a device's name
        line = {"rehearsal": True, "correct": line["correct"],
                "attempted": line["attempted"],
                "failed": line["failed"],
                "metric_names": sorted(line["metrics"]),
                "compared": line["compared"], "device": run.device}
    elif args.control:
        line["control"] = args.control
    print(json.dumps(line), flush=True)
    return 0
