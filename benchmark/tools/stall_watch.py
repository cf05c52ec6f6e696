#!/usr/bin/env python3
"""Where a served window's stall lies.  One process, set-up paid once,
then windows of the given lengths at the cell's own load, each on a
seed of its own, with a watcher thread beside the generator that
samples, every 20 ms:

  - its own lateness (a host that does not schedule it, or a thread
    that holds the interpreter, shows as an overshoot);
  - the innermost frames of every other thread (where the service's
    flusher and the generator stood);
  - once a second, the machine's CPU times from /proc/stat (steal,
    system, idle), this process's own CPU time and context switches,
    and the control group's throttling counters where there are any.

After each window the completions are laid on the window's clock: a
gap of `--stall` seconds or more in which no request completed is a
stall, and the samples inside it are printed.  One JSON line a window.

    python3 benchmark/tools/stall_watch.py --workload <cell> \
        --windows 51,51,51,30,30,30 [--rehearse-cpu]
"""

import argparse
import collections
import json
import os
import resource
import sys
import threading
import time

import numpy as np

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import harness  # noqa: E402

TICK = 0.02
CPU_FIELDS = ("user", "nice", "system", "idle", "iowait", "irq",
              "softirq", "steal")


def machine() -> dict:
    """Cumulative counters of the machine and of this process."""
    out = {"t": time.perf_counter(), "process_cpu_s": time.process_time()}
    try:
        with open("/proc/stat") as f:
            parts = f.readline().split()[1:1 + len(CPU_FIELDS)]
        out.update({k: int(v) / os.sysconf("SC_CLK_TCK")
                    for k, v in zip(CPU_FIELDS, parts)})
    except OSError:
        pass
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out.update(voluntary_switches=ru.ru_nvcsw,
               involuntary_switches=ru.ru_nivcsw,
               major_faults=ru.ru_majflt)
    try:
        with open("/sys/fs/cgroup/cpu.stat") as f:
            for line in f:
                k, v = line.split()
                if k in ("nr_throttled", "throttled_usec"):
                    out["cgroup_" + k] = int(v)
    except OSError:
        pass
    return out


def stack_of(frame, depth: int = 8) -> str:
    parts = []
    while frame is not None and len(parts) < depth:
        code = frame.f_code
        parts.append(f"{os.path.basename(code.co_filename)}:"
                     f"{code.co_name}:{frame.f_lineno}")
        frame = frame.f_back
    return " < ".join(parts)


class Watcher(threading.Thread):
    def __init__(self):
        super().__init__(name="stall_watch", daemon=True)
        self.samples: list = []     # (t, overshoot_s, {thread: stack})
        self.machine: list = []
        self._halt = threading.Event()

    def run(self):
        last = time.perf_counter()
        next_machine = last
        while not self._halt.is_set():
            time.sleep(TICK)
            now = time.perf_counter()
            names = {t.ident: t.name for t in threading.enumerate()}
            stacks = {names.get(i, str(i)): stack_of(f)
                      for i, f in sys._current_frames().items()
                      if i != self.ident}
            self.samples.append((now, now - last - TICK, stacks))
            last = now
            if now >= next_machine:
                self.machine.append(machine())
                next_machine = now + 1.0

    def stop(self):
        self._halt.set()
        self.join()
        self.machine.append(machine())


def between(records: list, a: float, b: float) -> dict:
    """Change of the machine's counters over the records that bracket
    [a, b], as seconds (CPU times) or counts."""
    inside = [r for r in records if a - 1.0 <= r["t"] <= b + 1.0]
    if len(inside) < 2:
        return {}
    first, last = inside[0], inside[-1]
    return {k: last[k] - first[k] for k in first
            if k in last and isinstance(first[k], (int, float))}


def stalls(done_rel: np.ndarray, least: float) -> list:
    """[start, end] of every gap of `least` seconds or more between two
    completions, on the window's clock."""
    t = np.sort(done_rel[np.isfinite(done_rel)])
    t = np.concatenate(([0.0], t))
    gaps = np.diff(t)
    return [[float(t[i]), float(t[i + 1])]
            for i in np.nonzero(gaps >= least)[0]]


def top_stacks(samples: list, a: float, b: float, top: int = 3) -> dict:
    by_thread: dict = {}
    for t, _over, stacks in samples:
        if a <= t <= b:
            for name, st in stacks.items():
                by_thread.setdefault(name, collections.Counter())[st] += 1
    return {name: c.most_common(top) for name, c in by_thread.items()}


def main(argv) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--windows", required=True,
                   help="window lengths in seconds, comma separated")
    p.add_argument("--first-seed", type=int, default=2_147_700_000)
    p.add_argument("--stall", type=float, default=1.0)
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args(argv)
    lengths = [float(x) for x in args.windows.split(",")]
    try:
        run, kind = harness.start(args.workload, args.first_seed,
                                  lengths[0], t_start=T_START,
                                  rehearse=args.rehearse_cpu)
    except harness.Refused as e:
        print(f"stall_watch: {e}. No result.", file=sys.stderr)
        return 2
    state = kind.setup(run)
    harness.settle(run)
    print(json.dumps({"setup_s": time.perf_counter() - T_START,
                      "device": run.device, "notes": run.notes,
                      "machine": machine(),
                      "cpus": len(os.sched_getaffinity(0))}), flush=True)
    seed = args.first_seed
    for wi, seconds in enumerate(lengths):
        seed += 7919
        run.seed, run.seconds = seed, seconds
        kind.reseed(run, state, seed)
        before = run.counters.snapshot()
        watch = Watcher()
        watch.start()
        with harness.GcWatch() as gcw:
            t0 = time.perf_counter()
            kind.window(run, state)
            t1 = time.perf_counter()
        watch.stop()
        compiled = harness.CompileCounters.delta(
            run.counters.snapshot(), before)
        lat = np.asarray(run.readings["latencies_in_due_order"])
        due = kind.arrivals(run.traffic["rate_per_s"], seconds,
                            run.traffic["gap_seed"], seed)
        done_rel = due[:len(lat)] + lat
        found = stalls(done_rel, args.stall)
        over = [o for _t, o, _s in watch.samples]
        hist = run.readings["serve_snapshot"]["histograms"]
        rec = {
            "window": wi, "seconds": seconds, "seed": seed,
            "serve_p50_s": run.readings.get("serve_p50_s"),
            "serve_p95_s": run.readings.get("serve_p95_s"),
            "latency_max_s": float(lat.max()) if len(lat) else None,
            "completed": int(len(lat)), "drain_s": run.notes["drain_s"],
            "generator_lag_max_s": run.notes["generator_lag_max_s"],
            "gc": gcw.notes(), "compiled_in_window": compiled,
            "watcher_overshoot_max_s": max(over, default=None),
            "watcher_overshoots_over_100ms": sum(o > 0.1 for o in over),
            # the service's histograms run on from window to window
            "device_solve_s_so_far": hist.get("serve.device_solve_s"),
            "queue_wait_s_so_far": hist.get("serve.queue_wait_s"),
            "machine_over_window": between(watch.machine, t0, t1),
            "stalls": [],
        }
        if wi == 0:
            # where the threads stand in a window without a stall
            rec["stacks_whole_window"] = top_stacks(watch.samples, t0, t1)
        for a, b in found:
            inside = [o for t, o, _s in watch.samples
                      if t0 + a <= t <= t0 + b]
            rec["stalls"].append({
                "from_s": a, "to_s": b,
                "watcher_samples": len(inside),
                "watcher_overshoot_max_s": max(inside, default=None),
                "machine": between(watch.machine, t0 + a, t0 + b),
                "stacks": top_stacks(watch.samples, t0 + a, t0 + b)})
        if args.rehearse_cpu:
            # never a time of the CPU under a device metric's name
            for k in ("serve_p50_s", "serve_p95_s"):
                rec.pop(k)
        print(json.dumps(rec), flush=True)
        v = kind.check(run, state)
        print(json.dumps({"window": wi, "attempted": v["attempted"],
                          "failed": v["failed"]}), flush=True)
    kind.close(run, state)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
