"""The reduction of the program's spans and scopes: exact arithmetic
on a hand-made trace (two host threads, nested spans, gaps), the
loader on a small .xplane.pb written here, and a look at an excerpt
recorded on the chip (`data/prog_excerpt.json`: both ends of the
`slu.solve.pack` of a traced step of lap3d_k30.step on a TPU v5 lite,
PR 26)."""

import json
import os
import types

import pytest

import progspans
from conftest import HERE

MAIN, FLUSH = "/host:CPU#0", "/host:CPU#1"
US = 1e-6


def span(thread, name, start_us, dur_us, **stats):
    return [thread, name, start_us * 1000, (start_us + dur_us) * 1000,
            stats]


def op(name, start_us, dur_us, scope=None):
    return [name, start_us * 1000, (start_us + dur_us) * 1000, scope]


def module(name, start_us, dur_us):
    return [name, start_us * 1000, (start_us + dur_us) * 1000]


# One served batch on the flusher's thread and one step on the
# caller's, against one device.
HAND_MADE = {
    "host": [
        # the caller's thread: a step, and the benchmark's own spans
        span(MAIN, "bench.factorize", 0, 100),
        span(MAIN, "slu.FACT", 10, 80),
        span(MAIN, "bench.solve", 100, 400),
        span(MAIN, "slu.SOLVE", 100, 200),
        span(MAIN, "slu.solve.pack", 110, 100, groups=3),
        span(MAIN, "slu.solve.sweep", 220, 70, nrhs=1, trans=0),
        span(MAIN, "slu.solve.fetch", 250, 40),
        span(MAIN, "slu.REFINE", 300, 200),
        span(MAIN, "slu.refine.residual", 310, 20),
        span(MAIN, "slu.REFINE_STEP", 340, 100),
        span(MAIN, "slu.solve.sweep", 350, 50, nrhs=1, trans=0),
        span(MAIN, "slu.solve.fetch", 370, 30),
        span(MAIN, "slu.refine.residual", 410, 20),
        # the flusher's thread: a wait, one whole batch, and a sweep
        # of a batch that the trace's start cut
        span(FLUSH, "slu.solve.sweep", 560, 30, nrhs=8, trans=0),
        span(FLUSH, "slu.serve.wait", 600, 100),
        span(FLUSH, "slu.serve.batch", 700, 200, batch=7, live=5,
             bucket=8),
        span(FLUSH, "slu.serve.assemble", 700, 10),
        span(FLUSH, "slu.serve.batch_solve", 710, 170),
        span(FLUSH, "slu.solve.sweep", 720, 100, nrhs=8, trans=0),
        span(FLUSH, "slu.refine.residual", 830, 40),
        span(FLUSH, "slu.serve.fanout", 880, 20),
    ],
    "modules": [
        module("jit_slu_factor(1)", 20, 70),
        module("jit_dynamic_slice(2)", 150, 10),
        module("jit_slu_solve_packed(3)", 230, 50),
        module("jit_slu_solve_packed(3)", 355, 40),
        module("jit_slu_solve_packed(4)", 730, 80),
    ],
    "ops": [
        # the factor program: a loop holding two operations, then an
        # unnamed copy after a 5 us gap
        op("%while.1 while", 20, 50, "slu.extend_add"),
        op("%fusion.1 fusion", 25, 10, "slu.partial_lu"),
        op("%fusion.2 fusion", 40, 20, "slu.schur"),
        op("%copy.1 copy", 75, 15),
        op("%slice.1 dynamic-slice", 150, 10),
        op("%fusion.7 fusion", 230, 50, "slu.fwd"),
        op("%fusion.7 fusion", 355, 40, "slu.fwd"),
        op("%fusion.8 fusion", 730, 80, "slu.bwd"),
    ],
    "inflight": [[275 * 1000, 285 * 1000]],
}


def test_innermost_and_self_time():
    flat = progspans.innermost(
        progspans.by_thread(HAND_MADE["host"])[MAIN])
    at = {s // 1000: name for s, _, name in flat}
    assert at[10] == "slu.FACT" and at[100] == "slu.SOLVE"
    assert at[110] == "slu.solve.pack" and at[210] == "slu.SOLVE"
    assert at[220] == "slu.solve.sweep" and at[250] == "slu.solve.fetch"
    # pieces are disjoint and ordered
    assert all(a[1] <= b[0] for a, b in zip(flat, flat[1:]))
    host = progspans.host_seconds(HAND_MADE["host"])
    assert host["slu.solve.pack"] == pytest.approx([100 * US,
                                                    100 * US, 1])
    # SOLVE holds pack (100) and a sweep (70) of its 200 us
    assert host["slu.SOLVE"][:2] == pytest.approx([200 * US, 30 * US])
    # the sweeps: 70 + 50 on the caller's thread, 30 + 100 on the
    # flusher's; self time leaves the fetches out
    assert host["slu.solve.sweep"] == pytest.approx(
        [250 * US, 180 * US, 4])
    assert host["slu.refine.residual"][2] == 3
    assert "bench.solve" not in host


def test_idle_by_innermost_span():
    idle = progspans.idle_by_span(HAND_MADE)
    # busy: [20,70] [75,90] [150,160] [230,285] [355,395] [730,810]
    # gaps: [70,75] [90,150] [160,230] [285,355] [395,730] = 540 us
    assert idle["idle_s"] == pytest.approx(540 * US)
    by = dict(idle["by_span"])
    assert by["slu.FACT"] == pytest.approx(5 * US)
    assert by["slu.solve.pack"] == pytest.approx((40 + 50) * US)
    # a parent is given its self time: SOLVE 100-110, 210-220, 290-300
    assert by["slu.SOLVE"] == pytest.approx(30 * US)
    # 220-230, 350-355 on the caller's thread; 560-590, 720-730 on
    # the flusher's
    assert by["slu.solve.sweep"] == pytest.approx(55 * US)
    assert by["slu.solve.fetch"] == pytest.approx(10 * US)
    assert by["slu.REFINE"] == pytest.approx(80 * US)
    assert by["slu.refine.residual"] == pytest.approx(40 * US)
    assert by["slu.REFINE_STEP"] == pytest.approx(30 * US)
    assert by["slu.serve.wait"] == pytest.approx(100 * US)
    assert by["slu.serve.assemble"] == pytest.approx(10 * US)
    assert by["slu.serve.batch_solve"] == pytest.approx(10 * US)
    assert "slu.serve.batch" not in by      # its stages cover it
    # under no slu span: 90-100 and 500-560 + 590-600; bench.solve is
    # the benchmark's span, not the program's
    assert by[progspans.NO_SPAN] == pytest.approx(80 * US)
    assert idle["attributed_s"] == pytest.approx(460 * US)
    assert sum(by.values()) == pytest.approx(idle["idle_s"])
    assert idle["by_span"][0][0] == "slu.serve.wait"


def test_span_device_and_scopes():
    dev = progspans.span_device_s(HAND_MADE, HAND_MADE["host"],
                                  "slu.solve.sweep")
    # programs run inside a sweep: [230,280] [355,395] [730,810]
    assert dev == pytest.approx(170 * US)
    # the two clocks agree to a millisecond or so: a program that
    # seems to start before its span opened is still the span's
    skewed = dict(HAND_MADE, modules=[
        module("jit_slu_solve_packed(3)", 215, 50)], inflight=[],
        ops=[op("%fusion.7 fusion", 215, 50, "slu.fwd")])
    assert progspans.span_device_s(
        skewed, HAND_MADE["host"],
        "slu.solve.sweep") == pytest.approx(50 * US)
    assert progspans.span_device_s(
        skewed, HAND_MADE["host"], "slu.solve.pack") == 0.0
    assert progspans.span_device_s(HAND_MADE, HAND_MADE["host"],
                                   "slu.nothing") is None
    # the pack's own program is the pack's, not a sweep's
    assert progspans.span_device_s(
        HAND_MADE, HAND_MADE["host"],
        "slu.solve.pack") == pytest.approx(10 * US)
    scopes = progspans.scope_seconds(HAND_MADE, "bench.factorize")
    # the loop runs 50 us, 30 of them in its two operations
    assert scopes == pytest.approx({
        "slu.extend_add": 20 * US, "slu.partial_lu": 10 * US,
        "slu.schur": 20 * US, progspans.UNNAMED: 15 * US})


def test_per_step_and_per_batch():
    step = progspans.reduce_loaded(HAND_MADE, "step", 2)
    assert step["units"] == 2
    assert step["unit_host_s"]["slu.refine.residual"][0] \
        == pytest.approx(80 * US)
    assert step["unit_sweep_device_s"] == pytest.approx(170 * US)
    serve = progspans.reduce_loaded(HAND_MADE, "serve_open", None)
    # one whole batch; the sweep the trace's start cut and the
    # caller's own spans are no batch's
    assert serve["units"] == 1
    assert serve["unit_host_s"]["slu.refine.residual"] \
        == pytest.approx([40 * US, 40 * US, 1])
    assert serve["unit_host_s"]["slu.solve.sweep"][2] == 1
    assert serve["unit_sweep_device_s"] == pytest.approx(80 * US)
    assert serve["host_s"]["slu.serve.wait"][0] \
        == pytest.approx(100 * US)
    assert serve["factor_scopes"] == step["factor_scopes"]


def _run(kind, loaded, steps=None, window_s=None):
    """What a reader is handed, with the reduction already made."""
    red = None if loaded is None else \
        progspans.reduce_loaded(loaded, kind, steps)
    return types.SimpleNamespace(
        readings={"progspans": red, "traced_steps": steps,
                  "trace_window_s": window_s},
        traffic={"kind": kind}, notes={})


def _read(name, run):
    from harness import metric_reader
    return metric_reader(name).read(run)


def test_readers():
    step = _run("step", HAND_MADE, steps=2)
    assert _read("pack_s.step", step) == pytest.approx(50 * US)
    assert _read("residual_s.step", step) == pytest.approx(40 * US)
    assert _read("sweep_device_s.step", step) == pytest.approx(85 * US)
    assert _read("idle_attributed.step", step) == pytest.approx(
        100 * 460 / 540)
    assert step.notes["idle_by_span"][0] == pytest.approx(
        ["slu.serve.wait", 100 * US])
    assert _read("factor_named_share", step) == pytest.approx(
        100 * 50 / 65)
    assert list(step.notes["factor_scopes"])[-1] == "slu.partial_lu"
    serve = _run("serve_open", HAND_MADE, window_s=1e-3)
    assert _read("residual_s.serve", serve) == pytest.approx(40 * US)
    assert _read("sweep_device_s.serve", serve) == pytest.approx(80 * US)
    assert _read("flusher_wait_share.serve", serve) \
        == pytest.approx(10.0)
    assert _read("idle_attributed.serve", serve) is not None


NAMES = ("pack_s.step", "residual_s.step", "residual_s.serve",
         "sweep_device_s.step", "sweep_device_s.serve",
         "flusher_wait_share.serve", "idle_attributed.step",
         "idle_attributed.serve", "factor_named_share")


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_nothing_where_nothing_is(name):
    """No TPU plane (a CPU rehearsal): None.  A program without spans
    or scopes (this PR's parent, traced with these files): None, no
    error, so the line leaves the metric out."""
    kind = "serve_open" if name.endswith(".serve") else "step"
    assert _read(name, _run(kind, None, steps=1, window_s=1.0)) is None
    bare = {"host": [h for h in HAND_MADE["host"]
                     if h[1].startswith("bench.")],
            "modules": HAND_MADE["modules"], "inflight": [],
            "ops": [o[:3] + [None] for o in HAND_MADE["ops"]]}
    run = _run(kind, bare, steps=1, window_s=1.0)
    assert _read(name, run) is None
    assert not run.notes


def test_every_new_metric_is_declared():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        declared = {m["name"] for m in json.load(f)["per_layer"]}
    assert set(NAMES) <= declared


XSPACE = """
planes {
  name: "/device:TPU:1"
  lines { name: "XLA Ops" events { metadata_id: 1 offset_ps: 0
                                   duration_ps: 9000 } }
  event_metadata { key: 1 value { id: 1 name: "%other = f32[] add()" } }
}
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
          events { metadata_id: 3 offset_ps: 0 duration_ps: 50000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
          events { metadata_id: 1 offset_ps: 0 duration_ps: 20000
                   stats { metadata_id: 2 uint64_value: 7 } }
          events { metadata_id: 2 offset_ps: 30000
                   duration_ps: 20000 } }
  lines { name: "Async XLA Ops" timestamp_ns: 1000
          events { metadata_id: 2 offset_ps: 20000
                   duration_ps: 5000 } }
  lines { name: "Steps" events { metadata_id: 2 offset_ps: 0
                                 duration_ps: 1 } }
  event_metadata { key: 1 value { id: 1
      name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
      stats { metadata_id: 2 uint64_value: 300 }
      stats { metadata_id: 1 str_value:
        "jit(slu_factor)/slu.partial_lu/slu.schur/dot_general:" } } }
  event_metadata { key: 2 value { id: 2
      name: "%copy.2 = f32[8]{0} copy(f32[8]{0} %q)"
      stats { metadata_id: 1 str_value: "jit(slu_factor)/copy:" } } }
  event_metadata { key: 3 value { id: 3 name: "jit_slu_factor(7)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "flops" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python3" timestamp_ns: 900
          events { metadata_id: 1 offset_ps: 0 duration_ps: 200000
                   stats { metadata_id: 1 int64_value: 3 } }
          events { metadata_id: 2 offset_ps: 5000
                   duration_ps: 1000 } }
  lines { name: "python3" timestamp_ns: 900
          events { metadata_id: 3 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "slu.solve.pack" } }
  event_metadata { key: 2 value { id: 2 name: "$threading.py run" } }
  event_metadata { key: 3 value { id: 3 name: "bench.sleep" } }
  stat_metadata { key: 1 value { id: 1 name: "groups" } }
}
"""


def test_loader_on_a_written_trace(tmp_path):
    """The loader against a small .xplane.pb: the first device only,
    the three lines only, threads told apart, the scope out of a text
    stat of the event's metadata entry (where the chip puts it, and
    ProfileData does not show it), host stats kept."""
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    loaded = progspans.load(str(path))
    assert loaded["modules"] == [["jit_slu_factor(7)", 1000, 1050]]
    assert loaded["ops"] == [
        ["%fusion.1 fusion", 1000, 1020, "slu.schur"],
        ["%copy.2 copy", 1030, 1050, None]]
    assert loaded["inflight"] == [[1020, 1025]]
    assert loaded["scope_stats"] == ["tf_op"]
    assert loaded["host"] == [
        ["/host:CPU#0", "slu.solve.pack", 900, 1100, {"groups": 3}],
        ["/host:CPU#1", "bench.sleep", 900, 901, {}]]
    # and no TPU plane, no reduction
    host_only = XSPACE[XSPACE.index('planes {\n  name: "/host:CPU"'):]
    path.write_bytes(
        ProfileData.text_proto_to_serialized_xspace(host_only))
    assert progspans.load(str(path)) is None


def test_excerpt_recorded_on_the_chip():
    """What the chip really writes, through `load` (cut by
    tools/prog_look.py to the operations around both ends of a step's
    `slu.solve.pack`): one thread's spans nest as the program opens
    them, the factor and solve programs' operations carry scopes, and
    the reductions run on it."""
    with open(os.path.join(HERE, "data", "prog_excerpt.json")) as f:
        loaded = json.load(f)
    names = {h[1] for h in loaded["host"]}
    assert names == {"bench.factorize", "bench.solve", "slu.FACT",
                     "slu.SOLVE", "slu.solve.pack", "slu.solve.sweep",
                     "slu.solve.fetch"}
    assert len({h[0] for h in loaded["host"]}) == 1     # one thread
    pack = next(h for h in loaded["host"] if h[1] == "slu.solve.pack")
    assert pack[4] == {"groups": 62}
    flat = progspans.innermost(
        progspans.by_thread(loaded["host"])[pack[0]])
    assert [n for _, _, n in flat] == [
        "slu.FACT", "slu.SOLVE", "slu.solve.pack", "slu.SOLVE",
        "slu.solve.sweep", "slu.solve.fetch", "slu.solve.sweep",
        "slu.SOLVE"]
    programs = {m[0].split("(")[0] for m in loaded["modules"]}
    assert {"jit_slu_factor", "jit_slu_solve_packed",
            "jit_dynamic_slice"} <= programs
    # the factor program's last kernels store the root's panels; the
    # sweep's first are forward steps
    scopes = progspans.scope_seconds(loaded, "bench.factorize")
    assert scopes["slu.store"] > 10 * scopes[progspans.UNNAMED]
    assert {o[3] for o in loaded["ops"] if o[3]} == {"slu.store",
                                                     "slu.fwd"}
    # every packing program is the pack's, none the sweep's
    assert progspans.span_device_s(loaded, loaded["host"],
                                   "slu.solve.sweep") \
        < 0.1 * (pack[3] - pack[2]) / 1e9
    idle = progspans.idle_by_span(loaded)
    assert idle["by_span"][0][0] == "slu.solve.pack"
    assert 0.9 * idle["idle_s"] < idle["attributed_s"] <= idle["idle_s"]
