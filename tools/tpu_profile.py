"""Capture ONE profiled step of the fused solver on the ambient
accelerator and commit a compact op-level summary.

The round-4 hardware story is latency-bound (MFU ~0.01%), and the tau
A/B could only price one lever blind; the trace says WHERE the step's
wall actually goes (per-op device time, gaps, transfers), which is the
round-5 optimization starting point.  Raw traces are big and stay in
the gitignored .tpu_trace/ dir; the committed artifact is
TPU_PROFILE_r05.json — per-plane top events by total duration.

Runs in one process on whatever accelerator jax finds;
SLU_PROFILE_DRYRUN=1 runs the same path on CPU (host planes only)
for plumbing tests.

The xplane parse rides tensorflow's bundled proto
(tensorflow.tsl.profiler.protobuf.xplane_pb2) under the pure-python
protobuf implementation — the tensorboard_plugin_profile converters
in this image predate the installed TF and cannot load
(xspace_to_tools_data missing), so the aggregation here is
deliberately proto-level and generic: sum of event durations grouped
by (plane, line, event name).
"""

import glob
import json
import os
import sys
import time

os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION",
                      "python")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(REPO, ".tpu_trace")
OUT = os.environ.get("SLU_PROFILE_OUT",
                     os.path.join(REPO, "TPU_PROFILE_r06.json"))


# fusion-class bucketing: the round-6 acceptance budget is per CLASS
# (scatter+gather combined < 50 ms), so the summary must be machine-
# readable by class, not only a top-events list.  Classification uses
# the event's hlo_category stat when the trace carries one, else the
# op name — both lowercase substring matches.
def _fusion_class(name: str, category: str = "") -> str:
    s = (category or name).lower()
    if "scatter" in s:
        return "scatter"
    if "gather" in s:
        return "gather"
    if "dot" in s or "matmul" in s or "convolution" in s:
        return "dot"
    if "while" in s or "loop" in s or "condition" in s:
        return "loop"
    if ("dynamic-slice" in s or "dynamic-update-slice" in s
            or "copy" in s or s.startswith("slice")):
        return "copy"
    if ("all-reduce" in s or "all-gather" in s or "collective" in s
            or "all-to-all" in s):
        return "collective"
    return "other"


def _event_category(p, ev) -> str:
    """Best-effort hlo_category extraction from an XEvent's stats
    (str_value or interned ref_value)."""
    try:
        for st in ev.stats:
            meta = p.stat_metadata.get(st.metadata_id)
            if meta is None or meta.name != "hlo_category":
                continue
            if st.str_value:
                return st.str_value
            if st.ref_value:
                ref = p.stat_metadata.get(st.ref_value)
                if ref is not None:
                    return ref.name
    except Exception:
        pass
    return ""


def capture():
    dryrun = os.environ.get("SLU_PROFILE_DRYRUN") == "1"
    if dryrun:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    if dryrun:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from superlu_dist_tpu import Options
    from superlu_dist_tpu.ops.batched import make_fused_solver
    from superlu_dist_tpu.plan.plan import plan_factorization
    from superlu_dist_tpu.utils.platform import (
        apply_accel_amalg_defaults)
    from superlu_dist_tpu.utils.testmat import (laplacian_3d,
                                                manufactured_rhs)

    dev = jax.devices()[0]
    if dev.platform != "cpu":
        apply_accel_amalg_defaults()
        from superlu_dist_tpu.utils.cache import place_compile_cache
        place_compile_cache()

    k = int(os.environ.get("SLU_PROFILE_K", "8" if dryrun else "30"))
    a = laplacian_3d(k)
    plan = plan_factorization(a, Options(factor_dtype="float32"),
                              autotune=True)
    step = make_fused_solver(plan, dtype="float32")
    _, b = manufactured_rhs(a)
    v, bb = jnp.asarray(a.data), jnp.asarray(b[:, None])
    step(v, bb)[0].block_until_ready()  # compile + warm outside trace
    t0 = time.perf_counter()
    with jax.profiler.trace(TRACE_DIR):
        step(v, bb)[0].block_until_ready()
    wall = time.perf_counter() - t0
    return dict(device=str(dev), device_kind=getattr(
        dev, "device_kind", dev.platform), n=a.n,
        profiled_step_wall_s=wall)


def summarize(meta, top=40):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    paths = sorted(glob.glob(TRACE_DIR + "/**/*.xplane.pb",
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise SystemExit("no xplane.pb captured under " + TRACE_DIR)
    xs = xplane_pb2.XSpace()
    with open(paths[-1], "rb") as f:
        xs.ParseFromString(f.read())
    planes = []
    sg_device_ms = 0.0
    sg_categorized = False
    for p in xs.planes:
        agg = {}
        classes = {}
        n_cat = n_ev = 0
        uncat_fusion_ps = 0
        for line in p.lines:
            for ev in line.events:
                name = p.event_metadata[ev.metadata_id].name
                key = (line.name, name)
                tot, cnt = agg.get(key, (0, 0))
                agg[key] = (tot + ev.duration_ps, cnt + 1)
                cat = _event_category(p, ev)
                n_ev += 1
                if cat:
                    n_cat += 1
                cls = _fusion_class(name, cat)
                classes[cls] = classes.get(cls, 0) + ev.duration_ps
                if not cat and cls == "other" \
                        and name.startswith("fusion"):
                    # a kCustom scatter/gather fusion with no
                    # hlo_category stat is indistinguishable from
                    # benign "other" work — count it so a ~0
                    # scatter_gather_ms reading is auditable
                    uncat_fusion_ps += ev.duration_ps
        if not agg:
            continue
        events = sorted(agg.items(), key=lambda kv: -kv[1][0])[:top]
        class_ms = {k: round(v / 1e9, 4)
                    for k, v in sorted(classes.items(),
                                       key=lambda kv: -kv[1])}
        is_device = ("TPU" in p.name or "/device" in p.name
                     or "Device" in p.name)
        if is_device:
            sg_device_ms += (classes.get("scatter", 0)
                             + classes.get("gather", 0)) / 1e9
            sg_categorized = sg_categorized or n_cat > 0
        planes.append(dict(
            plane=p.name,
            fusion_class_ms=class_ms,
            hlo_category_events=n_cat,
            uncategorized_fusion_ms=round(uncat_fusion_ps / 1e9, 4),
            events=[dict(line=ln, op=op_name,
                         total_ms=round(ps / 1e9, 4), count=cnt)
                    for (ln, op_name), (ps, cnt) in events]))
    return dict(meta, ts=time.strftime("%Y-%m-%dT%H:%M:%S"),
                xplane=os.path.relpath(paths[-1], REPO),
                # the round's acceptance budget: device scatter+gather
                # fusion classes combined (VERDICT target < 50 ms).
                # A ~0 reading is only meaningful when the trace
                # carried hlo_category stats — otherwise unnamed
                # "fusion.N" scatters classify as "other" and the
                # budget would pass vacuously; consumers must check
                # the reliability flag + per-plane
                # uncategorized_fusion_ms before certifying.
                scatter_gather_ms=round(sg_device_ms, 4),
                scatter_gather_ms_reliable=bool(sg_categorized),
                planes=planes)


def main():
    meta = capture()
    rec = summarize(meta)
    # atomic promote: the fire step's timeout may SIGKILL mid-write,
    # and a truncated committed artifact is worse than a stale one
    tmp = OUT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(rec, f, indent=1)
    os.replace(tmp, OUT)
    # twin artifact in the UNIFIED trace format (obs/ tracer schema):
    # the fusion-class buckets and top ops as Chrome trace spans, so
    # the profiled step opens in Perfetto next to the solver's own
    # SLU_TRACE phase spans instead of living in a bespoke JSON only
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    trace_out = (OUT[:-5] if OUT.endswith(".json") else OUT) \
        + ".trace.json"
    trace_err = None
    try:
        from trace_export import chrome_trace_from_profile, write_chrome
        write_chrome(chrome_trace_from_profile(rec), trace_out,
                     other={"source": os.path.basename(OUT),
                            "device": rec.get("device", "")})
    except Exception as e:
        # the twin is auxiliary: the profile JSON above is already
        # promoted, so a trace-conversion failure is reported in-band
        # instead of failing the fire step's profile stage
        trace_out, trace_err = None, repr(e)
    dev_planes = [p["plane"] for p in rec["planes"]]
    line = dict(profile=OUT, trace=trace_out, wall_s=meta[
        "profiled_step_wall_s"], planes=dev_planes,
        scatter_gather_ms=rec["scatter_gather_ms"])
    if trace_err:
        line["trace_error"] = trace_err
    print(json.dumps(line))


if __name__ == "__main__":
    main()
