"""The reduction from trace events to numbers: exact arithmetic on a
hand-made trace, and a look at a small trace recorded on the chip
(`data/step_excerpt.json`: the first second of a traced run of
lap3d_k30.step on a TPU v5 lite, PR 25)."""

import json
import os

import pytest

import roofline
import tracered
from conftest import HERE

D0, D1 = "/device:TPU:0", "/device:TPU:1"
OPS, MODS = tracered.OPS_LINE, tracered.MODULES_LINE
HOST = "/host:CPU"


def ev(plane, line, name, start_us, dur_us):
    return (plane, line, name, start_us * 1000, dur_us * 1000)


HAND_MADE = [
    # a factor program: two ops with a 10 us gap, then an all-reduce
    # half hidden behind a fusion
    ev(D0, MODS, "jit_factor(1)", 100, 100),
    ev(D0, OPS, "fusion.1", 100, 30),
    ev(D0, OPS, "fusion.2", 140, 30),
    ev(D0, OPS, "all-reduce.1", 160, 20),
    ev(D0, OPS, "fusion.1", 180, 20),
    # a solve program after a 300 us gap
    ev(D0, MODS, "jit_solve(2)", 500, 50),
    ev(D0, OPS, "fusion.9", 500, 50),
    # the second device ran only the first fusion
    ev(D1, OPS, "fusion.1", 100, 30),
    # what the host was doing
    ev(HOST, "python3", "bench.factorize", 90, 120),
    ev(HOST, "python3", "bench.solve", 400, 200),
]


def test_hand_made_trace():
    red = tracered.reduce_events(HAND_MADE, n_devices=2)
    us = 1e-6
    # device 0 busy: [100,130] [140,200] [500,550] = 140 us; device 1: 30
    assert red["busy_s"] == pytest.approx((140 + 30) / 2 * us)
    assert red["n_planes"] == 2
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(50 * us)
    assert red["device_ops"][0][0] in ("fusion.1", "fusion.9")
    # gaps: [130,140] inside bench.factorize; [200,500]: 10 us of it
    # inside bench.factorize, 100 us inside bench.solve, 190 us nowhere
    gaps = dict(red["idle_gaps"])
    assert gaps["bench.factorize"] == pytest.approx(20 * us)
    assert gaps["bench.solve"] == pytest.approx(100 * us)
    assert gaps["no_span"] == pytest.approx(190 * us)
    assert red["collective_s"] == pytest.approx(20 * us)
    # the program that started inside bench.factorize was busy 90 us
    assert red["span_device_s"]["bench.factorize"] == pytest.approx(
        90 * us)
    assert red["span_device_s"]["bench.solve"] == pytest.approx(50 * us)


def test_no_device_plane_gives_nothing():
    red = tracered.reduce_events(
        [ev(HOST, "python3", "bench.solve", 0, 10)], n_devices=1)
    assert red["busy_s"] is None and red["device_ops"] == []


def test_recorded_trace():
    path = os.path.join(HERE, "data", "step_excerpt.json")
    with open(path) as f:
        events = [tuple(e) for e in json.load(f)]
    red = tracered.reduce_events(events, n_devices=1)
    ops = [e for e in events if e[1] in (tracered.OPS_LINE,
                                         tracered.ASYNC_LINE)]
    first = min(e[3] for e in ops)
    last = max(e[3] + e[4] for e in ops)
    assert 0 < red["busy_s"] <= (last - first) / 1e9
    assert red["device_ops"] and red["device_ops"][0][1] > 0
    assert "bench.factorize" in red["span_device_s"]
    idle = sum(t for _, t in red["idle_gaps"])
    assert idle + red["busy_s"] == pytest.approx((last - first) / 1e9,
                                                 rel=1e-6)


def test_roofline_arithmetic():
    # one front, w=2, r=3: 2/3*8 + 2*4*3 + 2*2*9 = 65.333 flops
    assert roofline.factor_flops([2], [3]) == pytest.approx(
        2 / 3 * 8 + 24 + 36)
    # panels 2*5 + 3*2 = 16, update 2*9 = 18, nnz 7 -> 41 words
    assert roofline.factor_bytes([2], [3], 7, 4) == 41 * 4
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    share, bound = roofline.roofline_share(200.0, 50.0, 10.0, peaks)
    assert bound == "bytes" and share == pytest.approx(50.0)
    share, bound = roofline.roofline_share(2000.0, 50.0, 40.0, peaks)
    assert bound == "flops" and share == pytest.approx(50.0)
