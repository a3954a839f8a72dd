"""The reduction from a profiler trace to device busy time, per-op time and
named idle gaps, on hand-made planes shaped as ``ProfileData`` gives them
and on a small trace recorded on a TPU v5e by ``bench/record_trace.py``
(8 shards x 128 streams, 8 ticks in the window), kept gzipped in
``tests/data``."""
from __future__ import annotations

import gzip
import json
import os
from types import SimpleNamespace as NS

import pytest

import harness
import trace_reduce as R
from conftest import BENCH


def _line(name, events):
    return NS(name=name, events=[NS(name=n, start_ns=s, duration_ns=d)
                                 for n, s, d in events])


def _planes():
    host = NS(name="/host:CPU", lines=[_line("python", [
        ("bench.window", 100, 1000), ("bench.feed", 100, 300),
        ("bench.step", 400, 400), ("bench.sync", 800, 300), ("other", 0, 50)])])
    dev = NS(name="/device:TPU:0", lines=[
        _line("XLA Ops", [("fusion.1", 50, 100), ("q15_step", 450, 300),
                          ("copy", 700, 60), ("late", 1150, 100)]),
        _line("XLA Modules", [("jit_step", 450, 400)])])
    return [NS(name="/host:metadata", lines=[]), host, dev]


def test_union_merges_overlaps():
    assert R._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_gap_label_is_the_covering_span():
    spans = sorted([(0.0, 10.0, "bench.feed"), (10.0, 20.0, "bench.step"),
                    (25.0, 30.0, "bench.sync")])
    assert R._label(spans, 5.0) == "bench.feed"
    assert R._label(spans, 15.0) == "bench.step"
    assert R._label(spans, 22.0) == "none"
    assert R._label(spans, 27.0) == "bench.sync"
    assert R._label(spans, -1.0) == "none"


def test_hand_made_planes():
    r = R.reduce_planes(_planes())
    assert r["devices"] == 1 and r["window_s"] == pytest.approx(1000e-9)
    # ops clipped to the window [100, 1100]: 50 + 300 + 60 + 0
    assert r["ops"] == pytest.approx({"fusion.1": 50e-9, "q15_step": 300e-9,
                                      "copy": 60e-9})
    assert r["busy_s"] == pytest.approx(360e-9)    # copy overlaps q15_step
    # idle: [150, 450) in feed (midpoint 300), [760, 1100) in sync (930)
    assert r["gaps"] == pytest.approx({"bench.feed": 300e-9, "bench.sync": 340e-9})
    assert r["top_ops"][0][0] == "q15_step"
    assert r["op_text"]["q15_step"] == ""


def test_roofline_reader_finds_the_kernel_by_name_or_stats():
    read = harness._load_module(os.path.join(BENCH, "metrics", "q15_step_roofline.py")).read
    trace = {"devices": 1, "ops": {"custom-call.7": 0.002, "fusion.1": 0.001},
             "op_text": {"custom-call.7": "", "fusion.1": ""}}
    ctx = {"trace": trace, "peak": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
           "stream_steps": 131072,
           "work": {"flops": 131072 * 748, "hbm_bytes": 131072 * 140}}
    bound = 131072 * 140 / 819e9
    assert read(ctx) == pytest.approx(100 * bound / 0.002)
    trace["ops"] = {"fusion.2": 0.004}
    trace["op_text"] = {"fusion.2": "_q15_step_kernel tpu_custom_call"}
    assert read(ctx) == pytest.approx(100 * bound / 0.004)
    trace["op_text"] = {"fusion.2": "add"}
    with pytest.raises(LookupError):
        read(ctx)
    ctx["trace"] = None
    assert read(ctx) is None


def test_no_device_plane_gives_no_device_time():
    r = R.reduce_planes(_planes()[:2])
    assert r["devices"] == 0 and r["busy_s"] == 0.0 and r["ops"] == {}


RECORDED = os.path.join(BENCH, "tests", "data", "fleet_small_v5e.xplane.pb.gz")


def test_recorded_v5e_trace():
    """The figures the recording run printed on the chip: one device, its
    busy time and window, the step kernel first among the ops, the idle
    time named by the bench span it fell in, and the kernel's roofline
    share for 8 ticks of 1,024 stream-steps."""
    from jax.profiler import ProfileData
    with gzip.open(RECORDED) as f:
        planes = ProfileData.from_serialized_xspace(f.read()).planes
    r = R.reduce_planes(planes)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.315563251)
    assert r["busy_s"] == pytest.approx(0.000571205)
    top, seconds = r["top_ops"][0]
    assert "tpu_custom_call" in top and seconds == pytest.approx(0.000357499)
    assert r["top_gaps"][0][0] == "bench.step"
    assert set(r["gaps"]) <= {"bench.feed", "bench.step", "bench.sync", "none"}
    assert sum(r["gaps"].values()) + r["busy_s"] == pytest.approx(r["window_s"])
    read = harness._load_module(os.path.join(BENCH, "metrics", "q15_step_roofline.py")).read
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peak = json.load(f)["devices"]["TPU v5 lite"]
    ctx = {"trace": r, "peak": peak, "stream_steps": 8 * 1024,
           "work": {"flops": 8 * 1024 * 748, "hbm_bytes": 8 * 1024 * 140}}
    assert read(ctx) == pytest.approx(0.3917051181519054)
