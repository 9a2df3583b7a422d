"""The trace reducer gives the hand-worked numbers on the small recorded trace
beside this file, and raises where there is no device plane.

The trace is in the form ``trace.load_xplane`` gives, event names as the
v5e's profiler writes them (an operation's whole HLO text). In nanoseconds,
window 0..100000 (the ``bench:window`` span): operations run 10000-50000
(fusion.1, then while.2 with a flash kernel and fusion.4 nested in it) and
60000-90000 (a flash kernel, then after a pause of 500 a copy). So the device
is busy 69500 of 100000, the flash kernels take 8000 + 20000, and the idle
gaps are 0-10000 (host in ``bench:dispatch`` at its middle), 50000-60000
(``bench:wait``), 90000-100000 (nothing recorded) and the 500 between two
operations. The copy in flight on ``Async XLA Ops`` all along is not busy time.
"""

import json
import os

import pytest
from benchmark_testlib import HERE, REPO

from benchmarks import trace


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as fh:
        return json.load(fh)


def test_idle_share_and_kernel_time_are_the_hand_worked_ones(recorded):
    busy_s, window_s = trace.busy_and_window_s(recorded)
    assert busy_s == pytest.approx(69500e-9) and window_s == pytest.approx(100000e-9)
    with open(os.path.join(REPO, "benchmarks", "metrics", "flash_attn_roofline.json")) as fh:
        flash = json.load(fh)["params"]["patterns"]  # the pattern the metric's file holds
    assert trace.matched_seconds(recorded, flash) == pytest.approx(28000e-9)
    assert trace.matched_seconds(recorded, [r"^jit_step"], "XLA Modules") == pytest.approx(70000e-9)
    assert trace.matched_seconds(recorded, ["no such kernel"]) == 0.0


def test_breakdown_names_own_time_and_what_the_host_did_in_each_gap(recorded):
    b = trace.breakdown(recorded)
    ops = dict(b["device_ops"])
    # while.2 spans 30000 and its children cover 18000; two fusions of 10000; two kernels
    assert ops == pytest.approx({"attn": 28000e-9, "fusion": 20000e-9, "while": 12000e-9,
                                 "copy": 9500e-9})
    assert dict(b["idle_gaps"]) == pytest.approx(
        {"bench:dispatch": 10000e-9, "bench:wait": 10000e-9, "host:unattributed": 10000e-9,
         "device:between operations": 500e-9})
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_trace_without_a_device_plane_is_an_error_not_a_host_clock(recorded):
    host_only = {"planes": [p for p in recorded["planes"] if not p["name"].startswith("/device")]}
    with pytest.raises(trace.NoDevicePlane):
        trace.busy_and_window_s(host_only)
    with pytest.raises(trace.NoDevicePlane):
        trace.matched_seconds(host_only, ["flash"])
