"""The trace reduction: busy union, idle share, operation self times,
the longest idle gaps -- on hand-made events and on a short trace
recorded on a TPU v5e (``data/``: the serving loop at planner-proxy-100m
width)."""
import glob
import os

import pytest

from bench import devtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def made():
    tr = devtrace.Trace()
    # window [100, 200); ops touch at 140 and straddle the edges
    tr.devices[0] = [(90, 110, "fusion.1"), (120, 140, "flash_decode"),
                     (140, 150, "fusion.2"), (170, 180, "flash_decode"),
                     (195, 230, "fusion.1")]
    tr.host = [(100, 100, "bench.open"), (140, 175, "bench.step"),
               (150, 160, "bench.submit"), (200, 200, "bench.close")]
    return tr


def test_union_clip_and_idle():
    red = devtrace.reduce(made(), [0])
    assert red.window_ns == 100
    # busy: 100-110, 120-150, 170-180, 195-200 = 10 + 30 + 10 + 5
    assert red.busy_ns == {0: 55}
    assert red.idle_share[0] == pytest.approx(0.45)
    assert red.op_ns["flash_decode"] == 30
    assert "no_such_kernel" not in red.op_ns
    # gaps: 110-120 (no host span), 150-170 (bench.submit covers 10 of
    # it, bench.step 20) and 180-195 (none)
    assert red.idle_by_host == {"bench.step": (20, 1, 20),
                                "untraced": (25, 2, 15)}


def test_breakdown_averages_ops_over_chips():
    tr = made()
    tr.devices[1] = list(tr.devices[0])
    red = devtrace.reduce(tr, [0, 1])
    bd = devtrace.breakdown(red, 2)
    assert bd["device_ops"][0] == ["flash_decode", pytest.approx(30e-9)]
    assert bd["idle_gaps"][0] == ["untraced: 4 gaps, longest 0.000 ms",
                                  pytest.approx(25e-9)]


def test_self_time_of_enclosing_ops():
    evs = [(0, 100, "while"), (10, 30, "body"), (40, 90, "body"),
           (50, 60, "inner")]
    assert devtrace.op_totals(evs) == {"while": 30, "body": 60,
                                       "inner": 10}


def test_short_name():
    hlo = ('%closed_call.9 = bf16[48,4,3,64]{3,2,1,0:T(4,128)} custom-call('
           's32[48]{0} %a), custom_call_target="tpu_custom_call"')
    assert devtrace.short_name(hlo) == \
        "%closed_call.9 tpu_custom_call bf16[48,4,3,64]"


def recorded():
    files = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    if not files:
        pytest.fail("the recorded trace is missing from bench/tests/data")
    return devtrace.load(files[0])


def test_recorded_trace():
    tr = recorded()
    assert 0 in tr.devices and tr.devices[0]
    red = devtrace.reduce(tr, [0])
    lo, hi = devtrace.window(tr)
    evs = devtrace.clip(tr.devices[0], lo, hi)
    # the union against a brute-force sweep over the clipped events
    points = sorted({t for s, e, _ in evs for t in (s, e)})
    brute = sum(b - a for a, b in zip(points, points[1:])
                if any(s <= a and b <= e for s, e, _ in evs))
    assert red.busy_ns[0] == brute
    assert 0 < red.busy_ns[0] <= red.window_ns
    # the Pallas kernels are on the trace (as tpu_custom_call; they
    # carry no kernel name yet), and their self time is their events'
    target = 'custom_call_target="tpu_custom_call"'
    ns = sum(v for n, v in red.op_ns.items() if target in n)
    assert ns and ns == sum(e - s for s, e, n in evs if "tpu_custom_call" in n)
    assert sum(red.op_ns.values()) >= red.busy_ns[0]
    # the device is idle only while the serving loop steps
    assert set(red.idle_by_host) <= {"bench.step", "bench.submit",
                                     "bench.generate", "bench.wait",
                                     "untraced"}
    idle = sum(ns for ns, _, _ in red.idle_by_host.values())
    assert idle == red.window_ns - red.busy_ns[0]
