"""The trace reduction: interval arithmetic, and a trace from the chip."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import xplane  # noqa: E402
from xplane import Event  # noqa: E402


def test_union_merges_overlaps():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.length([(0, 3), (5, 8)]) == 6


def test_subtract_leaves_the_uncovered_parts():
    a = [(0, 10), (20, 30)]
    b = [(2, 4), (8, 22), (25, 26)]
    assert xplane.subtract(a, b) == [(0, 2), (4, 8), (22, 25), (26, 30)]
    assert xplane.subtract(a, []) == a
    assert xplane.subtract([], b) == []


def _toy():
    host = [Event("bench.traced", 0, 100), Event("bench.pump", 10, 60),
            Event("bench.pump", 70, 95), Event("bench.submit", 60, 70)]
    devices = {
        "/device:TPU:0": [Event("fusion.1", 12, 20),
                          Event("_stream_kernel", 20, 50),
                          Event("collective-permute-done.1", 45, 58),
                          Event("fusion.2", 72, 90),
                          Event("fusion.3", 95, 120)],
        "/device:TPU:1": [Event("_stream_kernel", 15, 30),
                          Event("collective-permute-done.1", 30, 58),
                          Event("fusion.2", 75, 90)],
    }
    return devices, host


def test_reduce_busy_exposed_and_flushes():
    red = xplane.reduce(*_toy())
    assert red.window == (0, 100)
    # device 0: 12-58, 72-90 and 95-100 (clipped at the window's end)
    assert red.busy_ns["/device:TPU:0"] == 46 + 18 + 5
    assert red.busy_ns["/device:TPU:1"] == 43 + 15
    # exposed: collective time with no other op on that device
    assert red.exposed_ns["/device:TPU:0"] == 8
    assert red.exposed_ns["/device:TPU:1"] == 28
    assert red.op_ns["_stream_kernel"] == 30 + 15
    assert red.op_ns["fusion.3"] == 5
    assert [(f.start, f.end) for f in red.flushes] == [(10, 60), (70, 95)]


def test_breakdown_labels_gaps_by_host_span():
    red = xplane.reduce(*_toy())
    bd = xplane.breakdown(red, top=3)
    assert bd["device_ops"][0][0] == "_stream_kernel"
    assert bd["device_ops"][0][1] == pytest.approx(45e-9)
    # the longest gaps: device 1 58-75 and device 0 58-72, the host
    # submitting at their midpoints, and device 1 0-15, before any span
    assert [g[0] for g in bd["idle_gaps"]] == [
        "bench.submit", "(no span)", "bench.submit"]
    assert [g[1] for g in bd["idle_gaps"]] == pytest.approx(
        [17e-9, 15e-9, 14e-9])


def test_reduce_needs_the_window_span():
    devices, host = _toy()
    with pytest.raises(ValueError, match="bench.traced"):
        xplane.reduce(devices, host[1:])


CHIP_TRACE = os.path.join(HERE, "tests", "data", "chip-trace.xplane.pb.gz")


def test_a_trace_recorded_on_the_chip_reduces():
    """A profile of two kaggle-hetero-p1 flushes on one TPU v5e (JAX 0.9):
    the device plane, its op line, the benchmark's host spans and the
    pooling kernel's event names are what the reduction looks for."""
    import importlib.util
    devices, host = xplane.load(CHIP_TRACE)
    assert sorted(devices) == ["/device:TPU:0"]
    assert {"bench.traced", "bench.pump", "bench.submit",
            "bench.harvest"} <= {e.name for e in host}
    names = {e.name for e in devices["/device:TPU:0"]}
    assert not any(" = " in n or n.startswith("%") for n in names)
    red = xplane.reduce(devices, host)
    assert len(red.flushes) == 2
    busy = red.busy_ns["/device:TPU:0"]
    assert 0.5 * red.window_ns < busy <= red.window_ns
    path = os.path.join(HERE, "metrics", "emb_bag_roofline.py")
    mod_spec = importlib.util.spec_from_file_location("roofline", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    kernels = {n for n in red.op_ns
               if any(k in n for k in mod.KERNEL_NAMES)}
    # one pooling kernel call per microbatch of the BLS step (4)
    assert len(kernels) == 4
    assert 0 < sum(red.op_ns[k] for k in kernels) < busy
    top = xplane.breakdown(red)["device_ops"][0][0]
    assert top in kernels
