"""Each metric reader against a hand count on a toy run."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import counts  # noqa: E402
import harness  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402
import xplane  # noqa: E402
from xplane import Event  # noqa: E402

CFG = {"table_sizes": [10, 20], "n_dense_features": 4, "embed_dim": 8,
       "bottom_mlp": [16, 8], "top_mlp": [12, 1], "dtype": "float32"}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def toy_run(trace=True):
    # four requests: two flushes of two; the second flush is traced
    w = harness.Window(
        seconds=2.0, due=np.array([0.1, 0.2, 1.0, 1.1]),
        dispatch=np.array([0.15, 0.25, 1.05, 1.2]),
        done=np.array([0.5, 0.5, 1.5, 2.5]),
        ctr=np.full(4, 0.5, np.float32), flush_of=np.array([0, 0, 1, 1]),
        flushes=np.array([[0.2, 0.5], [1.1, 1.5]]), late=np.zeros(4),
        traced=range(1, 2) if trace else None)
    host = [Event("bench.traced", 0, 1000), Event("bench.pump", 0, 1000)]
    devices = {
        "/device:TPU:0": [Event("embedding_bag_stacked_op.8", 100, 600),
                          Event("all-to-all.1", 600, 800)],
        "/device:TPU:1": [Event("embedding_bag_stacked_op.8", 100, 300),
                          Event("all-to-all.1", 300, 800),
                          Event("fusion.1", 700, 900)]}
    red = xplane.reduce(devices, host) if trace else None
    return run.Run(root=spec.ROOT, cell={"config": CFG}, seed=0,
                   setup_s=12.5, window=w, valid=np.array([3, 4, 5, 6]),
                   chips=2, peaks=PEAKS, trace=red)


def read(name, r):
    return spec.reader(name)(r)


def test_latency_and_rate_readers():
    r = toy_run()
    lat = np.array([0.4, 0.3, 0.5, 1.4])
    assert read("p50_ms", r) == pytest.approx(np.percentile(lat, 50) * 1e3)
    assert read("p99_ms", r) == pytest.approx(np.percentile(lat, 99) * 1e3)
    assert read("served_p99_ms", r) == read("p99_ms", r)
    # four served; the last completes 0.5 s after the 2 s window closed
    assert read("throughput_rps", r) == 4 / 2.5
    assert read("setup_s", r) == 12.5
    wait = np.array([0.05, 0.05, 0.05, 0.1])
    assert read("queue_wait_p99_ms", r) == pytest.approx(
        np.percentile(wait, 99) * 1e3)
    assert read("flush_ms", r) == pytest.approx((300 + 400) / 2)


def test_device_readers_by_hand():
    r = toy_run()
    # busy: device 0 100-800 (700 ns), device 1 100-900 (800 ns) of 1000
    assert read("device_idle_share", r) == pytest.approx(25.0)
    # traced flush 1 serves requests 2 and 3 (5 and 6 valid indices)
    flops = counts.request_flops(CFG, [5, 6]).sum()
    assert read("step_mfu", r) == pytest.approx(
        100 * flops / (2 * 1e12 * 750e-9))
    useful = counts.pooling_bytes(CFG, [5, 6]).sum()
    assert read("emb_bag_roofline", r) == pytest.approx(
        100 * useful / (700e-9 * 1e9))
    # exposed: device 0 600-800 (200 ns), device 1 300-700 (400 ns); one
    # traced flush; mean 300 ns = 3e-4 ms
    assert read("exchange_exposed_ms", r) == pytest.approx(3e-4)


def test_device_readers_without_a_trace_return_nothing():
    r = toy_run(trace=False)
    for name in ("device_idle_share", "step_mfu", "emb_bag_roofline",
                 "exchange_exposed_ms"):
        assert read(name, r) is None
