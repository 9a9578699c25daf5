"""The readers of the program's own spans, counters and device scopes."""
import gzip
import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import run  # noqa: E402
import spans  # noqa: E402
import spec  # noqa: E402
import xplane  # noqa: E402
from xplane import Event  # noqa: E402

from repro.serving.trace import Span  # noqa: E402

DATA = os.path.join(HERE, "tests", "data")
NEW = ("host_prep_ms", "frontend_self_ms", "pad_share", "plan_ms")


def read(name, r):
    return spec.reader(name)(r)


def flush_spans(f, t, *, n, pooled, padded, parts):
    """One flush's spans at time t (ns): ``parts`` are (name, ns)."""
    out, cur = [], t + 10
    for name, ns in parts:
        out.append(Span(name, cur, cur + ns, "engine.flush", 1,
                        {"flush": f}))
        cur += ns
    eng = Span("engine.flush", t + 5, cur + 5, "frontend.dispatch", 1,
               {"flush": f, "n": n, "pooled": pooled, "padded": padded})
    disp = Span("frontend.dispatch", t, cur + 20, None, 1,
                {"flush": f, "n": n, "queued": 0})
    comp = Span("frontend.complete", cur + 20, cur + 120, None, 1,
                {"flush": f, "n": n})
    return out + [eng, disp, comp]


PARTS = [("engine.stack", 1_000_000), ("engine.prepare", 2_000_000),
         ("engine.dispatch", 500_000), ("engine.account", 100_000),
         ("engine.wait", 20_000_000), ("engine.account", 300_000)]


def buffer():
    """Two warm-up flushes (0, 1) then a two-flush window (2, 3); an older
    engine's flush 2 sits before them and must not count."""
    old = flush_spans(2, 0, n=9, pooled=99, padded=99,
                      parts=[("engine.stack", 7_000_000)])
    out = list(old)
    for f, (n, pooled, padded) in enumerate(
            [(512, 900, 0), (512, 900, 0), (300, 1000, 424),
             (512, 1200, 0)]):
        out += flush_spans(f, 10_000_000 + f * 100_000_000, n=n,
                           pooled=pooled, padded=padded, parts=PARTS)
    return out


def fake_recorder(buf, scopes=None):
    return types.SimpleNamespace(spans=lambda: list(buf),
                                 op_scopes=lambda: dict(scopes or {}))


def toy_run(n_flushes=2, trace=None, traced=None):
    w = harness.Window(
        seconds=1.0, due=np.zeros(2), dispatch=np.zeros(2),
        done=np.ones(2), ctr=np.zeros(2, np.float32),
        flush_of=np.array([0, 1]),
        flushes=np.zeros((n_flushes, 2)), late=np.zeros(2), traced=traced)
    return run.Run(root=spec.ROOT, cell={}, seed=0, setup_s=1.0, window=w,
                   valid=np.array([1, 1]), chips=1, peaks={}, trace=trace)


def test_host_prep_and_frontend_self_by_hand(monkeypatch):
    monkeypatch.setattr(spans, "recorder", lambda: fake_recorder(buffer()))
    r = toy_run()
    # stack + prepare + dispatch of each window flush
    assert read("host_prep_ms", r) == pytest.approx(3.5)
    # dispatch less its engine.flush: 5 ns before and 15 ns after; the
    # completion 100 ns
    assert read("frontend_self_ms", r) == pytest.approx(120e-6)


def test_pad_share_by_hand(monkeypatch):
    monkeypatch.setattr(spans, "recorder", lambda: fake_recorder(buffer()))
    assert read("pad_share", toy_run()) == pytest.approx(
        100 * 424 / 2200)
    # a window of all four flushes
    assert read("pad_share", toy_run(4)) == pytest.approx(
        100 * 424 / 4000)


def test_window_takes_the_last_flushes_of_the_current_engine(monkeypatch):
    monkeypatch.setattr(spans, "recorder", lambda: fake_recorder(buffer()))
    w = spans.window(toy_run())
    assert [s.attrs["flush"] for s in w.flushes] == [2, 3]
    assert all(s.start_ns >= 10_000_000 for s in w.spans)
    assert spans.window(toy_run(6)) is None       # fewer flushes recorded
    assert spans.window(toy_run(0)) is None


def test_plan_scope_rule():
    import importlib.util
    mod_spec = importlib.util.spec_from_file_location(
        "plan_ms", os.path.join(HERE, "metrics", "plan_ms.py"))
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    base = "jit(step)/while/body/closed_call/stage_a/pool/jit(op)/plan"
    assert mod.in_scope(base + "/vmap(jit(searchsorted))/vmap()/while",
                        "plan")
    assert mod.in_scope(base + "/jit(argsort)/sort", "plan")
    assert mod.in_scope("jit(f)/plan/vmap(plan)/add", "plan")
    # the body and condition of a loop inside the scope: the loop counts
    assert not mod.in_scope(base + "/while/body/closed_call/add", "plan")
    assert not mod.in_scope(base + "/while/cond/lt", "plan")
    # the kernel is pooling; the exchange inside stage_b is the exchange
    assert not mod.in_scope("jit(step)/stage_a/pool/pallas_call", "plan")
    assert mod.in_scope("jit(step)/stage_b/exchange/ppermute", "exchange")
    assert not mod.in_scope("jit(step)/while", "plan")
    assert not mod.in_scope(None, "plan")


def test_plan_ms_on_a_toy_trace(monkeypatch):
    scopes = {"while.79": "jit(step)/stage_a/pool/plan/while",
              "fusion.3": "jit(step)/stage_a/pool/plan/while/body/add",
              "embedding_bag_stacked_op.8": "jit(step)/stage_a/pool/pc"}
    monkeypatch.setattr(spans, "recorder",
                        lambda: fake_recorder([], scopes))
    host = [Event("bench.traced", 0, 10_000_000)]
    dev = [Event("while.79", 0, 4_000_000), Event("fusion.3", 0, 3_000_000),
           Event("embedding_bag_stacked_op.8", 4_000_000, 9_000_000)]
    red = xplane.reduce({"/device:TPU:0": dev, "/device:TPU:1": dev}, host)
    # 4 ms on each of two chips over two traced flushes
    assert read("plan_ms", toy_run(trace=red, traced=range(0, 2))) == \
        pytest.approx(2.0)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_recorder_reads_nothing(monkeypatch, name):
    # the import of repro.serving.trace fails, as on an older program
    monkeypatch.setitem(sys.modules, "repro.serving.trace", None)
    assert spans.recorder() is None
    red = xplane.reduce({"/device:TPU:0": [Event("while.1", 0, 5)]},
                        [Event("bench.traced", 0, 10)])
    assert read(name, toy_run(trace=red, traced=range(0, 2))) is None


CHIP_TRACE = os.path.join(DATA, "chip-trace.xplane.pb.gz")


def chip_run(path, flushes):
    """A run over a recorded trace: its traced flushes each served 512
    requests of 50 valid indices."""
    devices, host = xplane.load(path)
    red = xplane.reduce(devices, host)
    n = 512 * flushes
    w = harness.Window(
        seconds=1.0, due=np.zeros(n), dispatch=np.zeros(n), done=np.ones(n),
        ctr=np.zeros(n, np.float32),
        flush_of=np.repeat(np.arange(flushes), 512),
        flushes=np.zeros((flushes, 2)), late=np.zeros(n),
        traced=range(0, flushes))
    with open(os.path.join(HERE, "configs", "dlrm-kaggle.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    return run.Run(root=spec.ROOT, cell={"config": cfg}, seed=0,
                   setup_s=1.0, window=w, valid=np.full(n, 50), chips=1,
                   peaks=peaks, trace=red)


def test_existing_readers_read_the_committed_chip_trace_as_before():
    """The device readers of the accepted benchmark on the trace recorded
    for it, with the same window: the numbers they read before the
    program had spans and scopes."""
    r = chip_run(CHIP_TRACE, 2)
    assert read("device_idle_share", r) == pytest.approx(
        EXPECTED_CHIP_TRACE["device_idle_share"], rel=1e-12)
    assert read("emb_bag_roofline", r) == pytest.approx(
        EXPECTED_CHIP_TRACE["emb_bag_roofline"], rel=1e-12)
    assert read("step_mfu", r) == pytest.approx(
        EXPECTED_CHIP_TRACE["step_mfu"], rel=1e-12)
    assert read("exchange_exposed_ms", r) is None     # one chip


SCOPED_TRACE = os.path.join(DATA, "scoped-trace.xplane.pb.gz")
SCOPED_TRACE_SCOPES = os.path.join(DATA, "scoped-trace-scopes.json")


def test_plan_ms_counts_each_plan_loop_of_the_chip_trace_once(monkeypatch):
    """Two kaggle-hetero-p1 flushes on one TPU v5e, recorded with the
    program's scopes, and ``op_scopes()`` of that run (restricted to the
    ops the trace holds): the stream plan's eight ``while`` loops (two a
    microbatch) are found under ``plan`` and counted once, the ops of
    their bodies, which the trace shows inside them, not again."""
    import importlib.util
    with open(SCOPED_TRACE_SCOPES) as f:
        scopes = json.load(f)
    monkeypatch.setattr(spans, "recorder",
                        lambda: fake_recorder([], scopes))
    r = chip_run(SCOPED_TRACE, 2)
    red = r.trace
    assert len(red.flushes) == 2
    mod_spec = importlib.util.spec_from_file_location(
        "plan_ms", os.path.join(HERE, "metrics", "plan_ms.py"))
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    loops = sorted(n for n in red.op_ns if n.startswith("while.")
                   and mod.in_scope(scopes.get(n), "plan"))
    assert loops == [f"while.{k}" for k in range(79, 87)]
    body = [n for n in red.op_ns
            if "/plan/" in scopes.get(n, "") and
            not mod.in_scope(scopes[n], "plan")]
    loop_ns = sum(red.op_ns[n] for n in loops)
    body_ns = sum(red.op_ns[n] for n in body)
    assert body_ns > 0.9 * loop_ns          # shown inside the loops
    plan = read("plan_ms", r)
    assert loop_ns / 2e6 <= plan < (loop_ns + 0.5 * body_ns) / 2e6
    assert plan == pytest.approx(205.3979715, rel=1e-9)
    # the ten longest ops are named: the kernels are pooling, the loops
    # the plan build
    for name, _ in xplane.breakdown(red)["device_ops"]:
        want = "pool" if name.startswith("embedding_bag_stacked_op") \
            else "plan"
        assert mod.in_scope(scopes[name], want), name


def test_the_program_spans_land_in_the_chip_trace():
    """The recorder's spans are profiler annotations: the recorded trace
    holds each engine and frontend span of its flushes, with the
    counters as attributes."""
    from jax.profiler import ProfileData
    with gzip.open(SCOPED_TRACE, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    flushes = []
    names = set()
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                names.add(e.name)
                if e.name == "engine.flush":
                    flushes.append({k: int(str(v)) for k, v in e.stats})
    assert {"frontend.dispatch", "frontend.complete", "engine.flush",
            "engine.stack", "engine.prepare", "engine.dispatch",
            "engine.wait", "engine.account"} <= names
    assert flushes and all(
        set(f) == {"flush", "n", "pooled", "padded"} and
        0 <= f["padded"] < f["pooled"] for f in flushes)


# read by the accepted benchmark's readers (before the program had spans
# and scopes) on the same trace and window
EXPECTED_CHIP_TRACE = {"device_idle_share": 2.200104162987593,
                       "emb_bag_roofline": 0.0034798741442624284,
                       "step_mfu": 0.00046938123631919094}


def test_span_readers_on_a_tiny_run_agree_with_the_window(tmp_path,
                                                          monkeypatch):
    """A tiny cell served on the CPU: the program's spans give every span
    reader a value, and its pooling counters agree with a count from the
    benchmark's own record of the window (each flush's requests, padded to
    the batch with copies of its last one)."""
    import time
    import jax
    sys.path.insert(0, os.path.join(HERE, "tests"))
    import test_bench_run as tbr
    from repro.serving import trace
    tbr.stub_chip(monkeypatch)
    root = tbr.make_root(tmp_path, "tiny")
    cell = spec.cell("tiny", spec.benchmark(root), root)
    trace.clear()
    sv = run.serve_cell(cell, tbr.SEED, 1.0, jax.devices()[:1],
                        time.perf_counter())
    w = sv.window
    valid = sv.pool.valid()[sv.order]
    r = run.Run(root=root, cell=cell, seed=tbr.SEED, setup_s=sv.setup_s,
                window=w, valid=valid, chips=1, peaks={}, trace=None)
    assert read("host_prep_ms", r) > 0
    assert read("frontend_self_ms", r) > 0
    batch = cell["config"]["batch"]
    pooled = padded = 0
    for f in range(w.flushes.shape[0]):
        mine = np.flatnonzero(w.flush_of == f)
        pad = (batch - mine.size) * valid[mine.max()]
        pooled += valid[mine].sum() + pad
        padded += pad
    assert read("pad_share", r) == pytest.approx(100 * padded / pooled)
