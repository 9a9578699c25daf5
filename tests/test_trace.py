"""The serving path's recorder (``repro.serving.trace``): host spans, the
pooling counters and the map from HLO instructions to device scopes."""
import gc
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax

from repro.configs.base import DLRMConfig
from repro.data import synthetic as S
from repro.serving import trace
from repro.serving.engine import DLRMEngine
from repro.serving.frontend import ServingFrontend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINE_PARTS = {"engine.stack", "engine.prepare", "engine.dispatch",
                "engine.wait", "engine.account"}
SCOPES = ("plan", "pool", "stage_a", "stage_b", "exchange")


@pytest.fixture(autouse=True)
def empty_recorder():
    trace.clear()
    yield
    trace.clear()


def _cfg(**kw):
    base = dict(table_sizes=(40, 60, 30, 50, 20, 70), embed_dim=8,
                n_dense_features=4, bottom_mlp=(16, 8), top_mlp=(16, 1),
                sparse_backend="ref", max_hot=4)
    base.update(kw)
    return DLRMConfig("t", **base)


def _engine(cfg, batch_size=16, **kw):
    from repro.models import dlrm as D
    params = D.init_dlrm(jax.random.PRNGKey(0), cfg, n_shards=1)
    return DLRMEngine(params, cfg, batch_size=batch_size, bound=1,
                      microbatches=2, exchange="dense", **kw)


def _streamed_cfg():
    """Kernel pooling (interpreted) over streamed row blocks: the step
    builds stream plans, so every device scope has ops."""
    return _cfg(table_sizes=(300, 200, 500), sparse_backend="interpret",
                row_block=128)


def _mesh_1():
    from repro.compat import make_mesh
    return make_mesh((1, 1), ("data", "model"))


def _by_name(spans, name):
    return [s for s in spans if s.name == name]


def scope_hits(scopes: dict) -> dict:
    """Instructions per device scope name found in the op_name paths."""
    hits = dict.fromkeys(SCOPES, 0)
    for path in scopes.values():
        for seg in path.split("/"):
            for name in SCOPES:
                if seg == name or seg.endswith(f"({name})"):
                    hits[name] += 1
    return hits


# -- spans ------------------------------------------------------------------


def test_spans_nest_with_their_parent_and_carry_attributes():
    with trace.span("a", flush=3) as outer:
        with trace.span("b", flush=3) as inner:
            inner.set(n=7)
        with trace.span("c"):
            pass
        outer.set(pooled=11)
    b, c, a = trace.spans()
    assert (a.name, a.parent, a.attrs) == ("a", None,
                                           {"flush": 3, "pooled": 11})
    assert (b.name, b.parent, b.attrs) == ("b", "a", {"flush": 3, "n": 7})
    assert (c.name, c.parent) == ("c", "a")
    assert a.start_ns <= b.start_ns <= b.end_ns <= c.start_ns \
        <= c.end_ns <= a.end_ns
    assert a.thread == b.thread == threading.get_ident()


def test_a_span_on_another_thread_has_no_parent_there():
    def work():
        with trace.span("other"):
            pass

    with trace.span("main"):
        t = threading.Thread(target=work)
        t.start()
        t.join()
    other, main = trace.spans()
    assert other.parent is None and other.thread != main.thread


def test_a_span_closes_on_an_exception():
    with pytest.raises(KeyError):
        with trace.span("outer"):
            with trace.span("inner"):
                raise KeyError("x")
    with trace.span("after"):
        pass
    assert [(s.name, s.parent) for s in trace.spans()] == [
        ("inner", "outer"), ("outer", None), ("after", None)]


def test_the_buffer_stays_bounded():
    for k in range(trace.CAPACITY + 100):
        with trace.span("s", k=k):
            pass
    got = trace.spans()
    assert len(got) == trace.CAPACITY
    assert got[0].attrs["k"] == 100 and got[-1].attrs["k"] == \
        trace.CAPACITY + 99


# -- engine and frontend spans ------------------------------------------------


def test_an_inline_flush_emits_its_parts_with_its_flush_number():
    cfg = _cfg()
    eng = _engine(cfg)
    reqs = S.request_stream(cfg, 16 + 5, rate_rps=1e6, seed=3)
    for r in reqs[:16]:
        eng.submit(r.dense, r.idx, r.mask)
    for r in reqs[16:]:
        eng.submit(r.dense, r.idx, r.mask)
    eng.flush()
    spans = trace.spans()
    flushes = _by_name(spans, "engine.flush")
    assert [f.attrs["flush"] for f in flushes] == [0, 1]
    assert [f.attrs["n"] for f in flushes] == [16, 5]
    for f in flushes:
        parts = [s for s in spans if s.name in ENGINE_PARTS
                 and s.attrs["flush"] == f.attrs["flush"]]
        assert {s.name for s in parts} == ENGINE_PARTS
        for s in parts:
            assert s.parent == "engine.flush"
            assert f.start_ns <= s.start_ns <= s.end_ns <= f.end_ns
    assert eng.steps == 2
    # a few spans a flush, none a request
    assert len(spans) <= 10 * len(flushes)


def test_frontend_dispatch_holds_the_engine_flush_of_its_requests():
    cfg = _cfg()
    eng = _engine(cfg)
    fe = ServingFrontend(eng, slo_s=10.0, admission="none", shed=False,
                         lookahead=False)
    done = []
    for r in S.request_stream(cfg, 40, rate_rps=1e6, seed=4):
        fe.try_submit(r.dense, r.idx, r.mask)
        done += fe.pump()
    done += fe.drain()
    spans = trace.spans()
    dispatches = _by_name(spans, "frontend.dispatch")
    flushes = _by_name(spans, "engine.flush")
    assert len(dispatches) == len(flushes) >= 2
    for d, f in zip(dispatches, flushes):
        assert f.parent == "frontend.dispatch"
        assert f.attrs["flush"] == d.attrs["flush"]
        assert f.attrs["n"] == d.attrs["n"]
        assert d.start_ns <= f.start_ns <= f.end_ns <= d.end_ns
    completes = _by_name(spans, "frontend.complete")
    assert [c.attrs["flush"] for c in completes] == \
        [f.attrs["flush"] for f in flushes]
    for c in completes:
        assert c.parent is None          # beside the dispatch, not in it
    n_of = {f.attrs["flush"]: f.attrs["n"] for f in flushes}
    per_flush = {}
    for r in done:
        per_flush[r.flush] = per_flush.get(r.flush, 0) + 1
    assert per_flush == n_of


def test_pooled_and_padded_indices_are_exact_on_a_partial_batch():
    cfg = _cfg()
    eng = _engine(cfg)
    reqs = S.request_stream(cfg, 16 + 5, rate_rps=1e6, seed=5)

    def valid(r):
        return int((np.asarray(r.mask) > 0).sum())

    for r in reqs:
        eng.submit(r.dense, r.idx, r.mask)
    eng.flush()
    full = sum(valid(r) for r in reqs[:16])
    part = sum(valid(r) for r in reqs[16:])
    padded = (16 - 5) * valid(reqs[-1])
    assert eng.stats.pooled_indices == full + part + padded
    assert eng.stats.padded_indices == padded
    f0, f1 = _by_name(trace.spans(), "engine.flush")
    assert (f0.attrs["pooled"], f0.attrs["padded"]) == (full, 0)
    assert (f1.attrs["pooled"], f1.attrs["padded"]) == (part + padded,
                                                        padded)
    d = eng.stats.to_dict()
    assert d["pooled_indices"] == full + part + padded
    assert "member_bytes" not in d


def test_plan_pipeline_emits_plan_and_watch_spans():
    from repro.sharding import partition
    cfg = _streamed_cfg()
    with partition.axis_rules(_mesh_1()):
        eng = _engine(cfg, batch_size=8, plan_pipeline=True)
        reqs = S.request_stream(cfg, 8 + 3, rate_rps=1e6, seed=6)
        outs = [eng.submit(r.dense, r.idx, r.mask) for r in reqs]
        outs.append(eng.drain())
    assert sum(o.shape[0] for o in outs if o is not None) == 11
    spans = trace.spans()
    plans = _by_name(spans, "engine.plan")
    watches = _by_name(spans, "engine.watch")
    assert [s.attrs["flush"] for s in plans] == [0, 1]
    assert sorted(s.attrs["flush"] for s in watches) == [0, 1]
    main = threading.get_ident()
    for s in plans:
        assert s.parent == "engine.flush" and s.thread == main
    for s in watches:
        assert s.parent is None and s.thread != main
    assert eng.stats.pooled_indices > 0
    assert eng.stats.padded_indices == \
        5 * int((np.asarray(reqs[-1].mask) > 0).sum())


# -- device scopes ------------------------------------------------------------


def test_hlo_text_parse():
    text = (
        'ENTRY %main.5 (p: f32[4]) -> f32[4] {\n'
        '  %while.79 = (s32[]) while(%t), condition=%c, body=%b, '
        'metadata={op_name="jit(step)/stage_a/pool/plan/while" '
        'source_file="x.py" source_line=3}\n'
        '  ROOT %embedding_bag_stacked_op.8 = f32[4] custom-call(%p), '
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(step)/stage_a/pool/pallas_call"}\n'
        '  %copy.1 = f32[4] copy(%p)\n'
        'fused.2 = f32[4] add(a, b), metadata={op_name="jit(f)/add"}\n'
        '  %reshape.9 = u32[1,4]{1,0:T(8,128)S(1)} reshape(%reshape.8)\n'
        '  %reshape.8 = u32[4]{0} reshape(%while.79)\n'
        '}\n')
    assert trace.scopes_of_hlo(text) == {
        "while.79": "jit(step)/stage_a/pool/plan/while",
        "embedding_bag_stacked_op.8": "jit(step)/stage_a/pool/pallas_call",
        "fused.2": "jit(f)/add",
        # added by the compiler: the path of the operand it was made from
        "reshape.8": "jit(step)/stage_a/pool/plan/while",
        "reshape.9": "jit(step)/stage_a/pool/plan/while"}


def test_op_scopes_maps_ops_to_each_one_chip_scope():
    from repro.sharding import partition
    cfg = _streamed_cfg()
    mesh = _mesh_1()
    with partition.axis_rules(mesh):
        eng = _engine(cfg, batch_size=8)
        reqs = S.request_stream(cfg, 8, rate_rps=1e6, seed=7)
        for r in reqs:
            eng.submit(r.dense, r.idx, r.mask)
        eng.flush()
        eng.flush()                       # nothing pending: no new step
        scopes = trace.op_scopes()
        # the registered shapes compile to the instructions the call ran
        fitted = eng._fit_batch(*(np.stack([getattr(r, k) for r in reqs])
                                  for k in ("dense", "idx", "mask")))
        real = trace.scopes_of_hlo(eng._step.lower(
            *eng._step_args(*fitted)).compile().as_text())
    assert scopes == real
    hits = scope_hits(scopes)
    for name in ("plan", "pool", "stage_a", "stage_b"):
        assert hits[name] > 0, (name, hits)
    assert eng.steps == 1


def test_op_scopes_survives_the_engine():
    cfg = _cfg()
    eng = _engine(cfg, batch_size=4)
    for r in S.request_stream(cfg, 4, rate_rps=1e6, seed=8):
        eng.submit(r.dense, r.idx, r.mask)
    eng.flush()
    del eng
    gc.collect()
    assert scope_hits(trace.op_scopes())["pool"] > 0


def test_op_scopes_names_the_exchange_on_four_devices():
    code = """
import jax
from repro.compat import make_mesh
from repro.configs.base import DLRMConfig
from repro.data import synthetic as S
from repro.models import dlrm as D
from repro.serving import trace
from repro.serving.engine import DLRMEngine
from repro.sharding import partition
cfg = DLRMConfig("t", table_sizes=(300, 200, 500, 100), embed_dim=8,
                 n_dense_features=4, bottom_mlp=(16, 8), top_mlp=(16, 1),
                 sparse_backend="interpret", row_block=128, max_hot=4,
                 exchange_pipeline=%r)
mesh = make_mesh((1, 4), ("data", "model"))
with partition.axis_rules(mesh):
    params = D.init_dlrm(jax.random.PRNGKey(0), cfg, n_shards=4, mesh=mesh)
    eng = DLRMEngine(params, cfg, batch_size=16, bound=1, microbatches=2)
    for r in S.request_stream(cfg, 16, rate_rps=1e6, seed=9):
        eng.submit(r.dense, r.idx, r.mask)
    eng.flush()
scopes = trace.op_scopes()
names = {n for v in scopes.values() for n in v.split("/")}
for want in ("plan", "pool", "stage_a", "stage_b", "exchange"):
    assert want in names or any(n.endswith("(" + want + ")")
                                for n in names), (want, sorted(names))
kinds = {k.split(".")[0] for k, v in scopes.items()
         if "/exchange/" in v or v.endswith("/exchange")}
print(sorted(kinds))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # the collective itself is named by the exchange scope
    for pipe, ops in (("ring", ("ppermute", "collective-permute")),
                      ("mono", ("all_to_all", "all-to-all"))):
        r = subprocess.run([sys.executable, "-c", code % pipe], env=env,
                           capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
        assert any(op in r.stdout for op in ops), (pipe, r.stdout)
