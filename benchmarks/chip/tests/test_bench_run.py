"""The measurement loop end to end on the CPU, at a tiny size.

The chip check is stubbed here, in the test, so that the rest of a run --
set-up, the open-loop window through ``ServingFrontend`` and
``DLRMEngine``, the reference comparison, the metrics and the result line
-- runs on the CPU.  Faults planted underneath the timed path must turn
``correct`` false: an answer altered where the engine produces it, and
(on four virtual devices) the exchange between chips left out.
"""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run  # noqa: E402
import spec  # noqa: E402

DATA = os.path.join(HERE, "tests", "data")
SEED = 2**31 + 12345


def make_root(tmp, config: str, chips: int = 1, rate: float = 300.0,
              traffic: str = "hetero", limit: float = 1e-5) -> str:
    """A checkout holding the benchmark with one tiny cell, ``tiny``."""
    root = os.path.join(str(tmp), "root")
    shutil.copytree(HERE, os.path.join(root, spec.SUBDIR),
                    ignore=shutil.ignore_patterns(".cache", ".archive",
                                                  "__pycache__", "tests"))
    sub = os.path.join(root, spec.SUBDIR)
    shutil.copy(os.path.join(DATA, config + ".json"),
                os.path.join(sub, "configs", "tiny.json"))
    with open(os.path.join(sub, "workloads", "tiny.json"), "w") as f:
        json.dump({"rate_rps": rate, "pool": 256,
                   "frontend": {"admission": "none", "shed": False,
                                "linger_s": 0.0, "slo_s": 3600.0},
                   "trace_at_s": 0.2, "trace_s": 0.3,
                   "ctr_gap_limit": limit, "why": "test"}, f)
    bench = spec.benchmark()
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": f"{spec.SUBDIR}/configs/tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": "tiny", "config": "tiny",
                           "traffic": traffic, "chips": chips,
                           "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def stub_chip(monkeypatch):
    """Stub the look for a chip; and keep JAX's process-wide settings
    (compilation cache, matmul precision) as the test worker has them --
    on the CPU the precision changes nothing."""
    import jax
    monkeypatch.setattr(run, "require_accelerator",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "configure_jax", lambda cfg: None)
    real = spec.peaks
    monkeypatch.setattr(spec, "peaks",
                        lambda kind, root=spec.ROOT: real("TPU v5 lite"))


def result(capsys, root, trace=0, seconds=1.0):
    rc = run.main(["--workload", "tiny", "--seed", str(SEED), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    res = json.loads(out.out.strip().splitlines()[-1])
    return res, out.err.strip().splitlines()


def test_no_tpu_no_result(capsys):
    """On the CPU, the real entry point exits non-zero with no result."""
    rc = run.main(["--workload", "kaggle-hetero-p1", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no result" in out.err


def test_one_window_end_to_end(tmp_path, monkeypatch, capsys):
    stub_chip(monkeypatch)
    root = make_root(tmp_path, "tiny")
    res, err = result(capsys, root)
    assert res["correct"] is True, res
    assert res["attempted"] == 300 and res["failed"] == 0
    assert set(res["metrics"]) == {"p50_ms", "p99_ms", "throughput_rps",
                                   "setup_s"}
    assert res["metrics"]["p99_ms"]["unit"] == "ms"
    assert res["metrics"]["p50_ms"]["value"] <= \
        res["metrics"]["p99_ms"]["value"]
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == 1
    assert list(res)[-1] == "checks"
    assert res["checks"]["ctr_gap"]["value"] <= 1e-5
    assert res["checks"]["unserved"] == {"value": 0, "limit": 0}
    assert err[-1].startswith("[bench] check unserved")
    assert err[-2].startswith("[bench] check ctr_gap")


def test_traced_window_reports_per_layer(tmp_path, monkeypatch, capsys):
    stub_chip(monkeypatch)
    root = make_root(tmp_path, "tiny", traffic="drift")
    res, _ = result(capsys, root, trace=1)
    assert res["correct"] is True, res
    # the CPU has no device plane: only the host-side layers report
    assert set(res["metrics"]) == {"queue_wait_p99_ms", "served_p99_ms",
                                   "flush_ms"}
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_metric_that_reads_nothing_ends_a_traced_run(tmp_path, monkeypatch,
                                                     capsys):
    """Where traces have device planes, a per-layer metric the cell lists
    that finds nothing to read ends the run with no result, instead of
    leaving the metric out of the line."""
    stub_chip(monkeypatch)
    monkeypatch.setattr(run, "TRACED_PLATFORMS", ("cpu",))
    root = make_root(tmp_path, "tiny")
    with pytest.raises(run.MissingMetric, match="device_idle_share"):
        run.main(["--workload", "tiny", "--seed", str(SEED), "--seconds",
                  "1", "--trace", "1"], root=root)
    assert capsys.readouterr().out.strip().splitlines()[-1].startswith(
        "generator lateness")


def test_altered_answer_is_not_correct(tmp_path, monkeypatch, capsys):
    """One CTR altered by 1e-3 where the engine produces it."""
    from repro.serving import engine as eng
    stub_chip(monkeypatch)
    real = eng.DLRMEngine._finish_batch
    state = {"n": 0}

    def altered(self, *a, **k):
        out = np.array(real(self, *a, **k))
        state["n"] += 1
        if state["n"] == 5:
            out[0] += 1e-3
        return out

    monkeypatch.setattr(eng.DLRMEngine, "_finish_batch", altered)
    root = make_root(tmp_path, "tiny")
    res, _ = result(capsys, root)
    assert res["correct"] is False
    assert res["failed"] == 1
    assert res["checks"]["ctr_gap"]["value"] > 5e-4


@pytest.mark.parametrize("cell,config,traffic", [
    ("kaggle-hetero-p1", "widths-kaggle", "hetero"),
    ("alicpp-zipf-p1", "widths-alicpp", "zipf-onehot")])
def test_control_in_the_programs_place_is_not_correct(
        tmp_path, monkeypatch, capsys, cell, config, traffic):
    """The control -- the reference with its products at three bf16
    passes, the precision below the configuration's -- served in place of
    the program's CTRs, at the cell's widths (small tables) and the cell's
    own limit: ``correct`` comes out false."""
    import reference
    with open(os.path.join(HERE, "workloads", cell + ".json")) as f:
        limit = json.load(f)["ctr_gap_limit"]
    stub_chip(monkeypatch)
    real = run.serve_cell

    def control_served(c, *a, **k):
        sv = real(c, *a, **k)
        p, u = sv.pool, np.unique(sv.order)
        lo = reference.ctr(sv.params, p.dense[u], p.idx[u], p.mask[u],
                           n_tables=len(c["config"]["table_sizes"]),
                           precision="high", block=256)
        sv.window.ctr[:] = lo[np.searchsorted(u, sv.order)]
        return sv

    monkeypatch.setattr(run, "serve_cell", control_served)
    root = make_root(tmp_path, config, traffic=traffic, limit=limit,
                     rate=200.0)
    res, _ = result(capsys, root)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["ctr_gap"]["value"] > limit


EXCHANGE = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, {here!r})
    import jax
    import run, spec, test_bench_run as t
    real = spec.peaks
    spec.peaks = lambda kind, root=spec.ROOT: real("TPU v5 lite")
    run.require_accelerator = lambda chips: jax.devices()[:chips]
    run.CACHE_DIR = {cache!r}
    if sys.argv[1] == "cut":
        # the ring's rounds deliver nothing: each member keeps its chunk
        jax.lax.ppermute = lambda x, axis_name, perm: x
    rc = run.main(["--workload", "tiny", "--seed", "7", "--seconds", "1",
                   "--trace", "0"], root={root!r})
    sys.exit(rc)
""")


@pytest.mark.parametrize("cut", [False, True], ids=["whole", "cut"])
def test_exchange_left_out_is_not_correct(tmp_path, cut):
    """Four virtual devices, tables split over model=4, ring exchange."""
    root = make_root(tmp_path, "tiny-p4", chips=4)
    script = tmp_path / "exchange.py"
    script.write_text(EXCHANGE.format(here=HERE, root=root,
                                      cache=str(tmp_path / "cache")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(HERE, "tests"), HERE]))
    p = subprocess.run([sys.executable, str(script),
                        "cut" if cut else "whole"], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is (not cut), res["checks"]
