"""Every part of the benchmark is found by its name in BENCHMARK.json, and
a new cell, configuration, mix or metric needs no edit to a file."""
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import spec  # noqa: E402

BENCH = spec.benchmark()


def test_command_and_paths():
    assert BENCH["command"][1] == os.path.join(spec.SUBDIR, "run.py")
    assert BENCH["paths"] == [spec.SUBDIR]


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(name):
    cell = spec.cell(name, BENCH)
    cfg = cell["config"]
    assert cfg["chips"] == cell["chips"]
    assert cell["workload"]["why"] == next(
        w["why"] for w in BENCH["workloads"] if w["name"] == name)
    assert cell["workload"]["rate_rps"] > 0
    assert cell["traffic"]["mode"]
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"]
    # each per-layer metric moves an end-to-end metric the cell reports
    assert {m["moves"] for m in cell["per_layer"]} <= e2e
    for m in cell["end_to_end"] + cell["per_layer"]:
        assert callable(spec.reader(m["name"]))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_every_configuration_file_is_its_own(entry):
    cfg = json.load(open(os.path.join(spec.ROOT, entry["file"])))
    assert cfg["name"] == entry["name"]
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert entry["file"].startswith(spec.SUBDIR + "/")
    used = {w["config"] for w in BENCH["workloads"]}
    assert entry["name"] in used


def test_metric_readers_exist_for_every_metric():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert os.path.isfile(os.path.join(HERE, "metrics",
                                           m["name"] + ".py"))


def test_peaks_refuse_an_unknown_device():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v99 imaginary")


def test_a_new_cell_needs_only_new_files(tmp_path):
    """Copy the tree, add a configuration, a mix, a cell and a metric as
    new files and entries, and find each by name; no file is edited."""
    root = tmp_path
    shutil.copytree(HERE, root / spec.SUBDIR,
                    ignore=shutil.ignore_patterns(".cache", ".archive",
                                                  "__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    before = {p: p.read_bytes() for p in (root / spec.SUBDIR).rglob("*")
              if p.is_file()}
    sub = root / spec.SUBDIR
    cfg = json.load(open(sub / "configs" / "dlrm-kaggle.json"))
    cfg["name"] = "dlrm-new"
    (sub / "configs" / "dlrm-new.json").write_text(json.dumps(cfg))
    (sub / "traffic" / "new-mix.json").write_text(json.dumps(
        {"mode": "uniform", "burstiness": 0.0, "burst_factor": 8.0,
         "mean_burst_len": 16}))
    wl = json.load(open(sub / "workloads" / "kaggle-hetero-p1.json"))
    (sub / "workloads" / "new-cell.json").write_text(json.dumps(wl))
    (sub / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["configs"].append({"name": "dlrm-new", "source": "s",
                             "file": f"{spec.SUBDIR}/configs/dlrm-new.json",
                             "reduced": [], "why": "w"})
    bench["workloads"].append({"name": "new-cell", "config": "dlrm-new",
                               "traffic": "new-mix", "chips": 1, "why": "w"})
    bench["per_layer"].append({"name": "new_metric", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "frontend", "moves": "p99_ms",
                               "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.cell("new-cell", spec.benchmark(str(root)), str(root))
    assert cell["config"]["name"] == "dlrm-new"
    assert cell["traffic"]["mode"] == "uniform"
    assert [m["name"] for m in cell["per_layer"]][-1] == "new_metric"
    assert spec.reader("new_metric", str(root))(None) == 42.0
    old = spec.cell("kaggle-hetero-p1", spec.benchmark(str(root)), str(root))
    assert "new_metric" not in [m["name"] for m in old["per_layer"]]
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
