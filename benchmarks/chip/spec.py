"""Find the benchmark's parts by the names in ``BENCHMARK.json``.

  configs[<name>].file          the configuration as it is run
  traffic/<traffic>.json        the traffic mix
  workloads/<cell>.json         the cell: offered rate, request pool,
                                frontend policy, trace window, limits
  metrics/<metric>.py           one reader per metric: ``read(run)``
  peaks.json                    device peaks keyed by ``device_kind``

Adding a cell, a mix, a configuration or a metric adds files and entries;
no file here needs an edit.
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# this directory, relative to the root of a checkout
SUBDIR = os.path.relpath(HERE, ROOT)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def cell(name: str, bench: dict, root: str = ROOT) -> dict:
    """The resolved cell ``name``: its BENCHMARK.json entry with the
    configuration (``config``), the mix (``traffic``), the cell's own file
    (``workload``) and the metrics it reports (``end_to_end``,
    ``per_layer``: lists of BENCHMARK.json metric entries)."""
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r}; have {sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = _json(os.path.join(root, configs[entry["config"]]["file"]))
    here = os.path.join(root, SUBDIR)

    def mine(m):
        return name in m.get("workloads", [name])

    return {
        "name": name, "chips": entry["chips"],
        "config": cfg,
        "traffic": _json(os.path.join(here, "traffic",
                                      entry["traffic"] + ".json")),
        "workload": _json(os.path.join(here, "workloads", name + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def reader(metric: str, root: str = ROOT):
    """The ``read(run) -> float | None`` of ``metrics/<metric>.py``."""
    path = os.path.join(root, SUBDIR, "metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, root: str = ROOT) -> dict:
    """Peaks of ``device_kind``; a device missing from the table is an
    error, never a default."""
    table = _json(os.path.join(root, SUBDIR, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; have {sorted(table)}")
    return table[device_kind]
