"""Find the benchmark's parts by the names in ``BENCHMARK.json``.

  configs[<name>].file          the configuration as it is run; its
                                ``arch`` names archs/<arch>.py
  archs/<arch>.py               what depends on the model's equations:
                                program config, weights, reference, counts
  traffic/<traffic>.json        the traffic mix
  workloads/<cell>.json         the cell: offered rate, request pool,
                                frontend policy, trace window, limits
  metrics/<metric>.py           one reader per metric: ``read(run)``
  peaks.json                    device peaks keyed by ``device_kind``

Adding a cell, a mix, a configuration, an architecture or a metric adds
files and entries; no file here needs an edit.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

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
    configuration (``config``), its architecture module (``arch``), the mix
    (``traffic``), the cell's own file (``workload``) and the metrics it
    reports (``end_to_end``, ``per_layer``: lists of BENCHMARK.json metric
    entries)."""
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r}; have {sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_file = configs[entry["config"]]["file"]
    cfg = _json(os.path.join(root, cfg_file))
    here = os.path.join(root, SUBDIR)

    def mine(m):
        return name in m.get("workloads", [name])

    return {
        "name": name, "chips": entry["chips"],
        "config": cfg, "arch": arch(cfg, root, cfg_file),
        "traffic": _json(os.path.join(here, "traffic",
                                      entry["traffic"] + ".json")),
        "workload": _json(os.path.join(here, "workloads", name + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def _load(kind: str, name: str, path: str):
    """The module at ``path``, loaded by path under a name of its own (the
    benchmark's files are no package, and ``repro`` is the program's)."""
    modname = f"bench_{kind}_" + name.replace(".", "_").replace("-", "_")
    mod_spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[modname] = mod
    mod_spec.loader.exec_module(mod)
    return mod


def arch(cfg: dict, root: str = ROOT, cfg_file: str = "the configuration"):
    """The module ``archs/<arch>.py`` that ``cfg`` names.  There is no
    default: a configuration without ``arch``, or naming no file there, is
    an error that names ``cfg_file``."""
    here = os.path.join(root, SUBDIR, "archs")
    have = sorted(f[:-3] for f in os.listdir(here) if f.endswith(".py"))
    if "arch" not in cfg:
        raise KeyError(f"{cfg_file} names no 'arch'; have {have}")
    if cfg["arch"] not in have:
        raise KeyError(f"{cfg_file}: no architecture {cfg['arch']!r} in "
                       f"{SUBDIR}/archs; have {have}")
    return _load("arch", cfg["arch"], os.path.join(here, cfg["arch"] + ".py"))


def reader(metric: str, root: str = ROOT):
    """The ``read(run) -> float | None`` of ``metrics/<metric>.py``."""
    return _load("metric", metric,
                 os.path.join(root, SUBDIR, "metrics", metric + ".py")).read


def peaks(device_kind: str, root: str = ROOT) -> dict:
    """Peaks of ``device_kind``; a device missing from the table is an
    error, never a default."""
    table = _json(os.path.join(root, SUBDIR, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; have {sorted(table)}")
    return table[device_kind]
