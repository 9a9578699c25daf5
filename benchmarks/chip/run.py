"""Chip benchmark of DLRM serving: one cell, one seed, one measured window.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs from the root of a checkout, on a machine that holds the chips the
cell asks for.  Set-up (imports, weights made on the device from the seed,
the request pool and arrival schedule, engine construction, warm-up of the
cell's one batch shape) is timed as ``setup_s``.  Then the cell's open-loop
Poisson traffic is offered for ``--seconds`` through
``ServingFrontend.try_submit`` / ``pump`` over a ``DLRMEngine`` on a
``model=chips`` mesh, and everything in flight is drained.  Once the
window has closed and the engine is gone, every served CTR is compared
with the plain reference (``reference.py``) on the same requests and
weights.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` records
a profiler trace of whole flushes inside the window and reports the
per-layer metrics, the device's busy and traced seconds and a breakdown.
The last line of standard output is the JSON result; the last lines of
standard error are the numbers compared, each beside its limit.  Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (HERE, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

import harness  # noqa: E402
import spec  # noqa: E402
import xplane  # noqa: E402
import traffic  # noqa: E402

# JAX's persistent compilation cache: a fixed directory inside the checkout
CACHE_DIR = os.path.join(HERE, ".cache", "jax")
# platforms whose traces hold the device planes every per-layer metric reads
TRACED_PLATFORMS = ("tpu",)
# JAX's event around every executable it builds: a compilation or a load from
# the persistent cache
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    pass


class MissingMetric(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def require_accelerator(chips: int) -> list:
    """The first ``chips`` TPU devices; raises when there are fewer."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU, only {devs[0].platform}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs[:chips]


def configure_jax(cfg: dict) -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])


class CompileCount:
    """Counts the executables JAX builds while the ``with`` block runs
    (``n``): warm-up should leave none for the measured window."""

    def __enter__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._seen)

    def _seen(self, event, duration, **kwargs):
        if event == BACKEND_COMPILE:
            self.n += 1


def make_mesh(devices):
    from repro.compat import make_mesh as _make_mesh
    return _make_mesh((1, len(devices)), ("data", "model"), devices=devices)


def make_pool(cell: dict, seed: int) -> traffic.Pool:
    cfg, mix = cell["config"], cell["traffic"]
    return traffic.make_pool(
        cfg["table_sizes"], cfg["n_dense_features"], cfg["max_hot"],
        cell["workload"]["pool"], mode=mix["mode"],
        t_pad=cell["arch"].stack_shape(cfg)[0],
        zipf_alpha=mix.get("zipf_alpha", 1.05), phase=mix.get("phase", 0),
        hot=mix.get("hot"), seed=seed)


def arrivals(cell: dict, seed: int, seconds: float,
             rate: Optional[float] = None):
    """(due times, pool entry of each arrival) of the cell's window."""
    mix, wl = cell["traffic"], cell["workload"]
    due = traffic.schedule(rate or wl["rate_rps"], seconds, seed=seed,
                           burstiness=mix["burstiness"],
                           burst_factor=mix["burst_factor"],
                           mean_burst_len=mix["mean_burst_len"])
    return due, traffic.pool_order(due.shape[0], wl["pool"], seed=seed)


def make_frontend(cell: dict, params):
    """A ``ServingFrontend`` over a ``DLRMEngine`` serving ``params``."""
    from repro.serving.engine import DLRMEngine
    from repro.serving.frontend import ServingFrontend
    cfg, fp = cell["config"], cell["workload"]["frontend"]
    engine = DLRMEngine(params, cell["arch"].program_config(cfg),
                        batch_size=cfg["batch"],
                        bound=cfg["bound"], microbatches=cfg["microbatches"])
    return ServingFrontend(engine, slo_s=fp["slo_s"],
                           admission=fp["admission"], shed=fp["shed"],
                           linger_s=fp["linger_s"])


def mesh_context(mesh):
    from repro.sharding import partition
    return partition.axis_rules(mesh)


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def reference_ctrs(params, cell: dict, pool: traffic.Pool, ids: np.ndarray,
                   precision: str = "highest") -> np.ndarray:
    """Reference CTRs of the pool entries ``ids``."""
    arch, cfg = cell["arch"], cell["config"]
    return arch.ctr(params, pool.dense[ids], pool.idx[ids], pool.mask[ids],
                    cfg=cfg, precision=precision,
                    block=arch.reference_block(cfg))


def ctr_gaps(params, cell: dict, pool: traffic.Pool, order: np.ndarray,
             served: np.ndarray) -> np.ndarray:
    """|served CTR - reference CTR| per arrival (nan where never served)."""
    u = np.unique(order)
    want = reference_ctrs(params, cell, pool, u)
    return np.abs(served.astype(np.float64)
                  - want[np.searchsorted(u, order)].astype(np.float64))


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""
    root: str
    cell: dict
    seed: int
    setup_s: float
    window: harness.Window
    valid: np.ndarray                  # (n,) valid indices of each arrival
    chips: int
    peaks: dict
    trace: Optional[xplane.Reduced] = None


def read_metrics(run: Run, entries: list) -> dict:
    out = {}
    for m in entries:
        value = spec.reader(m["name"], run.root)(run)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


@dataclasses.dataclass
class Served:
    """One measured window of a cell and what it was served from."""
    params: dict
    pool: traffic.Pool
    order: np.ndarray
    window: harness.Window
    setup_s: float
    memory_peak_bytes: int
    trace: Optional[xplane.Reduced]


def serve_cell(cell: dict, seed: int, seconds: float, devices: list,
               t_start: float, *, trace: bool = False,
               rate: Optional[float] = None,
               keep_trace: Optional[str] = None) -> Served:
    """Set up the cell for ``seed``, serve one window (at the cell's rate,
    or ``rate``), free the serving stack and return the record.  With
    ``keep_trace`` the traced window's profile is kept in that directory."""
    cfg, wl = cell["config"], cell["workload"]
    configure_jax(cfg)
    mesh = make_mesh(devices)
    params = cell["arch"].make_params(seed, cfg, mesh)
    pool = make_pool(cell, seed)
    due, order = arrivals(cell, seed, seconds, rate)
    with mesh_context(mesh):
        fe = make_frontend(cell, params)
        harness.warm_up(fe, pool, cfg["batch"])
        setup_s = time.perf_counter() - t_start
        log(f"set-up {setup_s:.3f} s; offering {due.shape[0]} requests "
            f"over {seconds} s at {rate or wl['rate_rps']} requests/s")
        # set-up's objects leave the collector's young generations, so a
        # collection inside the window does not walk them
        gc.collect()
        gc.freeze()
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as tmp:
            tdir = keep_trace or tmp
            with CompileCount() as compiled:
                win = harness.serve(
                    fe, pool, due, order, seconds,
                    trace_dir=tdir if trace else None,
                    trace_at=min(wl["trace_at_s"], seconds / 4),
                    trace_s=wl["trace_s"])
            peak = memory_peak(devices)
            del fe
            gc.unfreeze()
            gc.collect()
            red = None
            if trace:
                red = xplane.reduce(
                    *xplane.load(xplane.find_xplane(tdir)))
    late_ms = np.percentile(win.late, [50, 99, 100]) * 1e3
    print(f"generator lateness ms p50 {late_ms[0]:.4f} p99 "
          f"{late_ms[1]:.4f} max {late_ms[2]:.4f}; flushes "
          f"{win.flushes.shape[0]}", flush=True)
    log(f"compiles in window: {compiled.n}")
    return Served(params=params, pool=pool, order=order, window=win,
                  setup_s=setup_s, memory_peak_bytes=peak, trace=red)


def measure(cell: dict, seed: int, seconds: float, trace: bool,
            devices: list, peaks: dict, t_start: float,
            root: str = spec.ROOT) -> dict:
    """Run the cell once and return the result line (a dict)."""
    sv = serve_cell(cell, seed, seconds, devices, t_start, trace=trace)
    win, red = sv.window, sv.trace
    gaps = ctr_gaps(sv.params, cell, sv.pool, sv.order, win.ctr)
    limit = cell["workload"]["ctr_gap_limit"]
    unserved = int(np.isnan(win.ctr).sum())
    served_gaps = gaps[~np.isnan(gaps)]
    max_gap = float(served_gaps.max()) if served_gaps.size else math.inf
    failed = unserved + int((served_gaps > limit).sum())
    run = Run(root=root, cell=cell, seed=seed, setup_s=sv.setup_s,
              window=win,
              valid=sv.pool.valid()[sv.order], chips=len(devices),
              peaks=peaks, trace=red)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": sv.memory_peak_bytes}
    result = {"correct": failed == 0 and max_gap <= limit,
              "attempted": int(win.n), "failed": failed}
    if trace:
        result["metrics"] = read_metrics(run, cell["per_layer"])
        missing = [m["name"] for m in cell["per_layer"]
                   if m["name"] not in result["metrics"]]
        if missing and dev.platform in TRACED_PLATFORMS:
            # the cell lists these, so where the trace has device planes
            # they must read
            raise MissingMetric(f"the trace gave no {', '.join(missing)}; "
                                f"device planes {sorted(red.busy_ns)}")
        busy = list(red.busy_ns.values())
        device["busy_s"] = float(np.mean(busy)) * 1e-9 if busy else 0.0
        device["window_s"] = red.window_ns * 1e-9
        result["device"] = device
        result["breakdown"] = xplane.breakdown(red)
    else:
        result["metrics"] = read_metrics(run, cell["end_to_end"])
        result["device"] = device
    result["checks"] = {
        "ctr_gap": {"value": max_gap, "limit": limit},
        "unserved": {"value": unserved, "limit": 0}}
    return result


def main(argv=None, root: str = spec.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload, spec.benchmark(root), root)
    try:
        devices = require_accelerator(cell["chips"])
    except NoAccelerator as e:
        log(f"no result: {e}")
        return 3
    peaks = spec.peaks(devices[0].device_kind, root)
    result = measure(cell, args.seed, args.seconds, bool(args.trace),
                     devices, peaks, T_START, root)
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
