"""Knee sweep of a cell: one run of the cell at each of several offered rates.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 400 600 800 ... [--keep-trace DIR]

One process; for each rate, ``run.serve_cell`` sets the cell up and serves
one open-loop window of ``--seconds``, exactly as a benchmark run does at
the cell's own rate.  Per rate it prints the metric readers' throughput,
p50, p99 and flush time, and the mean latency of the window's first and
last quarters.  A rate is sustained when the throughput stays within
``--tolerance`` of the offered rate and the queue does not grow across the
window (the last quarter's mean latency within 1.3x the first quarter's).
A window holds exactly rate x seconds requests, so the throughput reads
rate x seconds / (seconds + drain): choose ``--seconds`` well above ten
latencies, so that only a queue left at the window's close fails it.
The knee is the highest rate sustained at that rate and every lower one;
the last line gives it and 0.8x of it.  ``--keep-trace DIR`` keeps the
profile of a short traced run at the lowest rate under DIR.  Runs on the
chip; it is not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

import run
import spec
import xplane

READ = ("throughput_rps", "p50_ms", "p99_ms", "flush_ms")


def summary(r: run.Run, rate: float) -> dict:
    w = r.window
    lat = w.done - w.due
    q = max(1, w.n // 4)
    rec = {"rate": rate, "n": w.n}
    rec.update({m: spec.reader(m, r.root)(r) for m in READ})
    rec.update({"first_quarter_ms": float(np.mean(lat[:q])) * 1e3,
                "last_quarter_ms": float(np.mean(lat[-q:])) * 1e3,
                "flushes": int(w.flushes.shape[0]),
                "late_p99_ms": float(np.percentile(w.late, 99)) * 1e3})
    return rec


def sustained(rec: dict, tolerance: float) -> bool:
    return (rec["throughput_rps"] >= (1.0 - tolerance) * rec["rate"]
            and rec["last_quarter_ms"] <= 1.3 * rec["first_quarter_ms"])


def knee_of(records: list) -> float | None:
    """The highest rate sustained at that rate and every lower one."""
    knee = None
    for rec in sorted(records, key=lambda r: r["rate"]):
        if not rec["sustained"]:
            break
        knee = rec["rate"]
    return knee


def serve(cell, seed, seconds, devices, rate, keep_trace=None):
    if keep_trace is not None:
        # a short trace: about two flushes from half a second in
        cell = dict(cell, workload=dict(cell["workload"], trace_at_s=0.5,
                                        trace_s=1.0))
    t = time.perf_counter()
    sv = run.serve_cell(cell, seed, seconds, devices, t, rate=rate,
                        trace=keep_trace is not None, keep_trace=keep_trace)
    r = run.Run(root=spec.ROOT, cell=cell, seed=seed, setup_s=sv.setup_s,
                window=sv.window, valid=sv.pool.valid()[sv.order],
                chips=len(devices), peaks={}, trace=sv.trace)
    return r, time.perf_counter() - t


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--tolerance", type=float, default=0.1)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload, spec.benchmark())
    try:
        devices = run.require_accelerator(cell["chips"])
    except run.NoAccelerator as e:
        run.log(f"no sweep: {e}")
        return 3
    rates = sorted(args.rates)
    if args.keep_trace:
        os.makedirs(args.keep_trace, exist_ok=True)
        r, _ = serve(cell, args.seed, 2.0, devices, rates[0],
                     keep_trace=args.keep_trace)
        path = xplane.find_xplane(args.keep_trace)
        print(json.dumps({"trace": path, "size": os.path.getsize(path),
                          "devices": sorted(r.trace.busy_ns),
                          "flushes": len(r.trace.flushes),
                          "breakdown": xplane.breakdown(r.trace)}),
              flush=True)
        del r
        gc.collect()
    records = []
    for rate in rates:
        r, wall = serve(cell, args.seed, args.seconds, devices, rate)
        rec = summary(r, rate)
        rec["wall_s"] = wall
        rec["sustained"] = sustained(rec, args.tolerance)
        records.append(rec)
        print(json.dumps(rec), flush=True)
        del r
        gc.collect()
    knee = knee_of(records)
    print(json.dumps({"workload": args.workload, "knee": knee,
                      "rate": 0.8 * knee if knee else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
