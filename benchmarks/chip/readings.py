"""Readings that set a cell's CTR limit: the program's and the control's.

    python3 benchmarks/chip/readings.py --workload <cell> --seconds <s> \
        --seeds 11 12 13 ...

For each seed, in one process: serve one window of the cell through the
timed path exactly as ``run.py`` does, then compare every served CTR with
the reference at the configuration's precision ("highest"), and compare
the control -- the reference with its products in the next precision down
("high", three bf16 passes) -- with the same reference on the same
requests.  One JSON line per seed; the last line holds the lower reading
(the largest program gap) and the upper reading (the smallest control gap).
Runs on the chip; it is not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import numpy as np

import run
import spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload, spec.benchmark())
    try:
        devices = run.require_accelerator(cell["chips"])
    except run.NoAccelerator as e:
        run.log(f"no readings: {e}")
        return 3
    program, control = [], []
    for seed in args.seeds:
        sv = run.serve_cell(cell, seed, args.seconds, devices,
                            time.perf_counter())
        u = np.unique(sv.order)
        want = run.reference_ctrs(sv.params, cell, sv.pool, u)
        gap = np.abs(sv.window.ctr.astype(np.float64)
                     - want[np.searchsorted(u, sv.order)])
        lo = np.abs(run.reference_ctrs(sv.params, cell, sv.pool, u,
                                       precision="high")
                    - want.astype(np.float64))
        rec = {"seed": seed, "served": int(np.isfinite(gap).sum()),
               "unserved": int(np.isnan(gap).sum()),
               "program_gap": float(np.nanmax(gap)),
               "program_gap_p50": float(np.nanmedian(gap)),
               "control_gap": float(lo.max()),
               "control_gap_p50": float(np.median(lo))}
        program.append(rec["program_gap"])
        control.append(rec["control_gap"])
        print(json.dumps(rec), flush=True)
        del sv
        gc.collect()
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "lower": max(program), "upper": min(control)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
