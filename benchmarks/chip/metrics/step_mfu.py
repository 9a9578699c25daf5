"""Model FLOPs of the served requests of the traced flushes (both MLPs, the
dot interaction and the pooling adds, counted from shapes) over
chips x bf16 peak x the device time of those flushes (busy time inside
each flush's host span, averaged over the chips), in %."""
import numpy as np

import counts


def read(run):
    red, w = run.trace, run.window
    if red is None or w.traced is None or not red.busy_ns:
        return None
    busy_ns = sum(red.busy_ns.values()) / len(red.busy_ns)
    if busy_ns <= 0:
        return None
    served = np.isin(w.flush_of, list(w.traced))
    flops = float(counts.request_flops(run.cell["config"],
                                       run.valid[served]).sum())
    peak = run.peaks["bf16_flops_per_s"]
    return 100.0 * flops / (run.chips * peak * busy_ns * 1e-9)
