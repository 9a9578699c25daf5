"""99th percentile of request latency, ms (due arrival time to CTR back in
the client loop), over every request due in the window; no medians of
chunks."""
import numpy as np


def read(run):
    w = run.window
    return float(np.percentile(w.done - w.due, 99)) * 1e3
