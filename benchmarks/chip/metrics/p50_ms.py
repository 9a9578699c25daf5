"""Median request latency, ms: from each request's due arrival time to its
CTR back in the client loop, over every request due in the window."""
import numpy as np


def read(run):
    w = run.window
    return float(np.percentile(w.done - w.due, 50)) * 1e3
