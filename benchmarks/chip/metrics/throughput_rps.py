"""Requests served per second, over all the work and all the time: every
request of the window that was served, over the time from the window's
start to the last completion (the window plus the drain of what was in
flight when it closed).  Above the knee it reads the rate the system
completes; below it, the offered rate less the drain's share of the time."""
import numpy as np


def read(run):
    w = run.window
    served = np.isfinite(w.done)
    if not served.any():
        return None
    return float(served.sum()) / max(w.seconds, float(np.max(w.done[served])))
