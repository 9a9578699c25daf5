"""99th percentile of the frontend's queue wait, ms: the frontend's dispatch
stamp (``ServedRequest.queue_delay_s``) minus the request's due time, over
every request of the window."""
import numpy as np


def read(run):
    w = run.window
    wait = w.dispatch - w.due
    if np.isnan(wait).all():
        return None
    return float(np.nanpercentile(wait, 99)) * 1e3
