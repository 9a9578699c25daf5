"""Mean time of a dispatching ``pump``, ms: the benchmark's span from the
call to the CTRs on the host, over every flush of the window."""
import numpy as np


def read(run):
    f = run.window.flushes
    if f.shape[0] == 0:
        return None
    return float(np.mean(f[:, 1] - f[:, 0])) * 1e3
