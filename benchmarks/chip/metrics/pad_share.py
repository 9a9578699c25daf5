"""Share of the pooled indices that pad a partial batch, %: the program's
per-flush counters on ``engine.flush`` (``padded``: indices of the copies
of the last request that fill the batch; ``pooled``: every valid index
pooled, padding included), summed over the window's flushes."""
import spans


def read(run):
    w = spans.window(run)
    if w is None:
        return None
    pooled = sum(s.attrs["pooled"] for s in w.flushes)
    if pooled <= 0:
        return None
    return 100.0 * sum(s.attrs["padded"] for s in w.flushes) / pooled
