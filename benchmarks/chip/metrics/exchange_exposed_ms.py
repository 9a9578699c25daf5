"""Exposed exchange per flush, ms: device time inside collective ops
(all-to-all, collective-permute and their start/done halves) during which
no other op runs on that device, averaged over the chips, per traced
flush.  A member that waits on the slowest shows here, not as idle."""


def read(run):
    red, w = run.trace, run.window
    if red is None or w.traced is None or len(red.exposed_ns) < 2:
        return None
    n = len(w.traced)
    if n == 0:
        return None
    per_dev = sum(red.exposed_ns.values()) / len(red.exposed_ns)
    return per_dev / n * 1e-6
