"""Share of the traced window, %, in which no operation ran on a device:
1 - (union of its op intervals / window), averaged over the cell's chips."""


def read(run):
    red = run.trace
    if red is None or not red.busy_ns or red.window_ns <= 0:
        return None
    busy = sum(red.busy_ns.values()) / len(red.busy_ns)
    return 100.0 * (1.0 - busy / red.window_ns)
