"""Seconds from process start to the first timed request: imports, weights
made on the device, request pool and schedule, engine construction,
compile-cache load and warm-up of the cell's one batch shape."""


def read(run):
    return run.setup_s
