"""Host work serial with the device, per flush, ms: the program's spans
``engine.stack`` (the pending rows stacked into batch arrays),
``engine.prepare`` (fitting the batch, the step's arguments, the copy to
the device) and ``engine.dispatch`` (the jitted step's call), summed and
averaged over the window's flushes."""
import spans


def read(run):
    w = spans.window(run)
    if w is None:
        return None
    return w.total_ms("engine.stack", "engine.prepare",
                      "engine.dispatch") / w.n
