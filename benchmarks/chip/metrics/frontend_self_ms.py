"""The frontend's own time per flush, ms: the program's
``frontend.dispatch`` span less the ``engine.flush`` it holds, plus
``frontend.complete`` (the served requests' records), averaged over the
window's flushes."""
import spans


def read(run):
    w = spans.window(run)
    if w is None:
        return None
    inside = sum(s.end_ns - s.start_ns for s in w.flushes
                 if s.parent == "frontend.dispatch") * 1e-6
    own = w.total_ms("frontend.dispatch", "frontend.complete") - inside
    return own / w.n
