"""Device time of the stream-plan build per traced flush, ms, averaged over
the chips: the traced ops whose innermost program scope is ``plan``.  The
trace names an op by its HLO instruction; the program maps each instruction
to its ``op_name`` path (``repro.serving.trace.op_scopes``).  An op counts
only when no loop body or condition lies between the scope and the op, so a
``while`` loop counts once and the ops of its body, which the trace shows
inside it, not again."""
import re

import spans

SCOPES = ("plan", "pool", "stage_a", "stage_b", "exchange")
LOOP_PARTS = ("body", "cond")
_WRAPPED = re.compile(r"^[\w-]+\((.*)\)$")


def _core(segment: str) -> str:
    """``vmap(plan)`` -> ``plan``: the name inside transform wrappers."""
    m = _WRAPPED.match(segment)
    while m:
        segment = m.group(1)
        m = _WRAPPED.match(segment)
    return segment


def in_scope(path, scope: str) -> bool:
    """Whether ``scope`` is the innermost program scope of ``path`` with no
    loop body or condition between it and the op."""
    if not path:
        return False
    parts = [_core(p) for p in path.split("/")]
    inner = max((i for i, p in enumerate(parts) if p in SCOPES),
                default=None)
    if inner is None or parts[inner] != scope:
        return False
    return not any(p in LOOP_PARTS for p in parts[inner + 1:-1])


def read(run):
    red, w = run.trace, run.window
    trace = spans.recorder()
    if trace is None or red is None or w.traced is None or not red.busy_ns:
        return None
    n = len(w.traced)
    if n == 0:
        return None
    scopes = trace.op_scopes()
    ns = sum(t for name, t in red.op_ns.items()
             if in_scope(scopes.get(name), "plan"))
    if ns <= 0:
        return None
    return ns / len(red.busy_ns) / n * 1e-6
