"""The serving path's flight recorder: host spans and device scope names.

Host spans.  ``span(name, **attrs)`` is a context manager that enters a
``jax.profiler.TraceAnnotation`` (so the span lands in any profiler trace,
on the same clock as the device ops) and, on exit, appends one
:class:`Span` to a process-wide buffer of the last :data:`CAPACITY` spans,
timed with ``time.perf_counter_ns`` (the clock the frontend stamps requests
with).  ``parent`` is the name of the span that was open on the same thread
when this one began.  :func:`spans` returns the buffer: an operator's
record of the most recent flushes.  The serving path opens nine spans a
flush and none per request.

Device scopes.  The step is compiled under ``jax.named_scope`` names
(``plan``, ``pool``, ``stage_a``, ``stage_b``, ``exchange``), which XLA
keeps as each instruction's ``op_name`` metadata but a TPU trace does not
show.  The engine registers each jitted step it dispatches
(:func:`note_step`, first flush only, shapes kept, no device buffers), and
:func:`op_scopes` compiles the registered steps again on demand and maps
every HLO instruction name -- the name a trace gives an op -- to its
``op_name`` path.
"""
from __future__ import annotations

import collections
import contextlib
import re
import threading
import time
from typing import NamedTuple, Optional

import jax

# spans kept: nine a flush, so the last seven thousand flushes
CAPACITY = 1 << 16
# jitted steps kept for op_scopes (the engine re-jits on retunes)
STEP_CAPACITY = 16


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[str]
    thread: int
    attrs: dict


_buffer: collections.deque = collections.deque(maxlen=CAPACITY)
_local = threading.local()


def _open_spans() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """``with span("engine.flush", flush=n) as s: ...; s.set(n=512)``.
    ``set`` adds attributes known only inside the span."""

    __slots__ = ("name", "attrs", "parent", "start", "_ann", "_stack")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        stack = self._stack = _open_spans()
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __exit__(self, *exc) -> bool:
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._stack.pop()
        # a plain tuple here; spans() makes them Span records
        _buffer.append((self.name, self.start, end, self.parent,
                        threading.get_ident(), self.attrs))
        return False


def spans() -> list:
    """The recorded spans, oldest first (at most :data:`CAPACITY`)."""
    return [Span._make(s) for s in list(_buffer)]


def clear() -> None:
    """Forget every recorded span and registered step."""
    _buffer.clear()
    with _steps_lock:
        _steps.clear()


# -- device scopes ----------------------------------------------------------

# id(jitted) -> [jitted, abstract args, mesh, scopes or None]
_steps: collections.OrderedDict = collections.OrderedDict()
_steps_lock = threading.Lock()


def _abstract(a):
    """A shape-only stand-in for a step argument: the sharding is kept
    where the array was committed to it, as the call saw it."""
    if isinstance(a, jax.Array):
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype, weak_type=a.weak_type,
            sharding=a.sharding if a.committed else None)
    if hasattr(a, "shape") and hasattr(a, "dtype"):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)
    return a


def note_step(jitted, args, mesh=None) -> None:
    """Register a jitted step at its first dispatch with ``args`` (kept as
    shapes and shardings) under ``mesh``.  Later calls are one lookup."""
    key = id(jitted)
    if key in _steps:
        return
    entry = [jitted, jax.tree.map(_abstract, args), mesh, None]
    with _steps_lock:
        _steps[key] = entry
        while len(_steps) > STEP_CAPACITY:
            _steps.popitem(last=False)


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_TOKEN = re.compile(r"[\w.\-]+")


def scopes_of_hlo(text: str) -> dict:
    """{instruction name: op_name path} of an HLO module's text.  An
    instruction the compiler added carries no metadata; it takes the path
    of the first operand that has one."""
    lines = dict(_INSTR.findall(text))
    out = {}
    for name, line in lines.items():
        m = _OP_NAME.search(line)
        if m:
            out[name] = m.group(1)
    changed = True
    while changed:
        changed = False
        for name, line in lines.items():
            if name in out:
                continue
            for tok in _TOKEN.findall(line.partition(", metadata=")[0]):
                if tok != name and tok in out:
                    out[name] = out[tok]
                    changed = True
                    break
    return out


def _compile_scopes(jitted, args, mesh) -> dict:
    from repro.sharding import partition
    ctx = partition.axis_rules(mesh) if mesh is not None \
        else contextlib.nullcontext()
    with ctx:
        text = jitted.lower(*args).compile().as_text()
    return scopes_of_hlo(text)


def op_scopes() -> dict:
    """{HLO instruction name: op_name path} over every registered step,
    each compiled again (from the compilation cache where one is set) the
    first time it is asked for."""
    with _steps_lock:
        entries = list(_steps.values())
    out = {}
    for entry in entries:
        if entry[3] is None:
            entry[3] = _compile_scopes(*entry[:3])
        out.update(entry[3])
    return out
