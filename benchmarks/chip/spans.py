"""The program's own flight recorder (``repro.serving.trace``) as the
per-layer metrics read it.

The serving path records host spans per flush: ``frontend.dispatch`` holds
``engine.flush``, which holds ``engine.stack``, ``engine.prepare``,
``engine.dispatch``, ``engine.wait`` and ``engine.account``;
``frontend.complete`` follows the dispatch.  Every span carries the engine's
flush number (``flush``); ``engine.flush`` also carries the flush's request
count ``n`` and its ``pooled`` and ``padded`` valid indices.  The window's
flushes are the last ``len(run.window.flushes)`` ``engine.flush`` spans.

A program without the recorder gives every reader here ``None``.
"""
from __future__ import annotations

import dataclasses
import importlib


def recorder():
    """The program's ``repro.serving.trace`` module, or None."""
    try:
        return importlib.import_module("repro.serving.trace")
    except ImportError:
        return None


@dataclasses.dataclass
class Flushes:
    """The window's ``engine.flush`` spans and the spans that share their
    flush numbers."""
    flushes: list
    spans: list

    @property
    def n(self) -> int:
        return len(self.flushes)

    def total_ms(self, *names) -> float:
        """Summed duration of the named spans of the window's flushes."""
        return sum((s.end_ns - s.start_ns) for s in self.spans
                   if s.name in names) * 1e-6


def window(run) -> Flushes | None:
    """The recorded spans of the run's measured window, or None."""
    trace = recorder()
    k = run.window.flushes.shape[0]
    if trace is None or k == 0:
        return None
    spans = trace.spans()
    flushes = [s for s in spans if s.name == "engine.flush"]
    if len(flushes) < k:
        return None
    flushes = sorted(flushes[-k:], key=lambda s: s.attrs["flush"])
    numbers = {s.attrs["flush"] for s in flushes}
    # spans of an earlier engine with the same flush numbers ended before
    # this window's first flush began
    lo = min(s.start_ns for s in flushes)
    mine = [s for s in spans if s.attrs.get("flush") in numbers
            and s.end_ns >= lo]
    return Flushes(flushes=flushes, spans=mine)
