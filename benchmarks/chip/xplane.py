"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

Input: the device planes (``/device:TPU:<n>``), whose op line holds one event
per XLA operation or kernel, named here by its HLO instruction, and the host plane, which holds the benchmark's
own ``bench.*`` spans (``jax.profiler.TraceAnnotation``).  The traced window
is the host span ``bench.traced``.  All times are nanoseconds on the
profiler's one clock.

Output (:class:`Reduced`), each clipped to the window:
  busy_ns[d]       union of the op intervals of device d;
  op_ns[name]      device time of each op name, summed over devices;
  exposed_ns[d]    time of device d inside a collective op while no other op
                   runs there (waiting in a collective counts as busy, so
                   this, not the idle share, shows members waiting on the
                   slowest);
  gaps             idle intervals of each device (``breakdown`` labels the
                   longest with the innermost ``bench.*`` host span open at
                   its midpoint);
  flushes          the host ``bench.pump`` spans that dispatched a batch.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops",)
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"
FLUSH_SPAN = "bench.pump"
COLLECTIVE_MARKS = ("all-to-all", "collective-permute", "all-reduce",
                    "all-gather", "reduce-scatter", "send", "recv")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Reduced:
    window: tuple
    busy_ns: dict
    op_ns: dict
    exposed_ns: dict
    gaps: list            # (device, start, end)
    host: list            # every bench.* host span
    flushes: list         # host Events of dispatching pumps in the window

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]


def is_collective(name: str) -> bool:
    low = name.lower()
    return any(m in low for m in COLLECTIVE_MARKS)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device event: a TPU trace names each op
    by its instruction's text ("%fusion.3 = f32[...] fusion(...)")."""
    head, sep, _ = event_name.partition(" = ")
    return head.lstrip("%") if sep else event_name


def load(path: str):
    """(device events by device name, host bench.* spans) of a trace file
    (``.xplane.pb``, or the same gzipped)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    evs += [Event(op_name(e.name), int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                            for e in line.events]
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [Event(e.name, int(e.start_ns),
                               int(e.start_ns + e.duration_ns))
                         for e in line.events
                         if e.name.startswith(HOST_PREFIX)]
    return devices, host


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Merged intervals a minus merged intervals b."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def label(host, t) -> str:
    """The innermost bench.* host span open at time ``t``."""
    best = None
    for ev in host:
        if ev.name != WINDOW_SPAN and ev.start <= t < ev.end:
            if best is None or ev.end - ev.start < best.end - best.start:
                best = ev
    return best.name if best is not None else "(no span)"


def reduce(devices: dict, host: list) -> Reduced:
    """Reduce device op events and host spans over the traced window."""
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    lo, hi = spans[0].start, spans[0].end
    busy, exposed, gaps = {}, {}, []
    op_ns = defaultdict(int)
    for dev, evs in sorted(devices.items()):
        inside = [ev for ev in evs if ev.end > lo and ev.start < hi]
        for ev in inside:
            op_ns[ev.name] += min(ev.end, hi) - max(ev.start, lo)
        merged = union(clip([(ev.start, ev.end) for ev in inside], lo, hi))
        busy[dev] = length(merged)
        coll = union(clip([(ev.start, ev.end) for ev in inside
                           if is_collective(ev.name)], lo, hi))
        comp = union(clip([(ev.start, ev.end) for ev in inside
                           if not is_collective(ev.name)], lo, hi))
        exposed[dev] = length(subtract(coll, comp))
        gaps += [(dev, s, e) for s, e in subtract([(lo, hi)], merged)]
    flushes = [e for e in host if e.name == FLUSH_SPAN
               and e.start >= lo and e.end <= hi]
    return Reduced(window=(lo, hi), busy_ns=busy, op_ns=dict(op_ns),
                   exposed_ns=exposed, gaps=gaps, host=host,
                   flushes=flushes)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device ops that took most time and the longest idle gaps by
    what the host was doing, in seconds (at most ``top`` entries each)."""
    ops = sorted(red.op_ns.items(), key=lambda kv: -kv[1])[:top]
    longest = sorted(red.gaps, key=lambda g: g[1] - g[2])[:top]
    return {"device_ops": [[n, ns * 1e-9] for n, ns in ops],
            "idle_gaps": [[label(red.host, (s + e) // 2), (e - s) * 1e-9]
                          for _, s, e in longest]}
