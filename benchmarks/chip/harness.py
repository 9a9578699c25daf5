"""The measured path: an open-loop client in front of the serving stack.

One thread does what a client and a server loop would: it submits every
request whose due time has come to ``ServingFrontend.try_submit``,
backdated to that due time, so a stall of the loop is charged to the
requests that waited behind it; whenever requests are queued it calls
``pump``, which dispatches them to ``DLRMEngine`` and returns their CTRs
once they are on the host.  A request's latency runs from its due time to
the moment its CTR is back in this loop.

Host spans (``jax.profiler.TraceAnnotation``) mark what the loop does:
``bench.submit`` (a burst of submissions), ``bench.pump`` (a dispatching
pump: stack, step, CTRs to the host), ``bench.harvest`` (recording the
completions) and ``bench.idle`` (waiting for the next due time).  In a
traced run ``bench.traced`` spans the traced window: whole flushes only.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Window:
    """What one measured window produced; times in seconds from its start."""
    seconds: float
    due: np.ndarray          # (n,) due time of each arrival
    dispatch: np.ndarray     # (n,) frontend's dispatch stamp (nan: never)
    done: np.ndarray         # (n,) CTR back in the client loop (nan: never)
    ctr: np.ndarray          # (n,) served CTR (nan: never)
    flush_of: np.ndarray     # (n,) index of the flush that served it (-1)
    flushes: np.ndarray      # (k, 2) start and end of each dispatching pump
    late: np.ndarray         # (n,) submission time minus due time
    traced: Optional[range] = None   # flushes inside the traced window

    @property
    def n(self) -> int:
        return int(self.due.shape[0])


def _annotation(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def trace_options():
    """Device ops and the benchmark's own host spans; no Python function
    tracing and no runtime-internal host events, which would swell the
    trace and slow the loop being traced."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def warm_up(frontend, pool, n: int, rounds: int = 2):
    """Serve ``rounds`` batches of ``n`` pool requests (compiles the step
    and fills every lazy cache of the path); returns their CTRs."""
    out = []
    for r in range(rounds):
        for i in range(n):
            k = (r * n + i) % pool.n
            res = frontend.try_submit(pool.dense[k], pool.idx[k],
                                      pool.mask[k])
            if not res.admitted:
                raise RuntimeError(f"warm-up request refused: {res.reason}")
        while frontend.stats.queued or frontend.stats.inflight:
            out += frontend.pump()
    out += frontend.drain()
    return out


def serve(frontend, pool, due_rel: np.ndarray, order: np.ndarray,
          seconds: float, *, trace_dir: Optional[str] = None,
          trace_at: float = 0.0, trace_s: float = 0.0,
          clock=time.perf_counter) -> Window:
    """Offer the arrivals ``due_rel`` (pool entries ``order``) to
    ``frontend`` open loop, serve until nothing is queued or in flight, and
    return the window's record (a request that never came back keeps nan).
    With ``trace_dir`` the profiler records whole flushes from ``trace_at``
    seconds into the window for about ``trace_s``."""
    import jax

    n = int(due_rel.shape[0])
    dispatch = np.full(n, np.nan)
    done = np.full(n, np.nan)
    ctr = np.full(n, np.nan, np.float32)
    flush_of = np.full(n, -1, np.int64)
    late = np.zeros(n)
    flushes = []
    slot = {}                       # frontend request id -> arrival
    tracing = None                  # the open bench.traced annotation
    traced_lo = traced_hi = None
    t0 = clock() + 0.005
    due = t0 + due_rel
    i = 0
    while True:
        now = clock()
        if i < n and due[i] <= now:
            j = int(np.searchsorted(due, now, side="right"))
            with _annotation("bench.submit"):
                for k in range(i, j):
                    p = order[k]
                    res = frontend.try_submit(pool.dense[p], pool.idx[p],
                                              pool.mask[p], now=due[k])
                    if not res.admitted:
                        raise RuntimeError(
                            f"request refused: {res.reason}")
                    slot[res.request_id] = k
            late[i:j] = now - due[i:j]
            i = j
        if frontend.stats.queued or frontend.stats.inflight:
            if trace_dir is not None and tracing is None and \
                    now - t0 >= trace_at:
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=trace_options())
                tracing = _annotation("bench.traced")
                tracing.__enter__()
                traced_lo = len(flushes)
            with _annotation("bench.pump"):
                a = clock()
                out = frontend.pump()
                b = clock()
            with _annotation("bench.harvest"):
                f = len(flushes)
                flushes.append((a - t0, b - t0))
                for r in out:
                    k = slot.pop(r.request_id)
                    done[k] = b - t0
                    dispatch[k] = r.t_dispatch - t0
                    ctr[k] = r.ctr
                    flush_of[k] = f
            if tracing is not None and traced_hi is None and \
                    b - t0 >= trace_at + trace_s:
                tracing.__exit__(None, None, None)
                traced_hi = len(flushes)
            continue
        if i >= n:
            break
        with _annotation("bench.idle"):
            wait = due[i] - clock()
            if wait > 0:
                time.sleep(wait)
    if tracing is not None:
        if traced_hi is None:
            tracing.__exit__(None, None, None)
            traced_hi = len(flushes)
        jax.profiler.stop_trace()
    return Window(seconds=seconds, due=due_rel, dispatch=dispatch, done=done,
                  ctr=ctr, flush_of=flush_of,
                  flushes=np.asarray(flushes, np.float64).reshape(-1, 2),
                  late=late,
                  traced=range(traced_lo, traced_hi)
                  if traced_lo is not None else None)
