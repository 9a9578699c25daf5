"""Open-loop request traffic for the chip benchmark, made from the seed.

The generators are copies of ``src/repro/data/synthetic.py``
(``table_heat``, ``make_batch``, ``open_loop_arrivals``) kept here so that
no change to the program can move the traffic.  A traffic mix is a JSON
file under ``traffic/`` that names a ``mode`` and its parameters; a cell
adds the offered rate and the size of the request pool.

Two things differ from the copied code, both to make every seed carry the
same amount of work:
  * the arrival schedule holds exactly ``round(rate * seconds)`` arrivals,
    its exponential gaps rescaled so that the last one falls at the end of
    the window; the seed changes their order and spacing, not their count;
  * requests are drawn from a seeded pool of distinct requests and recycled
    under the schedule (in a seeded order), since one distinct request per
    arrival would need gigabytes of host memory at the four-chip rate.
"""
from __future__ import annotations

import dataclasses

import numpy as np

MODES = ("uniform", "hetero", "powerlaw", "powerlaw_hetero", "drift", "fixed")


@dataclasses.dataclass(frozen=True)
class Pool:
    """``n`` distinct requests: dense (n, n_dense) f32, idx (n, t_pad, hot)
    int32 and mask (n, t_pad, hot) f32 (1 marks a valid index)."""
    dense: np.ndarray
    idx: np.ndarray
    mask: np.ndarray

    @property
    def n(self) -> int:
        return int(self.dense.shape[0])

    def valid(self) -> np.ndarray:
        """Valid indices per request, (n,) int64."""
        return self.mask.reshape(self.n, -1).sum(axis=1).astype(np.int64)


def table_heat(n_tables: int, phase: int, *, seed: int = 0) -> np.ndarray:
    """Per-table relative heat of one drift phase: a Zipf profile (1/rank)
    over a phase-seeded permutation of the tables, normalized to max 1."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xD21F, phase]))
    order = rng.permutation(n_tables)
    heat = np.empty(n_tables)
    heat[order] = 1.0 / (1.0 + np.arange(n_tables))
    return heat


def make_pool(table_sizes, n_dense: int, max_hot: int, n: int, *,
              mode: str, t_pad: int, zipf_alpha: float = 1.05,
              phase: int = 0, hot=None, seed: int = 0) -> Pool:
    """``n`` requests of the mix ``mode`` (``make_batch`` of the program's
    data module, one batch of ``n`` rows at step 0).  ``fixed``, which the
    program's module lacks, gives every bag of table t exactly ``hot[t]``
    ids, uniform over the table (MLPerf DLRMv2's ``multi_hot_sizes`` with
    the ``uniform`` distribution); the pool is ``max(hot)`` wide."""
    if mode not in MODES:
        raise ValueError(f"unknown traffic mode {mode!r}; have {MODES}")
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0]))
    if mode == "fixed":
        return _fixed_pool(rng, table_sizes, n_dense, n, t_pad, hot)
    t = len(table_sizes)
    ragged = mode in ("hetero", "powerlaw_hetero", "drift")
    hot = max_hot if ragged else 1
    dense = rng.standard_normal((n, n_dense), dtype=np.float32)
    idx = np.zeros((n, t_pad, hot), np.int32)
    mask = np.zeros((n, t_pad, hot), np.float32)
    sizes = np.asarray(table_sizes)
    heat = table_heat(t, phase, seed=seed) if mode == "drift" else None
    for ti in range(t):
        rows = sizes[ti]
        if mode.startswith("powerlaw") or mode == "drift":
            raw = rng.zipf(zipf_alpha, size=(n, hot))
            idx[:, ti] = np.minimum(raw - 1, rows - 1).astype(np.int32)
        else:
            idx[:, ti] = rng.integers(0, rows, size=(n, hot), dtype=np.int32)
        if mode == "drift":
            counts = 1 + rng.binomial(max_hot - 1, heat[ti], size=n)
        elif ragged:
            counts = rng.integers(1, max_hot + 1, size=n)
        else:
            counts = np.ones(n, np.int64)
        mask[:, ti] = (np.arange(hot)[None, :]
                       < counts[:, None]).astype(np.float32)
    rng.random(n)          # the labels draw of make_batch: keeps the stream
    return Pool(dense=dense, idx=idx, mask=mask)


def _fixed_pool(rng, table_sizes, n_dense: int, n: int, t_pad: int,
                hot) -> Pool:
    if hot is None or len(hot) != len(table_sizes):
        raise ValueError(f"mode 'fixed' needs one bag size a table: "
                         f"{len(table_sizes)} tables, hot {hot!r}")
    dense = rng.standard_normal((n, n_dense), dtype=np.float32)
    idx = np.zeros((n, t_pad, max(hot)), np.int32)
    mask = np.zeros((n, t_pad, max(hot)), np.float32)
    for ti, (rows, h) in enumerate(zip(table_sizes, hot)):
        idx[:, ti, :h] = rng.integers(0, rows, size=(n, h), dtype=np.int32)
        mask[:, ti, :h] = 1.0
    rng.random(n)          # the labels draw, as in the other modes
    return Pool(dense=dense, idx=idx, mask=mask)


def open_loop_gaps(n: int, *, rate_rps: float, burstiness: float = 0.0,
                   burst_factor: float = 8.0, mean_burst_len: int = 16,
                   seed: int = 0) -> np.ndarray:
    """Inter-arrival gaps of ``open_loop_arrivals``: Poisson at
    ``rate_rps``; ``burstiness`` in [0, 1) opens Markov-modulated bursts of
    geometric mean length ``mean_burst_len`` whose gaps shrink by
    ``burst_factor``."""
    if not 0.0 <= burstiness < 1.0:
        raise ValueError(f"burstiness must be in [0, 1), got {burstiness}")
    rng = np.random.default_rng(np.random.SeedSequence([seed, n]))
    gaps = rng.exponential(1.0 / rate_rps, size=n)
    opens = rng.random(n) < burstiness
    burst_left = 0
    for i in range(n):
        if burst_left <= 0 and opens[i]:
            burst_left = 1 + rng.geometric(1.0 / max(mean_burst_len, 1))
        if burst_left > 0:
            gaps[i] /= burst_factor
            burst_left -= 1
    return gaps


def schedule(rate_rps: float, seconds: float, *, seed: int,
             burstiness: float = 0.0, burst_factor: float = 8.0,
             mean_burst_len: int = 16) -> np.ndarray:
    """Due times (seconds from the window's start, ascending) of exactly
    ``round(rate_rps * seconds)`` arrivals, the last at ``seconds``."""
    n = max(1, int(round(rate_rps * seconds)))
    gaps = open_loop_gaps(n, rate_rps=rate_rps, burstiness=burstiness,
                          burst_factor=burst_factor,
                          mean_burst_len=mean_burst_len, seed=seed)
    t = np.cumsum(gaps)
    return t * (seconds / t[-1])


def pool_order(n_arrivals: int, pool_size: int, *, seed: int) -> np.ndarray:
    """Which pool entry each arrival sends: the pool in a seeded order,
    reshuffled on every pass, so consecutive arrivals are distinct."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x9001]))
    passes = -(-n_arrivals // pool_size)
    return np.concatenate([rng.permutation(pool_size)
                           for _ in range(passes)])[:n_arrivals]
