"""Batched inference engine.

DLRM path (the paper's scenario): requests (dense, sparse) accumulate into
fixed-size batches; the jitted BLS step runs the bounded-lag pipeline over
microbatches; per-batch latency feeds the straggler monitor whose
recommendation can retune the bound between batches.

LM path: synchronous batched greedy decode against a prefill'd KV cache.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DLRMConfig, ModelConfig
from repro.core import alltoallv as a2a_mod
from repro.core import bls as bls_mod
from repro.models import api, dlrm as dlrm_mod
from repro.runtime import placement as plc_mod
from repro.runtime.elastic import NodeFailure
from repro.runtime.reshard import MIG_KEYS, ReshardExecutor
from repro.runtime.straggler import (CapAutotuner, StragglerMonitor,
                                     detect_stragglers)
from repro.serving import trace
from repro.train import steps as steps_mod

# host <-> step argument order of the delta wire leaves (sorted, matching
# the dict order FreshnessManager.next_wire emits)
DELTA_KEYS = ("dcnt", "dcs", "dgid", "dvec", "dver")

# host <-> step argument order of the integrity-repair wire leaves
# (sorted, matching the dict order Scrubber.next_wire emits)
REP_KEYS = ("rcnt", "rcs", "rgid", "rvec")


@dataclasses.dataclass
class ServeStats:
    batches: int = 0
    requests: int = 0
    total_s: float = 0.0
    retunes: int = 0          # cap-autotuner re-jits
    # -- chaos ledger (deadline policy / degraded serving / eviction) ------
    deadline_breaches: int = 0  # flushes that exceeded deadline_s
    degraded_batches: int = 0   # batches served with degraded_members set
    approx_rows: int = 0        # live bags served from the fallback, total
    evictions: int = 0          # evict() recoveries (crash or policy)
    replays: int = 0            # batches re-dispatched after a NodeFailure
    recovery_s: float = 0.0     # wall time inside evict(): remesh ->
                                # repartition -> re-jit
    # -- freshness ledger (versioned delta updates, DESIGN.md §10) ---------
    rows_applied: int = 0       # delta rows committed into the tables
    rows_stale_served: int = 0  # bags served that touched a pending row
    versions_behind: int = 0    # ledger spread after the last flush
    delta_rejects: int = 0      # checksum-rejected (re-shipped) delta rows
    apply_rollbacks: int = 0    # applies abandoned by a mid-apply crash
    # -- placement ledger (skew-aware resharding, DESIGN.md §11) -----------
    reshards: int = 0           # committed placement cutovers
    reshard_aborts: int = 0     # in-flight reshards torn down by evict()
    migrated_rows: int = 0      # embedding rows moved by committed cutovers
    imbalance_ratio: float = 1.0   # max/mean per-member pooled-row load
    flush_time_ratio: float = 1.0  # max/mean per-member flush-time estimate
    # -- scrub ledger (silent-corruption self-healing, DESIGN.md §12) ------
    blocks_scrubbed: int = 0    # table blocks audited on device
    detections: int = 0         # rows (or cache slots) caught corrupt
    repaired_rows: int = 0      # quarantined rows restored from the mirror
    quarantined_served: int = 0  # bags that touched a quarantined row
    wire_rejects: int = 0       # (dst, microbatch, src) segments rejected
    detection_lag_flushes: int = 0  # worst inject -> detect lag observed
    # -- pooling ledger ----------------------------------------------------
    pooled_indices: int = 0     # valid indices pooled, padded rows included
    padded_indices: int = 0     # of those, the ones in padded rows
    # per-member exchange telemetry (EWMA pooled rows) — a list so the JSON
    # view keeps the member axis
    member_rows: list = dataclasses.field(default_factory=list)

    @property
    def throughput_rps(self) -> float:
        return self.requests / self.total_s if self.total_s else 0.0

    def to_dict(self) -> dict:
        """Plain-JSON view of the ledger (every dataclass field plus the
        derived throughput) — the stable surface benchmarks and CI gates
        consume instead of reaching into fields one by one.  Subclasses
        (``serving.frontend.FrontendStats``) extend it with their own
        counters and histograms; values stay JSON-serializable all the
        way down."""
        d = {f.name: getattr(self, f.name)
             for f in dataclasses.fields(ServeStats)}
        d["throughput_rps"] = self.throughput_rps
        return d


class DLRMEngine:
    """Fixed-batch CTR serving with the BLS-enabled step.

    ``wire_dtype`` (default: cfg.wire_dtype) selects the exchange codec;
    ``cache`` (a serving/hot_cache.HotCache over the full table stack) or a
    calibrated one via :meth:`calibrate_cache` turns the skewed head of the
    access stream into local pooling (DESIGN.md: the fused sparse hot path).

    ``exchange`` / ``ragged_cap`` (defaults: cfg) select the collective
    (DESIGN.md §6).  Under ``exchange='auto'`` the engine runs the cap
    autotuner: every flush feeds the step's live-count/drop diagnostics to
    a ``CapAutotuner``; every ``retune_every`` batches it adopts the
    recommended cap (re-jitting the step), switching between the ragged
    alltoallv and the dense butterfly as profitability flips.

    ``exchange_pipeline`` (default: cfg) picks how the fused wire buffer
    moves (DESIGN.md §7): 'mono' is one all_to_all per exchange, 'ring'
    the chunked ppermute butterfly with per-peer decode/compute overlap,
    and 'auto' resolves to ring when the model axis has P >= 4 members
    (enough rounds to overlap) and mono below.

    ``plan_pipeline=True`` overlaps the embedding-bag stream-plan build
    with compute (DESIGN.md §1): each flush asynchronously dispatches the
    incoming batch's index-bucketing plan (``build_forward_plans``) and
    the step that consumes it, then returns the PREVIOUS in-flight batch's
    CTRs — so flush n+1's plan is built while flush n still pools on the
    device, and the sort never sits between exchange and pool.  Results
    arrive one flush late; a final ``flush()`` (with an empty queue) or
    :meth:`drain` harvests the last in-flight batch.  When the
    configuration has no plan to build (ref backend, resident tables,
    ragged exchange), the pipeline degenerates to deferred-harvest
    dispatch with inline planning — outputs are identical either way.

    **Chaos hardening** (DESIGN.md §8): ``deadline_s`` arms a per-flush
    deadline with policy ``on_deadline``: 'block' only counts breaches
    (correctness over latency), 'degrade' serves around confirmed
    sustained stragglers via ``degraded_members`` masking with
    ``degraded_fallback`` (quality loss ledgered in
    ``ServeStats.approx_rows``), 'evict' removes them from the mesh.
    Transient breaches (nothing confirmed by ``detect_stragglers`` for
    ``confirm_after`` consecutive breaching flushes) instead widen the
    absorption window by raising the BLS bound toward
    :meth:`recommend_bound`.  ``faults`` (a ``runtime.faults.
    FaultInjector``) drives deterministic chaos: injected per-member
    delays gate each flush and crash steps raise ``NodeFailure``, which
    the engine recovers from in place — rebuild the mesh from survivors,
    repartition the table stack (and cache), re-jit, and replay the
    in-flight batch with bounded backoff — zero requests lost.

    **Skew-aware placement** (DESIGN.md §11): ``rebalance=True`` arms the
    background rebalance policy.  Every flush's live-bag counts feed a
    per-table ``runtime.placement.TableLoadModel``; per-member imbalance
    sustained over ``rebalance_threshold`` for ``rebalance_patience``
    flushes (paused while the serving ladder is off FULL) plans a minimal
    LPT migration and executes it ONLINE (``runtime.reshard``): moved rows
    ride the fused wire in ``mig_slice_cap``-bounded installments while
    serving continues bit-exact on the pre-move layout, then one atomic
    swap cuts over.  Eviction aborts any in-flight reshard (rollback is
    the absence of the swap) and makes a rebalance on the shrunken pod
    mandatory.
    """

    def __init__(self, params, cfg: DLRMConfig, *, batch_size: int = 512,
                 bound: int = 0, microbatches: int = 1,
                 unroll: Optional[int] = None,
                 wire_dtype: Optional[str] = None, cache=None,
                 exchange: Optional[str] = None,
                 ragged_cap: Optional[int] = None,
                 exchange_pipeline: Optional[str] = None,
                 retune_every: int = 8,
                 row_block: Optional[int] = None,
                 pool_mode: Optional[str] = None,
                 plan_pipeline: bool = False,
                 deadline_s: Optional[float] = None,
                 on_deadline: str = "block",
                 faults=None,
                 freshness=None,
                 degraded_fallback: str = "zero",
                 confirm_after: int = 2,
                 max_retries: int = 2,
                 retry_backoff_s: float = 0.0,
                 rebalance: bool = False,
                 rebalance_threshold: float = 1.25,
                 rebalance_patience: int = 8,
                 mig_slice_cap: int = 8,
                 scrub_budget: int = 0,
                 scrub_block_rows: int = 32,
                 rep_slice_cap: int = 8,
                 quarantine_cap: int = 64,
                 scrub_mirror: bool = True):
        self.params, self.cfg = params, cfg
        self.batch_size = batch_size
        self.bound, self.microbatches = bound, microbatches
        # BLS scan unroll.  None keeps the pipeline's throughput default
        # (min(bound+1, 4)); unroll=1 makes every microbatch compile to
        # the SAME loop body, so a request's served CTR is bit-identical
        # regardless of its position in the batch — serving paths that
        # promise replay-exact answers (the frontend's parity gate) want 1
        self.unroll = unroll
        self.wire_dtype = wire_dtype or cfg.wire_dtype
        self.cache = cache
        self.exchange = exchange or cfg.exchange
        self.ragged_cap = ragged_cap if ragged_cap is not None \
            else cfg.ragged_cap
        self.exchange_pipeline = exchange_pipeline or cfg.exchange_pipeline
        self.retune_every = retune_every
        # embedding-bag kernel regime (DESIGN.md §1): 0 auto — resident
        # table blocks when they fit VMEM, DMA row streaming otherwise
        self.row_block = row_block if row_block is not None \
            else cfg.row_block
        # pooling loop: unrolled vector walk vs scalar walk (DESIGN.md §1)
        self.pool_mode = pool_mode if pool_mode is not None \
            else cfg.pool_mode
        self.plan_pipeline = plan_pipeline
        if on_deadline not in ("block", "degrade", "evict"):
            raise ValueError(f"unknown on_deadline {on_deadline!r}")
        if faults is not None and plan_pipeline:
            raise ValueError(
                "fault injection drives recovery through the synchronous "
                "flush path; plan_pipeline's deferred harvest would tear "
                "the replay boundary — run chaos without plan_pipeline")
        if freshness is not None and plan_pipeline:
            raise ValueError(
                "freshness applies deltas atomically BETWEEN synchronous "
                "flushes; plan_pipeline's deferred harvest would tear the "
                "apply/replay boundary — serve updates without "
                "plan_pipeline")
        if rebalance and plan_pipeline:
            raise ValueError(
                "online resharding migrates rows through the synchronous "
                "flush path; plan_pipeline's deferred harvest would tear "
                "the cutover boundary — rebalance without plan_pipeline")
        if scrub_budget and plan_pipeline:
            raise ValueError(
                "integrity scrubbing audits and repairs through the "
                "synchronous flush path; plan_pipeline's deferred harvest "
                "would tear the quarantine/repair boundary — scrub "
                "without plan_pipeline")
        self.deadline_s = deadline_s
        self.on_deadline = on_deadline
        self.faults = faults
        self.freshness = freshness
        self.degraded_fallback = degraded_fallback
        self.confirm_after = max(1, int(confirm_after))
        self.max_retries = max(0, int(max_retries))
        self.retry_backoff_s = float(retry_backoff_s)
        self.degraded_members: tuple = ()
        self._mesh = None              # owned post-eviction mesh (else ambient)
        self._flushes = 0              # fault-plan step counter
        self._streak: dict = {}        # straggler confirmation streaks
        self.monitor = StragglerMonitor()
        self.cap_tuner = CapAutotuner()
        self.stats = ServeStats()
        self._pending: list = []
        # (out_future, diag, n, t0, watcher, done, step_no) under
        # plan_pipeline; always None otherwise
        self._inflight = None
        self._last_finish_t = 0.0      # end of the last harvested batch
        # lookahead-prefetched plan (digest, plan) staged by stage_plan();
        # the next pipelined flush adopts it when its batch matches
        self._staged_plan = None
        self.plan_stage_hits = 0       # flushes served a prefetched plan
        # -- skew-aware placement + online resharding (DESIGN.md §11) ------
        self.rebalance = bool(rebalance)
        self.rebalance_threshold = float(rebalance_threshold)
        self.rebalance_patience = max(1, int(rebalance_patience))
        self.mig_slice_cap = max(1, int(mig_slice_cap))
        self._pmap = None              # None == identity boot placement
        self.reshard = None            # in-flight ReshardExecutor
        self._reshard_epoch = 0        # fences dead reshards' wire slices
        self.load_model = None         # lazy TableLoadModel (sized per mesh)
        self._member_ewma = None       # EWMA per-member pooled live rows
        self._imb_streak = 0           # consecutive over-threshold flushes
        self._rebalance_pending = False  # mandatory rebalance after evict()
        # bumped on every layout change (cutover AND eviction): the
        # frontend's flush-EWMA keys off it to recalibrate
        self.layout_version = 0
        # -- integrity scrubbing (DESIGN.md §12) ---------------------------
        self.scrub = None
        self._held_wbad = None         # previous flush's corrupt-src flags
        self._wire_streak: dict = {}   # per-src consecutive-corrupt flushes
        self._flip_log: dict = {}      # injected-flip gid -> flush, for lag
        if scrub_budget:
            from repro.runtime.scrub import Scrubber
            self.scrub = Scrubber(self, budget=int(scrub_budget),
                                  block_rows=int(scrub_block_rows),
                                  slice_cap=int(rep_slice_cap),
                                  quarantine_cap=int(quarantine_cap),
                                  mirror=bool(scrub_mirror))
        self._rebuild_step()

    def calibrate_cache(self, idx: np.ndarray, mask: np.ndarray,
                        cache_rows: Optional[int] = None):
        """Build the hot-row cache from an observed (idx, mask) sample and
        re-jit the step around it.  cache_rows defaults to cfg.cache_rows."""
        from repro.serving import hot_cache as HC
        rows = cache_rows if cache_rows is not None else self.cfg.cache_rows
        self.cache = HC.build_from_batch(self.params["tables"], idx, mask,
                                         rows)
        self._rebuild_step()
        return self.cache

    def adopt_cache(self, cache):
        """Swap in an externally built hot-row cache (the frontend's
        lookahead warmer rebuilds one from observed access counts) and
        re-jit the step around it.  Pass None to drop the cache."""
        self.cache = cache
        self._staged_plan = None       # plan applicability may change
        self._rebuild_step()

    # -- placement-conditioned step construction ---------------------------

    @property
    def pmap(self) -> "plc_mod.PartitionMap":
        """The live table placement.  ``None`` internally means the
        identity boot layout (materialized lazily — t_pad depends on the
        active mesh, which __init__ may not have yet)."""
        if self._pmap is None:
            _, t_pad, _, _ = self._exchange_geometry()
            return plc_mod.PartitionMap.identity(t_pad)
        return self._pmap

    def _step_flags(self):
        """(with_mig, with_inv, with_scrub): whether the step signature
        carries the migration wire leaves, the placement inverse
        permutation, and/or the scrub group (repair wire leaves +
        quarantine gids + wire-flip hook + wire checksums).  The inv
        rides whenever a migration is live (so the cutover is an ARRAY
        swap, not a signature change) or the map is non-identity.  The
        scrub flag is constant over the engine's life (scrub_budget is
        an __init__ knob), so it never forces a mid-serve retrace."""
        with_mig = self.reshard is not None and self.reshard.active
        with_inv = with_mig or (self._pmap is not None
                                and not self._pmap.is_identity)
        with_scrub = self.scrub is not None
        return with_mig, with_inv, with_scrub

    def _rebuild_step(self):
        with_mig, with_inv, with_scrub = self._step_flags()
        self._step_key = (with_mig, with_inv, with_scrub)
        self._step = jax.jit(self._make_step(
            self.bound, self.microbatches,
            with_mig=with_mig, with_inv=with_inv, with_scrub=with_scrub))

    def _ensure_step(self):
        """Re-jit only when the step's SIGNATURE flags drifted from the
        compiled one (migration started/ended) — every other layout
        change flows through the table_inv argument without a retrace."""
        if self._step_flags() != self._step_key:
            self._rebuild_step()

    def _make_step(self, bound, microbatches, *, with_mig=False,
                   with_inv=False, with_scrub=False):
        cfg, wire = self.cfg, self.wire_dtype
        ex, cap = self.exchange, self.ragged_cap
        pipe = self.exchange_pipeline
        rblk, pool = self.row_block, self.pool_mode
        deg, fb = self.degraded_members, self.degraded_fallback
        # the step closes over settings only, never over the engine: the
        # trace registry (trace.note_step) may keep it past the engine
        unroll = self.unroll
        # diagnostics cost a full-batch miss re-probe + collectives:
        # trace them only when something consumes them — drop monitoring
        # (explicit ragged), the autotuner (auto WITH a cache; cacheless
        # auto can never resolve to ragged, and skipping the observations
        # also keeps pre-calibration full-live counts out of the window),
        # or the degraded-serving approx_rows ledger
        diag_on = ex == "ragged" or (ex == "auto" and
                                     self.cache is not None) or bool(deg)
        # the plan builder the pipelined flush dispatches ahead of the
        # step; rebuilt with the step so retuned caps / recalibrated
        # caches re-resolve whether a plan applies at all
        if self.plan_pipeline:
            eng_cache = self.cache

            def plan_fn(params, idx):
                return dlrm_mod.build_forward_plans(
                    params, cfg, idx, microbatches=microbatches,
                    cache=eng_cache, exchange=ex, ragged_cap=cap,
                    row_block=rblk)

            self._plan_fn = jax.jit(plan_fn)

        def _finish(out):
            if not diag_on:
                logits = out
                return (jax.nn.sigmoid(logits),)
            logits, diag = out
            return (jax.nn.sigmoid(logits), diag.live_max, diag.drops,
                    diag.approx_rows)

        def forward(params, dense, idx, mask, cache, plan, *xargs):
            # xargs tail, in order: delta wire leaves (DELTA_KEYS,
            # freshness serving), migration wire leaves (MIG_KEYS, live
            # resharding), repair wire leaves (REP_KEYS, scrub repair),
            # quarantine gids + wire-flip hook (scrub), then the
            # placement inverse permutation.  Presence of each group is a
            # trace-time constant baked into this step variant, so the
            # split below is static
            rest = list(xargs)
            table_inv = rest.pop() if with_inv else None
            repair = quarantine = wire_flip = None
            if with_scrub:
                wire_flip = rest.pop()
                quarantine = rest.pop()
                repair = dict(zip(REP_KEYS, rest[-len(REP_KEYS):]))
                del rest[-len(REP_KEYS):]
            migration = None
            if with_mig:
                migration = dict(zip(MIG_KEYS, rest[-len(MIG_KEYS):]))
                del rest[-len(MIG_KEYS):]
            deltas = dict(zip(DELTA_KEYS, rest)) if rest else None
            res = dlrm_mod.forward_distributed(
                params, cfg, dense, idx, mask, bound=bound,
                microbatches=microbatches, unroll=unroll,
                cache=cache, wire_dtype=wire,
                exchange=ex, ragged_cap=cap, exchange_pipeline=pipe,
                row_block=rblk, pool_mode=pool, plan=plan, deltas=deltas,
                migration=migration, repair=repair, quarantine=quarantine,
                wire_flip=wire_flip, wire_check=with_scrub,
                table_inv=table_inv,
                degraded_members=deg, degraded_fallback=fb,
                return_diag=diag_on)
            n_staged = (int(deltas is not None) + int(migration is not None)
                        + int(repair is not None) + int(with_scrub))
            if n_staged:
                core, staged = res[:-n_staged], res[-n_staged:]
                return _finish(core[0] if len(core) == 1
                               else tuple(core)) + tuple(staged)
            return _finish(res)

        if self.cache is None:
            if self.plan_pipeline:
                def step(params, dense, idx, mask, plan):
                    return forward(params, dense, idx, mask, None, plan)
            else:
                def step(params, dense, idx, mask, *xargs):
                    return forward(params, dense, idx, mask, None, None,
                                   *xargs)
            return step

        from repro.serving.hot_cache import HotCache

        # cache arrays ride as jit ARGUMENTS (like params), not closure
        # constants — a closure would duplicate the (T,R) slot map into
        # the executable's constant pool and re-embed it on every
        # calibration re-trace; hot_ids only names the cached rows and is
        # not needed by the forward path
        if self.plan_pipeline:
            def step(params, dense, idx, mask, hot_rows, slot_of, plan):
                c = HotCache(hot_ids=None, hot_rows=hot_rows,
                             slot_of=slot_of)
                return forward(params, dense, idx, mask, c, plan)
        else:
            def step(params, dense, idx, mask, hot_rows, slot_of, *xargs):
                c = HotCache(hot_ids=None, hot_rows=hot_rows,
                             slot_of=slot_of)
                return forward(params, dense, idx, mask, c, None, *xargs)

        return step

    def _step_args(self, d, i, m):
        base = (self.params, jnp.asarray(d), jnp.asarray(i),
                jnp.asarray(m))
        if self.cache is None:
            return base
        return base + (self.cache.hot_rows, self.cache.slot_of)

    # -- lookahead plan prefetch (the frontend's PR 4 hook) ----------------

    @staticmethod
    def _plan_digest(i: np.ndarray):
        i = np.ascontiguousarray(i)
        return (i.shape, hash(i.tobytes()))

    def stage_plan(self, idx_rows) -> bool:
        """Prefetch the embedding-bag stream plan for a PROSPECTIVE batch
        before it is flushed: ``idx_rows`` are the per-request index rows
        (n <= batch_size; padded exactly as :meth:`flush` pads) of the
        batch a continuous-batching frontend expects to dispatch next.
        The plan build is DISPATCHED (async) here, so it overlaps whatever
        the device is doing; the next pipelined flush whose batch matches
        adopts it instead of re-planning (``plan_stage_hits``), and a
        mismatch (the queue changed under the frontend) silently falls
        back to inline planning.  Returns True when a plan was staged."""
        if not self.plan_pipeline:
            return False
        rows = list(idx_rows)
        if not rows or len(rows) > self.batch_size:
            return False
        i = np.stack(rows + [rows[-1]] * (self.batch_size - len(rows)))
        _, i, _ = self._fit_batch(None, i,
                                  np.zeros(i.shape, np.float32))
        with self._mesh_ctx():
            plan = self._plan_fn(self.params, jnp.asarray(i))
        self._staged_plan = (self._plan_digest(i), plan)
        return True

    def submit(self, dense: np.ndarray, idx: np.ndarray, mask: np.ndarray):
        """Queue one request (row).  Returns CTRs when a batch fills (the
        PREVIOUS batch's CTRs under ``plan_pipeline``)."""
        self._pending.append((dense, idx, mask))
        if len(self._pending) >= self.batch_size:
            return self.flush()
        return None

    def _finish_batch(self, out, diag, n, t0, done_t=None, step_no=None):
        """Materialize one batch's result and account for it.  ``done_t``
        (pipelined batches: the watcher thread's device-completion
        timestamp) keeps the straggler monitor observing dispatch-to-
        completion step latency rather than harvest-to-harvest wall time;
        ``total_s`` clips each interval at the previous batch's end so it
        sums non-overlapping busy time (throughput_rps stays honest even
        though pipelined steps overlap request accumulation)."""
        with trace.span("engine.wait", flush=step_no):
            out = np.asarray(out)
        with trace.span("engine.account", flush=step_no):
            end = done_t if done_t is not None else time.perf_counter()
            self.monitor.observe(end - t0)
            if diag:
                self.cap_tuner.observe(int(diag[0]), int(diag[1]))
                if len(diag) > 2:
                    self.stats.approx_rows += int(diag[2])
            if self.degraded_members:
                self.stats.degraded_batches += 1
            self.stats.batches += 1
            self.stats.requests += n
            self.stats.total_s += end - max(t0, self._last_finish_t)
            self._last_finish_t = max(self._last_finish_t, end)
            if self.exchange == "auto" and \
                    self.stats.batches % self.retune_every == 0:
                self.retune_cap()
            if step_no is not None:
                self._after_flush(step_no, end - t0)
                self.maybe_rebalance()
        return out[:n]

    def _harvest(self):
        """Materialize the in-flight batch dispatched by a pipelined
        flush, if any.  An async step failure (the watcher thread saw the
        device computation die) surfaces HERE, with batch context, and
        clears the in-flight entry first so the engine stays usable."""
        if self._inflight is None:
            return None
        out, diag, n, t0, watcher, done, step_no = self._inflight
        self._inflight = None
        with trace.span("engine.wait", flush=step_no):
            watcher.join()
        if done["err"] is not None:
            err = done["err"]
            raise RuntimeError(
                f"pipelined step failed in flight (batch of {n} requests, "
                f"flush #{step_no}): {err!r}") from err
        return self._finish_batch(out, diag, n, t0, done["t"],
                                  step_no=step_no)

    @property
    def steps(self) -> int:
        """Batches dispatched so far: the flush number the next batch
        gets, which its spans and requests carry."""
        return self._flushes

    def flush(self):
        """Run the pending batch.  Inline mode returns its CTRs; under
        ``plan_pipeline`` the batch's plan + step are DISPATCHED (async)
        and the previous in-flight batch's CTRs are returned instead —
        call again with an empty queue (or :meth:`drain`) for the last
        one."""
        if not self._pending:
            return self._harvest()
        step_no = self._flushes
        self._flushes += 1
        with trace.span("engine.flush", flush=step_no) as sp:
            return self._flush(step_no, sp)

    def _flush(self, step_no, sp):
        n = len(self._pending)
        pad = self.batch_size - n
        last = self._pending[-1]
        with trace.span("engine.stack", flush=step_no):
            d = np.stack([p[0] for p in self._pending] + [last[0]] * pad)
            i = np.stack([p[1] for p in self._pending] + [last[1]] * pad)
            m = np.stack([p[2] for p in self._pending] + [last[2]] * pad)
        self._pending.clear()
        # padded rows copy the last request, so they pool its indices
        padded = pad * int(np.count_nonzero(np.asarray(last[2]) > 0))
        t0 = time.perf_counter()
        if not self.plan_pipeline:
            out, diag, pooled = self._run_batch(d, i, m, step_no)
            self._count_pooled(sp, n, pooled, padded)
            return self._finish_batch(out, diag, n, t0, step_no=step_no)
        # flush n+1's plan is dispatched while flush n (the in-flight
        # entry harvested below) still occupies the device — the plan
        # build overlaps stage_a compute instead of serializing with it
        mesh = self._active_mesh()
        with self._mesh_ctx():
            with trace.span("engine.prepare", flush=step_no):
                fitted = self._fit_batch(d, i, m)
                args = self._step_args(*fitted)
            self._count_pooled(sp, n, int(self._live_counts(fitted[2]).sum()),
                               padded)
            with trace.span("engine.plan", flush=step_no):
                # a lookahead-staged plan (stage_plan) is adopted when its
                # batch digest matches what we are about to dispatch; a
                # stale stage (queue churn between peek and flush) replans
                # inline
                staged, self._staged_plan = self._staged_plan, None
                if staged is not None and \
                        staged[0] == self._plan_digest(fitted[1]):
                    plan = staged[1]
                    self.plan_stage_hits += 1
                else:
                    trace.note_step(self._plan_fn, (self.params, args[2]),
                                    mesh)
                    plan = self._plan_fn(self.params, args[2])
            trace.note_step(self._step, args + (plan,), mesh)
            with trace.span("engine.dispatch", flush=step_no):
                out, *diag = self._step(*args, plan)
        # a daemon watcher blocks on the async result off the main thread
        # and stamps true completion, so the harvested batch's latency is
        # dispatch -> device completion, not harvest-to-harvest wall time
        done = {"t": None, "err": None}

        def _watch(o=out, d=done):
            with trace.span("engine.watch", flush=step_no):
                try:
                    jax.block_until_ready(o)
                except Exception as e:   # surfaced at the NEXT harvest
                    d["err"] = e
                finally:
                    d["t"] = time.perf_counter()

        watcher = threading.Thread(target=_watch, daemon=True)
        watcher.start()
        prev = self._harvest()
        self._inflight = (out, diag, n, t0, watcher, done, step_no)
        return prev

    def _count_pooled(self, sp, n, pooled, padded):
        self.stats.pooled_indices += pooled
        self.stats.padded_indices += padded
        sp.set(n=n, pooled=pooled, padded=padded)

    def drain(self):
        """Flush the pending queue AND the pipeline: returns every CTR not
        yet returned (concatenated), or None if nothing is outstanding.

        Idempotent by contract: with an empty queue and no in-flight
        batch this is a guaranteed no-op returning None — callers (the
        serving frontend's shutdown path, chaos harnesses) may drain
        repeatedly without tracking whether anything is outstanding."""
        if not self._pending and self._inflight is None:
            return None
        outs = [o for o in (self.flush(), self._harvest()) if o is not None]
        return np.concatenate(outs) if outs else None

    # -- chaos hardening: fault injection, deadline policy, eviction ------

    def _active_mesh(self):
        """The mesh the engine serves on: its own post-eviction mesh once
        one exists, the ambient ``partition.axis_rules`` mesh before."""
        if self._mesh is not None:
            return self._mesh
        from repro.sharding import partition
        return partition.current_mesh()

    def _mesh_ctx(self):
        """Context installing the engine-owned mesh (post-eviction it
        OVERRIDES whatever the caller's ``axis_rules`` block installed —
        the caller's mesh still names dead devices)."""
        if self._mesh is None:
            import contextlib
            return contextlib.nullcontext()
        from repro.sharding import partition
        return partition.axis_rules(self._mesh)

    def _fit_batch(self, d, i, m):
        """Re-fit a host batch's sparse tensors to the ACTIVE mesh's table
        padding: eviction changes P, and with it t_pad = padded_tables(cfg,
        P).  Cropping is safe (padding tables beyond n_tables carry mask 0
        and are never indexed); growth pads with dead (idx 0, mask 0)
        slots.  A non-identity placement then PERMUTES the table axis —
        physical column p serves original table perm[p], so the shard a
        bag lands on is the one that owns its table."""
        _, t_pad, _, _ = self._exchange_geometry()
        have = i.shape[1]
        if have > t_pad:
            i, m = i[:, :t_pad], m[:, :t_pad]
        elif have < t_pad:
            iz = np.zeros((i.shape[0], t_pad - have, i.shape[2]), i.dtype)
            mz = np.zeros((m.shape[0], t_pad - have, m.shape[2]), m.dtype)
            i = np.concatenate([i, iz], axis=1)
            m = np.concatenate([m, mz], axis=1)
        pm = self._pmap
        if pm is not None and not pm.is_identity:
            perm = pm.perm_array()
            i = np.take(i, perm, axis=1)
            m = np.take(m, perm, axis=1)
        return d, i, m

    def _run_batch(self, d, i, m, step_no):
        """Dispatch one batch with fault injection + bounded-retry
        eviction recovery.  The SAME requests are served no matter how
        many members die: a ``NodeFailure`` (raised by the injector, or
        by real collective monitoring) triggers evict() and the batch is
        re-dispatched on the shrunken mesh — zero requests lost.  Returns
        (step output, diagnostics, valid indices pooled)."""
        for attempt in range(self.max_retries + 1):
            try:
                with trace.span("engine.prepare", flush=step_no):
                    if self.freshness is not None:
                        # the atomic apply window sits BETWEEN flushes:
                        # rows harvested last flush commit (or roll back)
                        # before this flush's batch is dispatched
                        self.freshness.apply(self, step_no)
                    if self.scrub is not None:
                        # repair rows share the freshness apply window (and
                        # run AFTER it, so a delta that already overwrote
                        # the corruption wins); injected faults land before
                        # the audit so the scrubber is exercised, not
                        # informed
                        self.scrub.apply(self, step_no)
                        if self.faults is not None:
                            for (_, t, r, b, tgt) in \
                                    self.faults.bitflips(step_no):
                                self._inject_bitflip(t, r, b, tgt,
                                                     step_no)
                        for g in self.scrub.audit(self, step_no):
                            fs = self._flip_log.pop(g, None)
                            if fs is not None:
                                self.stats.detection_lag_flushes = max(
                                    self.stats.detection_lag_flushes,
                                    step_no - fs)
                    # the cutover window sits between flushes too: once
                    # every migrated row is banked and verified, the atomic
                    # swap happens here, BEFORE this flush's batch is
                    # dispatched
                    resh = self.reshard
                    if resh is not None and resh.try_commit(self, step_no):
                        self._finish_cutover(resh)
                    self._ensure_step()
                    if self.faults is not None:
                        self.faults.on_flush(
                            step_no, mesh=self._active_mesh(),
                            exclude=self.degraded_members)
                    fd, fi, fm = self._fit_batch(d, i, m)
                    args = self._step_args(fd, fi, fm)
                    if self.freshness is not None:
                        dw = self.freshness.next_wire(self, step_no)
                        args = args + tuple(jnp.asarray(dw[k])
                                            for k in DELTA_KEYS)
                    mig_live = self.reshard is not None and \
                        self.reshard.active
                    if mig_live:
                        mw = self.reshard.next_wire(self, step_no)
                        args = args + tuple(jnp.asarray(mw[k])
                                            for k in MIG_KEYS)
                    if self.scrub is not None:
                        rw = self.scrub.next_wire(self, step_no)
                        args = args + tuple(jnp.asarray(rw[k])
                                            for k in REP_KEYS)
                        args = args + (jnp.asarray(
                            self.scrub.quarantine_phys(self), jnp.int32),)
                        args = args + (self._wire_flip_arg(step_no),)
                    if self._step_key[1]:        # with_inv
                        args = args + (
                            jnp.asarray(self.pmap.inv_array()),)
                trace.note_step(self._step, args, self._active_mesh())
                with trace.span("engine.dispatch", flush=step_no), \
                        self._mesh_ctx():
                    out, *diag = self._step(*args)
                with trace.span("engine.account", flush=step_no):
                    held_wbad = None
                    if self.scrub is not None:
                        # wire flags + repair harvest ride LAST; the flags
                        # bank one flush unread (same deferred-harvest
                        # discipline as the riders: never sync the step we
                        # just dispatched).  Processing is deferred to the
                        # END of the flush — _note_wire may evict, and the
                        # accounting below must see this batch's geometry
                        held_wbad, self._held_wbad = \
                            self._held_wbad, diag.pop()
                        self.scrub.ingest(diag.pop(), self, step_no)
                    if mig_live:
                        self.reshard.ingest(diag.pop(), self, step_no)
                    if self.freshness is not None:
                        staged = diag.pop()
                        self.freshness.ingest(staged, self, step_no)
                        fr = self.freshness
                        self.stats.rows_stale_served += \
                            fr.count_stale_served(self, fi, fm)
                        self.stats.rows_applied = fr.rows_applied
                        self.stats.delta_rejects = fr.delta_rejects
                        self.stats.apply_rollbacks = fr.rollbacks
                        self.stats.versions_behind = \
                            fr.ledger.versions_behind
                    if self.scrub is not None:
                        sc = self.scrub
                        self.stats.blocks_scrubbed = sc.blocks_scrubbed
                        self.stats.detections = sc.detections
                        self.stats.repaired_rows = sc.repaired_rows
                        self.stats.quarantined_served += \
                            sc.count_quarantined_served(self, fi, fm)
                    live = self._live_counts(fm)
                    self._observe_load(live, step_no)
                    if held_wbad is not None:
                        self._note_wire(held_wbad, step_no)
                return out, diag, int(live.sum())
            except NodeFailure as e:
                if attempt >= self.max_retries:
                    raise
                if self.retry_backoff_s:
                    time.sleep(self.retry_backoff_s * (2 ** attempt))
                self.evict(e.surviving_devices)
                self.stats.replays += 1
        raise AssertionError("unreachable")

    # -- silent-corruption self-healing (DESIGN.md §12) --------------------

    def _wire_flip_arg(self, step_no):
        """The (P_src, P_dst) uint8 XOR hook the step applies to the
        first payload byte of each fused slot.  All-zeros (XOR identity)
        on a healthy pod — the clean path stays bit-exact with the hook
        armed; the fault injector's scheduled wire corruptions set a
        single byte, which the per-destination checksum is guaranteed to
        catch (every byte carries a non-zero fold weight)."""
        p, _, _, _ = self._exchange_geometry()
        flip = np.zeros((p, p), np.uint8)
        if self.faults is not None:
            for (s, q) in self.faults.wire_corruptions(step_no):
                if s < p and q < p:
                    flip[s, q] = 1
        return jnp.asarray(flip)

    def _note_wire(self, wb, step_no):
        """Process one BANKED flush's wire-verification flags: ledger the
        rejects and escalate persistently corrupt SOURCES through the
        straggler ladder (streak >= confirm_after degrades the member,
        >= 2x evicts it).  A rejected segment's rows were zeroed at
        consume and the riders re-ship next flush, so escalation is about
        the link's health, never about request loss."""
        p, _, _, _ = self._exchange_geometry()
        arr = np.asarray(wb).reshape(-1)
        if arr.size % p:
            return                       # geometry changed under the bank
        per_src = arr.reshape(-1, p).sum(axis=0)
        self.stats.wire_rejects += int(per_src.sum())
        for q in range(p):
            if per_src[q]:
                s = self._wire_streak.get(q, 0) + 1
                self._wire_streak[q] = s
                if s >= 2 * self.confirm_after:
                    self._wire_streak.pop(q, None)
                    self.evict_member(q)
                    return               # positions renumbered: stop here
                if s >= self.confirm_after and \
                        q not in self.degraded_members:
                    self.degrade(tuple(set(self.degraded_members) | {q}))
            else:
                self._wire_streak.pop(q, None)

    def _inject_bitflip(self, table, row, bit, target, step_no):
        """Flip ONE bit of a resident table row (``target='table'``) or
        its hot-cache copy (``target='cache'``) in device memory — the
        §8 fault-plan hook the scrub tests drive.  ``table``/``row`` are
        ORIGINAL-space; the live placement translates to the physical
        column so flips land correctly mid-reshard."""
        pm = self._pmap
        phys_t = int(pm.inv_array()[table]) if pm is not None \
            and not pm.is_identity else int(table)
        byte, bi = divmod(int(bit), 8)
        if target == "cache":
            if self.cache is None:
                return
            slot = int(np.asarray(self.cache.slot_of[phys_t, row]))
            if slot < 0:
                return                   # row not cached: nothing to flip
            vec = np.asarray(self.cache.hot_rows[phys_t, slot])
            u8 = np.frombuffer(vec.tobytes(), np.uint8).copy()
            u8[byte % u8.size] ^= np.uint8(1 << bi)
            new = np.frombuffer(u8.tobytes(), vec.dtype).reshape(vec.shape)
            from repro.serving.hot_cache import HotCache
            self.cache = HotCache(
                hot_ids=self.cache.hot_ids,
                hot_rows=self.cache.hot_rows.at[phys_t, slot].set(
                    jnp.asarray(new)),
                slot_of=self.cache.slot_of)
        else:
            vec = np.asarray(self.params["tables"][phys_t, row])
            u8 = np.frombuffer(vec.tobytes(), np.uint8).copy()
            u8[byte % u8.size] ^= np.uint8(1 << bi)
            new = np.frombuffer(u8.tobytes(), vec.dtype).reshape(vec.shape)
            self.params["tables"] = \
                self.params["tables"].at[phys_t, row].set(jnp.asarray(new))
        r_all = int(self.params["tables"].shape[1])
        self._flip_log[int(table) * r_all + int(row)] = step_no

    # -- skew-aware placement: telemetry, policy, online resharding --------

    @staticmethod
    def _live_counts(fm) -> np.ndarray:
        """Valid (unmasked) indices per physical table column of a fitted
        batch mask, padded rows included."""
        return np.asarray(np.asarray(fm) > 0).sum(axis=(0, 2)) \
            .astype(np.float64)

    def _observe_load(self, live, step_no):
        """Per-table / per-member load telemetry from the flushed batch's
        live (unmasked) indices per physical table column (``live``, from
        :meth:`_live_counts` of the FITTED, already permuted mask) — the
        placement cost model's input and the ``ServeStats`` imbalance
        mirror.  The counts are mapped back to ORIGINAL table space before
        they feed the EWMA: observations survive cutovers and evictions
        unchanged."""
        p, t_pad, _, _ = self._exchange_geometry()
        pm = self._pmap
        if pm is not None and not pm.is_identity:
            orig = np.empty_like(live)
            orig[pm.perm_array()] = live
        else:
            orig = live
        if self.load_model is None or self.load_model.n_tables != t_pad:
            self.load_model = plc_mod.TableLoadModel(t_pad)
        row_b = self.cfg.embed_dim * (
            a2a_mod.WIRE_ITEMSIZE[a2a_mod.canon_wire(self.wire_dtype)]
        ) + a2a_mod.WIRE_SCALE_BYTES[a2a_mod.canon_wire(self.wire_dtype)]
        self.load_model.observe(orig, row_bytes=row_b)
        # per-member pooled rows (physical slot ranges ARE the members)
        mrows = live.reshape(p, -1).sum(axis=1)
        if self._member_ewma is None or len(self._member_ewma) != p:
            self._member_ewma = mrows.copy()
        else:
            self._member_ewma = 0.75 * self._member_ewma + 0.25 * mrows
        st = self.stats
        st.member_rows = [float(x) for x in self._member_ewma]
        st.imbalance_ratio = plc_mod.imbalance(self._member_ewma)
        if self.faults is not None:
            base = self.monitor.percentile(0.5) or 1e-3
            lats = np.asarray(sorted(
                self.faults.latencies(step_no, base).values()), np.float64)
            st.flush_time_ratio = float(lats.max() / lats.mean()) \
                if lats.size and lats.mean() > 0 else 1.0
        else:
            # lockstep SPMD gives no per-member clock: the exchange-load
            # ratio is the best flush-time estimate available
            st.flush_time_ratio = st.imbalance_ratio

    def _table_rows(self, t_pad):
        """Real (unpadded) per-original-table row counts over the padded
        stack — what a migration of each table actually ships."""
        rows = np.zeros(t_pad, np.int64)
        sizes = np.asarray(self.cfg.table_sizes, np.int64)[:t_pad]
        rows[:sizes.shape[0]] = sizes
        return rows

    def maybe_rebalance(self, *, force=False):
        """The background rebalance policy, run once per harvested batch:
        start an online reshard when per-member imbalance stayed over
        ``rebalance_threshold`` for ``rebalance_patience`` consecutive
        flushes, or unconditionally after an eviction re-leveled the
        geometry (``_rebalance_pending``).  Pauses whenever the serving
        ladder is off FULL (``stats.level > 0``: under overload, moving
        rows competes with serving for the wire).  Returns the started
        :class:`ReshardExecutor`, or None."""
        if self.plan_pipeline or (not self.rebalance and not force):
            return None
        if self.reshard is not None:
            return None
        lm = self.load_model
        if lm is None or not lm.ready:
            return None
        if getattr(self.stats, "level", 0) > 0:   # LEVEL_FULL only
            return None
        p, t_pad, _, _ = self._exchange_geometry()
        if p < 2:
            return None
        ml = plc_mod.member_loads(lm.loads, self.pmap, p)
        imb = plc_mod.imbalance(ml)
        if not (force or self._rebalance_pending):
            if imb < self.rebalance_threshold:
                self._imb_streak = 0
                return None
            self._imb_streak += 1
            if self._imb_streak < self.rebalance_patience:
                return None
        plan = plc_mod.plan_migration(
            self.pmap, lm.loads, p, table_rows=self._table_rows(t_pad))
        self._imb_streak = 0
        self._rebalance_pending = False
        if plan.is_noop:
            return None
        return self.start_reshard(plan)

    def start_reshard(self, plan, *, slice_cap=None):
        """Begin a crash-safe online reshard onto ``plan`` (DESIGN.md
        §11).  Serving continues throughout: moved rows ride the fused
        wire in ``slice_cap``-bounded installments; a later flush
        performs the atomic cutover once every row is banked and
        verified.  Until then serving is bit-exact on the pre-move
        layout, and any crash rolls back via evict()."""
        if self.plan_pipeline:
            raise ValueError(
                "online resharding migrates rows through the synchronous "
                "flush path; plan_pipeline's deferred harvest would tear "
                "the cutover boundary — rebalance without plan_pipeline")
        if self.reshard is not None:
            raise ValueError("a reshard is already in flight")
        self._reshard_epoch += 1
        ex = ReshardExecutor(plan, epoch=self._reshard_epoch,
                             slice_cap=slice_cap or self.mig_slice_cap)
        ex.start(self)
        self.reshard = ex
        self._rebuild_step()
        return ex

    def _finish_cutover(self, resh):
        """Post-commit bookkeeping: the layout just changed, so every
        layout-conditioned estimator restarts — the cap autotuner's
        live-count window and the straggler monitor's latency window
        describe skew that no longer exists (they used to silently carry
        over; the frontend's flush EWMA resets off ``layout_version``)."""
        self.stats.reshards += 1
        self.stats.migrated_rows += resh.plan.moved_rows
        self.reshard = None
        self.layout_version += 1
        self.cap_tuner.reset()
        self.monitor.reset()
        self._staged_plan = None
        self._imb_streak = 0
        self._rebuild_step()

    def _after_flush(self, step_no, elapsed):
        """Deadline policy.  A breach is classified by straggler telemetry:
        members flagged by ``detect_stragglers`` for ``confirm_after``
        CONSECUTIVE breaching flushes are sustained (the case no bound
        masks — degrade or evict them per ``on_deadline``); anything else
        is transient, and the response is to widen the absorption window
        (raise the bound toward :meth:`recommend_bound`), never to react
        structurally."""
        if self.deadline_s is None:
            return
        if elapsed <= self.deadline_s:
            self._streak.clear()     # confirmation requires consecutiveness
            return
        self.stats.deadline_breaches += 1
        if self.on_deadline == "block":
            return
        confirmed = self._confirmed_stragglers(step_no, elapsed)
        if not confirmed:
            rec = self.recommend_bound()
            k = min(rec.bound, max(self.microbatches - 1, 0))
            if k > self.bound:
                self.set_bound(k)
            return
        if self.on_deadline == "degrade":
            self.degrade(tuple(set(self.degraded_members) | set(confirmed)))
        else:                        # "evict"
            worst = max(confirmed, key=lambda h: self._streak.get(h, 0))
            self.evict_member(worst)

    def _confirmed_stragglers(self, step_no, elapsed):
        """Sustained-straggler confirmation: per-member latency telemetry
        (synthesized by the injector; a real pod feeds measured values)
        -> ``detect_stragglers`` -> streak bookkeeping."""
        if self.faults is None:
            return []
        base = self.monitor.percentile(0.5) or max(elapsed, 1e-6)
        lats = self.faults.latencies(step_no, base)
        flagged = detect_stragglers(lats)
        for h in flagged:
            self._streak[h] = self._streak.get(h, 0) + 1
        for h in list(self._streak):
            if h not in flagged:
                del self._streak[h]
        return [h for h in flagged
                if self._streak[h] >= self.confirm_after]

    def set_bound(self, bound: int):
        """Adopt a new BLS bound (re-jits the step)."""
        bound = int(bound)
        if bound == self.bound:
            return
        self.bound = bound
        self._rebuild_step()

    def degrade(self, members):
        """Serve AROUND the given model-axis members: their shards'
        exchange contribution is masked and affected bags fall back per
        ``degraded_fallback`` — approximate but deadline-safe, with the
        quality loss ledgered in ``ServeStats.approx_rows``.  Pass ()
        to restore exact serving."""
        members = tuple(sorted({int(x) for x in members}))
        if members == self.degraded_members:
            return
        self.degraded_members = members
        self._rebuild_step()

    def evict_member(self, pos: int):
        """Evict ONE member by model-axis position: its mesh column is
        dropped and :meth:`evict` rebuilds on the survivors.  The fault
        injector (when present) retires the member too, so telemetry and
        future crash schedules track the shrunken pod."""
        mesh = self._active_mesh()
        if mesh is None or "model" not in mesh.axis_names:
            raise ValueError("evict_member needs a model-axis mesh")
        dev = np.asarray(mesh.devices)
        ax = list(mesh.axis_names).index("model")
        keep = [j for j in range(dev.shape[ax]) if j != pos]
        if not keep:
            raise ValueError("cannot evict the last member")
        if self.faults is not None and pos < len(self.faults.live):
            orig = self.faults.live[pos]
            self.faults.fired.add(orig)
            self.faults.live.remove(orig)
        self.evict(list(np.take(dev, keep, axis=ax).reshape(-1)))

    def evict(self, survivors):
        """Full elastic recovery onto ``survivors``: rebuild the mesh
        (preserving the data-axis width when the survivor count allows),
        re-fit + repartition the table stack and cache onto it, reset
        degraded state (positions renumbered), re-jit.  The wall time is
        ledgered in ``ServeStats.recovery_s``."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.runtime import elastic
        from repro.serving.hot_cache import HotCache
        if not survivors:
            raise ValueError("evict: no surviving devices")
        t_rec = time.perf_counter()
        # an in-flight reshard rolls back by the ABSENCE of its commit:
        # abort it, recover on the canonical layout, and let the mandatory
        # post-evict rebalance re-plan against the shrunken geometry
        resh, self.reshard = self.reshard, None
        if resh is not None:
            resh.abort()
            self.stats.reshard_aborts += 1
        old = self._active_mesh()
        n_data = 1
        if old is not None:
            for a in dlrm_mod._batch_axes(old):
                n_data *= old.shape[a]
        n_surv = len(survivors)
        model = n_surv // n_data if n_surv % n_data == 0 else 0
        mesh = elastic.make_mesh_from(survivors, model)
        p_new = mesh.shape["model"]
        n_data_new = 1
        for a in dlrm_mod._batch_axes(mesh):
            n_data_new *= mesh.shape[a]
        denom = n_data_new * self.microbatches * p_new
        if self.batch_size % denom:
            raise ValueError(
                f"batch_size {self.batch_size} does not divide the post-"
                f"eviction geometry (data {n_data_new} x microbatches "
                f"{self.microbatches} x members {p_new})")
        t_pad = dlrm_mod.padded_tables(self.cfg, p_new)

        def host(a):
            return np.asarray(jax.device_get(a))

        # recovery CANONICALIZES placement: undo the live permutation
        # FIRST — fit_t's crop assumes original table order, and under a
        # non-identity map a real table could sit in a high physical slot
        # and be cropped away as "padding"
        pm = self._pmap
        inv = None if pm is None or pm.is_identity else pm.inv_array()

        def canon(a):
            return a[inv] if inv is not None else a

        def fit_t(a, fill=0):
            """Crop/zero-pad a (T_pad_old, ...) stack to the new t_pad —
            padding tables are never indexed (mask 0), so this is exact."""
            if a.shape[0] >= t_pad:
                return a[:t_pad]
            pad = np.full((t_pad - a.shape[0],) + a.shape[1:], fill,
                          a.dtype)
            return np.concatenate([a, pad], axis=0)

        params = {"tables": fit_t(canon(host(self.params["tables"]))),
                  "bot": jax.tree.map(host, self.params["bot"]),
                  "top": jax.tree.map(host, self.params["top"])}
        shardings = {
            "tables": NamedSharding(mesh, P("model", None, None)),
            "bot": jax.tree.map(lambda _: NamedSharding(mesh, P()),
                                self.params["bot"]),
            "top": jax.tree.map(lambda _: NamedSharding(mesh, P()),
                                self.params["top"])}
        self.params = elastic.reshard(params, shardings)
        if self.cache is not None:
            rep = NamedSharding(mesh, P())
            ids = self.cache.hot_ids
            if resh is not None:
                # mid-cutover the cache's physical order is untrustworthy
                # (the crash may sit BETWEEN the commit's two swaps, where
                # tables and cache disagree): cold-start it — shapes
                # refit, every slot a miss, warmed back by serving
                from repro.serving import hot_cache as hc_mod
                cold = hc_mod.cold(HotCache(
                    hot_ids=(host(ids) if ids is not None else None),
                    hot_rows=host(self.cache.hot_rows),
                    slot_of=host(self.cache.slot_of)))
                self.cache = HotCache(
                    hot_ids=(jax.device_put(
                        fit_t(np.asarray(cold.hot_ids), fill=-1), rep)
                        if ids is not None else None),
                    hot_rows=jax.device_put(
                        fit_t(np.asarray(cold.hot_rows)), rep),
                    slot_of=jax.device_put(
                        fit_t(np.asarray(cold.slot_of), fill=-1), rep))
            else:
                self.cache = HotCache(
                    hot_ids=(jax.device_put(fit_t(canon(host(ids))), rep)
                             if ids is not None else None),
                    hot_rows=jax.device_put(
                        fit_t(canon(host(self.cache.hot_rows))), rep),
                    # -1 = miss: resurrected padding tables stay cold
                    slot_of=jax.device_put(
                        fit_t(canon(host(self.cache.slot_of)), fill=-1),
                        rep))
        self._mesh = mesh
        self.degraded_members = ()   # positions renumbered: start clean
        self._streak.clear()
        # post-recovery placement is the identity boot layout; every
        # layout-conditioned estimator recalibrates (the cap window and
        # latency window used to silently carry over an eviction), and a
        # rebalance against the shrunken geometry becomes mandatory
        self._pmap = None
        self.layout_version += 1
        self.load_model = None
        self._member_ewma = None
        self._imb_streak = 0
        self._rebalance_pending = True
        self.cap_tuner.reset()
        self.monitor.reset()
        self._rebuild_step()
        if self.freshness is not None:
            # un-committed delta rows re-queue; ownership is recomputed
            # from the new geometry at the next ship
            self.freshness.on_evict(self)
        if self.scrub is not None:
            # in-flight repairs re-queue against the refit mirror; banked
            # wire flags describe the OLD geometry and are dropped
            self.scrub.on_evict(self)
            self._held_wbad = None
            self._wire_streak.clear()
        self.stats.evictions += 1
        self.stats.recovery_s += time.perf_counter() - t_rec

    # -- ragged-exchange cap autotuning ------------------------------------

    def _exchange_geometry(self):
        """(P, t_pad, bs, dense_rows) under the installed mesh, where bs is
        the per-(member, microbatch) batch slice and dense_rows = bs·t_loc
        is what the dense butterfly moves per destination."""
        mesh = self._active_mesh()
        if mesh is not None and "model" in mesh.axis_names:
            p = mesh.shape["model"]
            n_data = 1
            for a in dlrm_mod._batch_axes(mesh):   # same source of truth
                n_data *= mesh.shape[a]            # as forward_distributed
        else:
            p, n_data = 1, 1
        t_pad = dlrm_mod.padded_tables(self.cfg, p)
        bs = max(1, self.batch_size // (n_data * self.microbatches * p))
        return p, t_pad, bs, bs * (t_pad // p)

    def retune_cap(self):
        """Under ``exchange='auto'``: adopt the autotuner's cap
        recommendation, re-jitting the step when it differs enough to
        matter — growth (drops seen, or the live tail drifted up) is
        adopted immediately, shrinks only past 25% to avoid re-trace
        thrash.  Under a forced exchange this is a PURE read (peeked
        recommendation, no state mutated, no re-jit).  Returns the
        recommendation (or None before any observations)."""
        if not len(self.cap_tuner):
            return None
        _, _, _, dense_rows = self._exchange_geometry()
        cur = self.ragged_cap or dense_rows
        rec = self.cap_tuner.recommend(dense_rows=dense_rows,
                                       current_cap=self.ragged_cap or None,
                                       peek=self.exchange != "auto")
        if self.exchange != "auto":
            return rec
        grow = rec.cap > cur
        shrink = rec.cap * 4 <= cur * 3
        if grow or shrink:
            self.ragged_cap = rec.cap
            self.stats.retunes += 1
            self._rebuild_step()
        return rec

    def slot_bytes(self) -> int:
        """Bytes ONE BLS ring slot buffers under the current engine
        configuration.  The exchange payload is the fused wire buffer
        (DESIGN.md §7) — one flat (P, slot_bytes) uint8 leaf whose layout
        already accounts codec rows, int8 scales, narrow slot ids, counts
        and alignment padding; the same buffer rides the slot whether the
        pipeline is mono (the received buffer) or ring (the send buffer
        awaiting its ppermute rounds).  Side activations add their own
        per-leaf bytes."""
        cfg = self.cfg
        p, t_pad, bs, dense_rows = self._exchange_geometry()
        s = cfg.embed_dim
        use_cache = self.cache is not None and self.cache.cache_rows > 0
        use_ragged, cap = dlrm_mod.resolve_exchange(
            self.exchange, use_cache=use_cache, cap=self.ragged_cap,
            dense_rows=dense_rows)
        delta_bytes = 0
        if self.freshness is not None:
            delta_bytes = a2a_mod.delta_wire_layout(
                p, self.freshness.slice_cap, s,
                self.params["tables"].dtype).slot_bytes
        mig_bytes = 0
        if self.reshard is not None and self.reshard.active:
            mig_bytes = a2a_mod.mig_wire_layout(
                p, self.reshard.slice_cap, s,
                self.params["tables"].dtype).slot_bytes
        rep_bytes = 0
        if self.scrub is not None:
            rep_bytes = a2a_mod.rep_wire_layout(
                p, self.scrub.slice_cap, s,
                self.params["tables"].dtype).slot_bytes
        layout = a2a_mod.exchange_wire_layout(
            ragged=use_ragged, n_dest=p, cap=cap, bs=bs, t_loc=t_pad // p,
            embed_dim=s, wire_dtype=self.wire_dtype,
            emb_dtype=self.params["tables"].dtype,
            delta_bytes=delta_bytes, mig_bytes=mig_bytes,
            rep_bytes=rep_bytes, wire_check=self.scrub is not None)
        recv = {"buf": jax.ShapeDtypeStruct((p, layout.slot_bytes),
                                            jnp.uint8)}
        side = [jax.ShapeDtypeStruct((bs, s), jnp.dtype(cfg.dtype))]
        if use_cache:
            side.append(jax.ShapeDtypeStruct(
                (bs, t_pad, s), self.params["tables"].dtype))
        return bls_mod.ring_slot_bytes(recv, side)

    def recommend_bound(self, memory_budget: int = 64 << 20):
        """Memory-budget -> bound recommendation, with slot_bytes from
        :meth:`slot_bytes` — what the ring actually buffers, not a dense
        f32 estimate."""
        return self.monitor.recommend_bound(slot_bytes=self.slot_bytes(),
                                            memory_budget=memory_budget)


class LMEngine:
    """Batched greedy decoding for the LM families."""

    def __init__(self, params, cfg: ModelConfig, *, max_len: int = 256):
        self.params, self.cfg, self.max_len = params, cfg, max_len
        self._serve = jax.jit(steps_mod.make_serve_step(cfg))
        self.monitor = StragglerMonitor()

    def generate(self, prompts: np.ndarray, n_tokens: int) -> np.ndarray:
        """prompts: (B, P) int32 -> (B, n_tokens) greedy continuation."""
        from repro.models import transformer as T
        b, p = prompts.shape
        if self.cfg.family in ("dense", "moe", "vlm"):
            _, cache = T.prefill(self.params, self.cfg,
                                 jnp.asarray(prompts), pad_to=self.max_len)
        else:
            cache = api.make_cache(self.cfg, b, self.max_len)
            for t in range(p):  # recurrent families consume token-by-token
                _, cache = api.decode_step(self.params, self.cfg,
                                           jnp.asarray(prompts[:, t:t + 1]),
                                           cache)
        tok = jnp.asarray(prompts[:, -1:])
        outs = []
        for _ in range(n_tokens):
            t0 = time.perf_counter()
            tok, cache = self._serve(self.params, tok, cache)
            self.monitor.observe(time.perf_counter() - t0)
            outs.append(np.asarray(tok))
        return np.concatenate(outs, axis=1)
