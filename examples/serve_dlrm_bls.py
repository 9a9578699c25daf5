"""End-to-end driver: BLS-enabled DLRM inference serving (the paper's kind).

Streams batched CTR requests through the serving engine with the bounded-lag
pipeline, measures latency/throughput, lets the straggler monitor recommend a
bound, and cross-checks BLS-on vs BLS-off outputs bit-for-bit.

Run:  PYTHONPATH=src python examples/serve_dlrm_bls.py [--batches 20]
      [--batch-size 256] [--bound 4] [--microbatches 8]
      [--wire-dtype float32|bfloat16|int8] [--cache-rows N]
      [--exchange dense|ragged|auto] [--ragged-cap N] [--row-block N]
      [--pool-mode auto|vector|scalar]
      [--exchange-pipeline mono|ring|auto]
      [--frontend [--open-requests N] [--overload X] [--burstiness B]
       [--slo-ms MS] [--max-queue N] [--admission slo|queue|none]
       [--updates N] [--k-fresh K]]

With --frontend the example switches from closed-loop batch replay to the
overload-robust serving frontend (DESIGN.md §9): an open-loop bursty
request stream is generated at --overload times the engine's measured
capacity and driven in real time through SLO-aware admission, deadline
shedding and backpressure; the run reports the request-level ledger and
asserts the exact accounting invariant.

With --updates N (frontend mode) a live synthetic delta stream — N rows
per version — rides the fused BLS wire while the frontend keeps
admitting (DESIGN.md §10): versioned row updates are shipped inside the
serving exchange, applied atomically between flushes under the
--k-fresh bounded-staleness gate, and the run reports the freshness
ledger and asserts versions_behind <= k_fresh at every flush.

With --cache-rows > 0 and --exchange auto, the engine starts on the dense
butterfly and the cap autotuner flips it to the ragged miss-residual
exchange (DESIGN.md §6) once the observed live counts justify a cap.

--exchange-pipeline picks how the fused wire buffer moves (DESIGN.md §7):
'mono' ships it as one all_to_all per exchange, 'ring' as P-1 chunked
ppermute rounds with per-peer decode/compute overlap — bit-identical
outputs, the knob trades collective-issue overhead against overlap.
'auto' resolves to ring when the model axis has >= 4 members.

--row-block picks the embedding-bag kernel regime (DESIGN.md §1): 0 (auto)
keeps small table blocks VMEM-resident and switches production-size tables
to the DMA stream of 128-row lane tiles; > 0 forces streaming at that block
height (useful for A/B-ing the streamed path at small scale).

--pool-mode picks the kernel's pooling loop (DESIGN.md §1): 'vector' (what
'auto' resolves to) gathers whole lane-width row tiles per step, 'scalar'
keeps the one-row-per-iteration walk — both bit-identical in f32, so the
flag exists purely for A/B timing.
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import base as cb
from repro.data import synthetic as S
from repro.data.pipeline import Preloader
from repro.launch.mesh import make_host_mesh
from repro.models import dlrm as D
from repro.serving.engine import DLRMEngine
from repro.sharding import partition

# wire-codec round-trip error bounds on the sigmoid CTR outputs
# (float32 allows the cache path's fp32 hits+misses summation reorder)
WIRE_TOL = {"float32": 1e-4, "bfloat16": 3e-2, "int8": 6e-2}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--bound", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--wire-dtype", default="float32",
                    choices=sorted(WIRE_TOL))
    ap.add_argument("--cache-rows", type=int, default=0,
                    help="hot-row cache rows per table (0 = off)")
    ap.add_argument("--exchange", default="auto",
                    choices=("dense", "ragged", "auto"),
                    help="pooled-exchange collective (DESIGN.md §6)")
    ap.add_argument("--ragged-cap", type=int, default=0,
                    help="rows per destination bucket (0 = autotuned)")
    ap.add_argument("--row-block", type=int, default=0,
                    help="embedding-bag row streaming (DESIGN.md §1): 0 = "
                         "auto, > 0 = forced DMA-streamed block height")
    ap.add_argument("--pool-mode", default="auto",
                    choices=("auto", "vector", "scalar"),
                    help="embedding-bag pooling loop (DESIGN.md §1): "
                         "chunked vector gather ('auto'/'vector') vs the "
                         "scalar one-row walk — bit-identical, for A/B")
    ap.add_argument("--exchange-pipeline", default="auto",
                    choices=("mono", "ring", "auto"),
                    help="fused-wire collective (DESIGN.md §7): one "
                         "all_to_all ('mono') vs P-1 chunked ppermute "
                         "rounds with per-peer decode overlap ('ring') — "
                         "bit-identical outputs; 'auto' = ring at P >= 4")
    ap.add_argument("--frontend", action="store_true",
                    help="serve an open-loop bursty request stream through "
                         "the overload-robust frontend (DESIGN.md §9) "
                         "instead of closed-loop batch replay")
    ap.add_argument("--open-requests", type=int, default=512,
                    help="--frontend: number of open-loop requests")
    ap.add_argument("--overload", type=float, default=1.5,
                    help="--frontend: offered load as a multiple of the "
                         "engine's measured capacity (>1 overloads)")
    ap.add_argument("--burstiness", type=float, default=0.3,
                    help="--frontend: burst-opening probability in [0, 1)")
    ap.add_argument("--slo-ms", type=float, default=100.0,
                    help="--frontend: per-request deadline budget")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="--frontend: queue bound (0 = 4 batches)")
    ap.add_argument("--admission", default="slo",
                    choices=("slo", "queue", "none"),
                    help="--frontend: admission policy ('none' = the "
                         "accept-everything breaching baseline)")
    ap.add_argument("--updates", type=int, default=0,
                    help="--frontend: stream live embedding-row deltas at "
                         "N rows per version over the BLS wire "
                         "(DESIGN.md §10; 0 = off)")
    ap.add_argument("--k-fresh", type=int, default=2,
                    help="--frontend --updates: bounded-staleness gate — "
                         "max versions any member may lag")
    ap.add_argument("--rebalance", action="store_true",
                    help="closed-loop demo of skew-aware placement "
                         "(DESIGN.md §11): a drifting-hotset stream "
                         "triggers an online reshard — rows migrate "
                         "over the fused wire while serving continues, "
                         "bit-exact vs a static-placement engine")
    args = ap.parse_args()

    cfg = cb.get_arch("dlrm-kaggle").smoke()
    # table-parallel over every local device so the butterfly, wire codec
    # and cache path actually execute (model=1 still runs them, degenerately)
    n_model = len(jax.devices())
    while args.batch_size % (args.microbatches * n_model):
        n_model //= 2
    mesh = make_host_mesh(model=n_model)
    params = D.init_dlrm(jax.random.PRNGKey(0), cfg, n_shards=n_model)
    t_pad = D.padded_tables(cfg, n_model)

    if args.frontend:
        return run_frontend(args, cfg, mesh, params, t_pad)
    if args.rebalance:
        return run_rebalance(args, cfg, mesh, params, t_pad)

    # paper protocol: preload the dataset before measuring
    data = Preloader(
        lambda i: S.make_batch(cfg, args.batch_size, mode="hetero", seed=7,
                               step=i, t_pad=t_pad), args.batches)

    engines = {
        "sync(k=0)": DLRMEngine(params, cfg, batch_size=args.batch_size,
                                bound=0, microbatches=1,
                                row_block=args.row_block,
                                pool_mode=args.pool_mode,
                                exchange_pipeline=args.exchange_pipeline),
        f"bls(k={args.bound})": DLRMEngine(
            params, cfg, batch_size=args.batch_size, bound=args.bound,
            microbatches=args.microbatches, wire_dtype=args.wire_dtype,
            exchange=args.exchange, ragged_cap=args.ragged_cap,
            exchange_pipeline=args.exchange_pipeline,
            row_block=args.row_block, pool_mode=args.pool_mode),
    }
    if args.cache_rows > 0:
        # calibrate the BLS engine's hot cache on the first preloaded batch
        calib = S.make_batch(cfg, args.batch_size, mode="hetero", seed=7,
                             step=0, t_pad=t_pad)
        name = f"bls(k={args.bound})"
        cache = engines[name].calibrate_cache(calib.idx, calib.mask,
                                              args.cache_rows)
        from repro.serving import hot_cache as HC
        hr = HC.hit_rate(cache, jax.numpy.asarray(calib.idx),
                         jax.numpy.asarray(calib.mask))
        print(f"hot cache: {args.cache_rows} rows/table, "
              f"calibration hit rate {hr:.2f}")
    outputs = {}
    with partition.axis_rules(mesh):
        for name, eng in engines.items():
            outs = []
            for b in data:
                for i in range(args.batch_size):
                    r = eng.submit(b.dense[i], b.idx[i], b.mask[i])
                    if r is not None:
                        outs.append(r)
            tail = eng.flush()
            if tail is not None:
                outs.append(tail)
            outputs[name] = np.concatenate(outs)
            p50 = eng.monitor.percentile(0.5) * 1e3
            p99 = eng.monitor.percentile(0.99) * 1e3
            print(f"{name:12s}: {eng.stats.requests} reqs, "
                  f"{eng.stats.throughput_rps:,.0f} req/s, "
                  f"batch p50={p50:.1f} ms p99={p99:.1f} ms")

    names = list(outputs)
    diff = float(np.max(np.abs(outputs[names[0]] - outputs[names[1]])))
    tol = WIRE_TOL[args.wire_dtype]
    print(f"max |CTR(sync) - CTR(bls)| = {diff:.2e} (tol {tol:.0e}; paper "
          f"§III-C: accuracy fully preserved, wire codec adds bounded noise)")
    assert diff < tol
    eng = engines[names[1]]
    rec = eng.recommend_bound()
    print(f"straggler monitor: {rec.reason} "
          f"(ring slot = {eng.slot_bytes()} B)")
    cap_rec = eng.retune_cap()
    if cap_rec is not None:
        print(f"cap autotuner: {cap_rec.reason} "
              f"({eng.stats.retunes} retunes, cap in service = "
              f"{eng.ragged_cap or 'dense-equivalent'})")


def run_frontend(args, cfg, mesh, params, t_pad):
    """Open-loop bursty serving through the overload-robust frontend."""
    from repro.serving.frontend import ServingFrontend

    fm = None
    if args.updates > 0:
        from repro.runtime.freshness import FreshnessManager
        fm = FreshnessManager(
            S.delta_stream(cfg, rows_per_version=args.updates, seed=7),
            k_fresh=args.k_fresh)
        print(f"freshness: streaming {args.updates} rows/version onto "
              f"the wire, k_fresh={args.k_fresh}")
    eng = DLRMEngine(params, cfg, batch_size=args.batch_size,
                     bound=args.bound, microbatches=args.microbatches,
                     wire_dtype=args.wire_dtype, exchange=args.exchange,
                     ragged_cap=args.ragged_cap,
                     exchange_pipeline=args.exchange_pipeline,
                     row_block=args.row_block, pool_mode=args.pool_mode,
                     freshness=fm)
    with partition.axis_rules(mesh):
        # warm the compile caches, then measure the steady flush time the
        # offered load and the admission predictor are calibrated against
        warm = S.make_batch(cfg, args.batch_size, mode="hetero", seed=7,
                            step=0, t_pad=t_pad)
        flush_s = []
        for _ in range(max(2, args.batches)):
            t0 = time.perf_counter()
            for i in range(args.batch_size):
                eng.submit(warm.dense[i], warm.idx[i], warm.mask[i])
            eng.drain()
            flush_s.append(time.perf_counter() - t0)
        flush_s = min(flush_s)
        capacity_rps = args.batch_size / flush_s
        rate = args.overload * capacity_rps
        print(f"capacity ~{capacity_rps:,.0f} req/s (flush "
              f"{flush_s * 1e3:.1f} ms); offering {args.overload:.1f}x "
              f"= {rate:,.0f} req/s, burstiness {args.burstiness}")

        reqs = S.request_stream(cfg, args.open_requests, rate_rps=rate,
                                burstiness=args.burstiness, mode="hetero",
                                t_pad=t_pad, seed=7)
        fe = ServingFrontend(
            eng, slo_s=args.slo_ms / 1e3,
            max_queue=args.max_queue or 4 * args.batch_size,
            admission=args.admission, init_flush_s=flush_s)
        completed, nxt = [], 0
        t0 = time.perf_counter()
        while nxt < len(reqs):
            # open-loop drive: everything that has arrived by now enters
            # before the next scheduling round, backdated to its true
            # arrival — a flush never throttles the offered load
            now = time.perf_counter()
            while nxt < len(reqs) and t0 + reqs[nxt].t_arrive <= now:
                r = reqs[nxt]
                fe.try_submit(r.dense, r.idx, r.mask,
                              now=t0 + r.t_arrive)
                nxt += 1
            completed += fe.pump()
        completed += fe.drain()

    st = fe.stats
    e2e, qd = st.e2e, st.queue_delay
    print(f"frontend[{args.admission}]: offered {st.offered}, admitted "
          f"{st.admitted}, rejected {st.rejected} (retried {st.retried}), "
          f"shed {st.shed}, served {st.served} (+{st.degraded_served} "
          f"degraded), late {st.served_late}")
    print(f"latency: queue-delay p50={qd.percentile(.5) * 1e3:.1f} "
          f"p99={qd.percentile(.99) * 1e3:.1f} ms, e2e "
          f"p50={e2e.percentile(.5) * 1e3:.1f} "
          f"p99={e2e.percentile(.99) * 1e3:.1f} ms (SLO {args.slo_ms} ms)")
    ok = (st.accounted and st.queued == 0 and st.inflight == 0
          and len(completed) == st.completed)
    print(f"accounting: {'exact' if ok else 'DRIFTED'} "
          f"(admitted {st.admitted} == served {st.served} + degraded "
          f"{st.degraded_served} + shed {st.shed})")
    assert ok, "conservation invariant violated"
    if fm is not None:
        behind = max(fm.behind_trace, default=0)
        print(f"freshness: applied {fm.rows_applied} rows over "
              f"{fm.applies} atomic windows while serving; staleness "
              f"max {behind} <= k_fresh {fm.k_fresh}, "
              f"{eng.stats.rows_stale_served} stale rows served, "
              f"{fm.delta_rejects} rejects, {fm.rollbacks} rollbacks")
        assert all(v <= fm.k_fresh for v in fm.behind_trace), \
            "bounded-staleness invariant violated"


def run_rebalance(args, cfg, mesh, params, t_pad):
    """Skew-aware placement demo (DESIGN.md §11): serve a drifting
    hot-set stream through two engines — one static, one with the
    online rebalance policy — and show the reshard ledger with
    bit-exact outputs."""
    # placement permutes tables across members, so each member must own
    # >= 2 slots for a move to exist (t_loc = 1 makes every layout a
    # relabeling with identical member loads — the planner noops)
    n_model = mesh.shape["model"]
    while n_model > 1 and D.padded_tables(cfg, n_model) // n_model < 2:
        n_model //= 2
    while args.batch_size % (args.microbatches * n_model):
        n_model //= 2
    if n_model != mesh.shape["model"]:
        print(f"placement: shrinking model axis to {n_model} so each "
              f"member owns >= 2 table slots")
        mesh = make_host_mesh(model=n_model)
        params = D.init_dlrm(jax.random.PRNGKey(0), cfg,
                             n_shards=n_model)
        t_pad = D.padded_tables(cfg, n_model)
    eng = DLRMEngine(dict(params), cfg, batch_size=args.batch_size,
                     bound=args.bound, microbatches=args.microbatches,
                     rebalance=True, rebalance_threshold=1.05,
                     rebalance_patience=2, mig_slice_cap=8)
    ref = DLRMEngine(dict(params), cfg, batch_size=args.batch_size,
                     bound=args.bound, microbatches=args.microbatches)
    outs, refs = [], []
    with partition.axis_rules(mesh):
        for s in range(args.batches):
            b = S.make_batch(cfg, args.batch_size, mode="drift",
                             t_pad=t_pad, seed=7, step=s)
            for i in range(args.batch_size):
                o = eng.submit(b.dense[i], b.idx[i], b.mask[i])
                ro = ref.submit(b.dense[i], b.idx[i], b.mask[i])
                if o is not None:
                    outs.append(o)
                if ro is not None:
                    refs.append(ro)
    st = eng.stats
    print(f"placement: reshards={st.reshards} aborts={st.reshard_aborts} "
          f"migrated_rows={st.migrated_rows} "
          f"imbalance={st.imbalance_ratio:.3f} "
          f"layout_version={eng.layout_version}")
    ewma = [] if eng._member_ewma is None else list(eng._member_ewma)
    print(f"placement: member pooled rows (EWMA) = "
          f"{[round(float(x), 1) for x in ewma]}")
    if eng.reshard is not None:
        print(f"placement: reshard in flight: {eng.reshard.summary()}")
    a, b_ = np.concatenate(outs), np.concatenate(refs)
    exact = a.shape == b_.shape and bool((a == b_).all())
    print(f"placement: served CTRs bit-exact vs static placement: "
          f"{exact} ({st.requests} requests, zero lost)")
    assert exact, "rebalanced serving diverged from the static engine"
    assert len(outs) * args.batch_size == st.requests


if __name__ == "__main__":
    main()
