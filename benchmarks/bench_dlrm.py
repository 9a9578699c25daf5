"""DLRM end-to-end benchmark (the paper's §VI-B with measured stage times).

1. Measure the real JAX stage durations (apply_emb / bottom MLP /
   interaction+top) of the smoke-scale DLRM on this host.
2. Feed them to the schedule simulator at 8 processes and sweep the bound —
   the paper's latency/throughput plots driven by OUR implementation's
   numbers rather than hand-picked constants.
3. Report the BLS ring memory overhead for the paper's configuration.
4. Measure the FUSED sparse hot path (DESIGN.md): reference vs Pallas
   pooled lookup, and the exchanged payload bytes of the reference f32
   butterfly vs the cache-aware + quantized-wire exchange under the
   power-law-skewed heterogeneous distribution.

``run`` returns a machine-readable payload; ``write_bench_json`` appends it
to BENCH_dlrm.json keyed by git SHA so the perf trajectory is diffable
across PRs.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import base as cb
from repro.core import alltoallv as A2A
from repro.core.schedule_sim import Workload, simulate
from repro.data import synthetic as S
from repro.models import dlrm as D
from repro.serving import hot_cache as HC

import numpy as np


def _kernel_impl() -> str:
    """Kernels compile natively on TPU; elsewhere the interpreter runs
    them."""
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


def _timeit(fn, *args, reps=10):
    fn(*args)
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps


def _best(fn, *args, reps=5, trials=3):
    """min-of-trials: the standard microbenchmark noise filter — scheduler
    hiccups only ever ADD time, so the minimum is the honest estimate."""
    return min(_timeit(fn, *args, reps=reps) for _ in range(trials))


def _best_paired(fns: dict, *args, reps=5, trials=6):
    """min-of-trials with the candidates INTERLEAVED, so a load spike taxes
    every candidate equally instead of biasing whichever ran under it —
    the honest way to compare two stages on a shared host."""
    for fn in fns.values():
        fn(*args)                       # compile outside the clock
    best = {k: float("inf") for k in fns}
    for _ in range(trials):
        for k, fn in fns.items():
            best[k] = min(best[k], _timeit(fn, *args, reps=reps))
    return best


def _stage_throughput(batch: int, t: int, hot: int, s: int,
                      seconds: float) -> dict:
    """Scale-independent stage throughput: request rows/s plus the pooled
    embedding GB/s the stage moved (B·T·hot weighted (row, s) f32 tiles) —
    so cross-SHA BENCH_dlrm.json comparisons survive shape changes."""
    if not seconds:
        return {"rows_per_s": 0.0, "pooled_gb_per_s": 0.0}
    return {"rows_per_s": batch / seconds,
            "pooled_gb_per_s": batch * t * hot * s * 4 / seconds / 1e9}


def measure_stages(batch=512):
    cfg = cb.get_arch("dlrm-kaggle").smoke()
    params = D.init_dlrm(jax.random.PRNGKey(0), cfg, n_shards=1)
    b = S.make_batch(cfg, batch, mode="hetero", seed=0)
    dense, idx, mask = map(jnp.asarray, (b.dense, b.idx, b.mask))

    emb = jax.jit(lambda p, i, m: D.apply_emb(p["tables"][:cfg.n_tables],
                                              i[:, :cfg.n_tables],
                                              m[:, :cfg.n_tables]))
    bot = jax.jit(lambda p, d: D.apply_mlp(p["bot"], d))

    def top_fn(p, z0, e):
        z = jnp.concatenate([z0[:, None, :], e], axis=1)
        inter = D.dot_interaction(z)
        return D.apply_mlp(p["top"], jnp.concatenate(
            [z0, inter.astype(z0.dtype)], -1))

    top = jax.jit(top_fn)
    t_emb = _timeit(emb, params, idx, mask)
    z0 = bot(params, dense)
    e = emb(params, idx, mask)
    t_bot = _timeit(bot, params, dense)
    t_top = _timeit(top, params, z0, e)
    full = jax.jit(lambda p, d, i, m: D.forward_local(p, cfg, d, i, m))
    t_full = _timeit(full, params, dense, idx, mask)
    t, hot, s = cfg.n_tables, cfg.max_hot, cfg.embed_dim
    return {"t_emb": t_emb, "t_bot": t_bot, "t_top": t_top,
            "t_full": t_full,
            "throughput": {
                k: _stage_throughput(batch, t, hot, s, v)
                for k, v in [("t_emb", t_emb), ("t_full", t_full)]}}


def measure_fused(batch=256, cache_rows=16, csv=True):
    """The fused sparse hot path under power-law skew + ragged bags:
    pooled-lookup(+exchange) stage time per backend, and the exchanged
    payload bytes per wire format with and without the hot cache.  On one
    device the butterfly is the identity, so the stage time covers pooled
    lookup + wire encode/decode + pooled-hit correction — the per-member
    compute of the exchange stage; payload bytes are exact (they depend
    only on the miss residual and the codec, not on the device count)."""
    cfg = cb.get_arch("dlrm-kaggle").smoke()
    params = D.init_dlrm(jax.random.PRNGKey(0), cfg, n_shards=1)
    t, s = cfg.n_tables, cfg.embed_dim
    tables = params["tables"][:t]
    b = S.make_batch(cfg, batch, mode="powerlaw_hetero", seed=0)
    idx, mask = jnp.asarray(b.idx[:, :t]), jnp.asarray(b.mask[:, :t])

    cache = HC.build_from_batch(tables, b.idx[:, :t], b.mask[:, :t],
                                cache_rows)
    hit_rate = HC.hit_rate(cache, idx, mask)
    _, miss_mask = HC.lookup(cache, idx, mask)

    # --- pooled-lookup stage time: reference vs Pallas kernel ---
    kernel_backend = _kernel_impl()
    lookups = {
        "ref": jax.jit(lambda i, m: D.apply_emb(tables, i, m, "ref")),
        kernel_backend: jax.jit(
            lambda i, m: D.apply_emb(tables, i, m, kernel_backend)),
    }
    stage_times = {name: _best(fn, idx, mask)
                   for name, fn in lookups.items()}

    # --- the fused stage: miss residual lookup + wire codec + hit add ---
    def fused(i, m, mm):
        pooled = D.apply_emb(tables, i, mm, "ref")
        payload = A2A.encode_wire(pooled, "bfloat16")   # butterfly here
        emb = A2A.decode_wire(payload, tables.dtype)
        hits = HC.pooled_hits_of(cache.hot_rows, cache.slot_of, i, m)
        return emb + hits.astype(emb.dtype)

    mm = jnp.asarray(miss_mask)

    # --- the ragged stage (DESIGN.md §6): pack the live rows, pool ONLY
    # what ships, codec, scatter back.  On one device the alltoallv is the
    # identity, so the stage time covers the per-member pack + pooled
    # lookup of O(cap) rows + codec + receive-side scatter; exchanged
    # bytes are exact.  The cap is what the serving autotuner would pick
    # from the observed live counts.
    from repro.runtime.straggler import CapAutotuner
    dense_rows = batch * t
    tuner = CapAutotuner()
    tuner.observe(int(np.asarray((miss_mask > 0).any(-1)).sum()), 0)
    cap = tuner.recommend(dense_rows=dense_rows).cap

    def ragged(i, m, mm):
        payload, drops = D.ragged_exchange_pack(tables, i, mm, n_dest=1,
                                                cap=cap, wire="bfloat16")
        emb = D.ragged_exchange_unpack(payload, t_loc=t, bs=batch,
                                       out_dtype=tables.dtype)
        hits = HC.pooled_hits_of(cache.hot_rows, cache.slot_of, i, m)
        return emb + hits.astype(emb.dtype), drops

    stage_times.update(_best_paired(
        {"fused_cache_bf16": jax.jit(fused),
         "ragged_cache_bf16": jax.jit(ragged)}, idx, mask, mm))
    out_ragged, drops = jax.jit(ragged)(idx, mask, mm)
    out_fused = jax.jit(fused)(idx, mask, mm)
    assert np.allclose(np.asarray(out_ragged), np.asarray(out_fused),
                       atol=1e-5), "ragged stage diverged from fused stage"

    # --- exchanged payload bytes per configuration ---
    wires = {
        "ref_f32": A2A.wire_stats(mask, s, "float32"),
        "bf16": A2A.wire_stats(mask, s, "bfloat16"),
        "cache_bf16": A2A.wire_stats(miss_mask, s, "bfloat16"),
        "cache_int8": A2A.wire_stats(miss_mask, s, "int8"),
    }
    ref_bytes = wires["ref_f32"].ref_bytes
    # size the REAL fused buffer built from the packed payload so the
    # recorded bytes can never drift from what the wire actually moves
    # (narrow ids + counts + alignment padding included); the analytic
    # helper is cross-checked against it
    real_payload, _ = D.ragged_exchange_pack(tables, idx, mm, n_dest=1,
                                             cap=cap, wire="bfloat16")
    n_slots = batch * t
    layout = A2A.exchange_wire_layout(
        ragged=True, n_dest=1, cap=cap, bs=batch, t_loc=t, embed_dim=s,
        wire_dtype="bfloat16")
    # padding-waste accounting of the fused buffer the wire moves: the
    # payload bytes ARE the single-buffer bytes (ids, counts, alignment
    # padding included), useful bytes the live codec rows
    a2av = A2A.dispatch_stats(real_payload["counts"], cap,
                              layout.field("q").nbytes // cap,
                              slot_bytes=layout.slot_bytes)
    ragged_bytes = int(A2A.fuse_wire(real_payload, layout).size)
    assert ragged_bytes == layout.wire_bytes == a2av.payload_bytes == \
        A2A.ragged_wire_bytes(1, cap, s, "bfloat16", n_slots=n_slots)
    payload = {
        "batch": batch, "cache_rows": cache_rows,
        "hit_rate": float(hit_rate),
        "stage_us": {k: v * 1e6 for k, v in stage_times.items()},
        # rows/s + pooled GB/s next to every stage ms, so cross-SHA entry
        # comparisons are scale-independent
        "stage_throughput": {
            k: _stage_throughput(batch, t, cfg.max_hot, s, v)
            for k, v in stage_times.items()},
        "wire": {k: {"dense_bytes": w.dense_bytes,
                     "live_bytes": w.live_bytes,
                     "reduction_vs_ref": w.reduction_vs_ref}
                 for k, w in wires.items()},
        "ref_exchange_bytes": ref_bytes,
        # the live-byte win REALIZED on the wire (vs merely accounted)
        "ragged": {
            "cap": cap, "drops": int(drops),
            "exchanged_bytes": ragged_bytes,
            "padding_fraction": a2av.padding_fraction,
            "live_bytes": wires["cache_bf16"].live_bytes,
            "dense_bytes": wires["cache_bf16"].dense_bytes,
            "bytes_vs_live": ragged_bytes /
            max(wires["cache_bf16"].live_bytes, 1),
        },
        # what exchange="auto" statically resolves to at this scale
        "auto_exchange": {
            "cache": "ragged" if D.resolve_exchange(
                "auto", use_cache=True, cap=cap,
                dense_rows=dense_rows)[0] else "dense",
            "cache0": "ragged" if D.resolve_exchange(
                "auto", use_cache=False, cap=0,
                dense_rows=dense_rows)[0] else "dense",
        },
    }
    if csv:
        for k, v in stage_times.items():
            th = payload["stage_throughput"][k]
            print(f"dlrm/fused_stage_{k},{v*1e6:.1f},lookup+exchange "
                  f"rows/s={th['rows_per_s']:.0f} "
                  f"gb/s={th['pooled_gb_per_s']:.3f}")
        print(f"dlrm/fused_hit_rate,{hit_rate:.3f},"
              f"powerlaw_hetero cache_rows={cache_rows}")
        for k, w in wires.items():
            print(f"dlrm/wire_{k},{w.live_bytes},"
                  f"reduction={w.reduction_vs_ref:.2f}")
        r = payload["ragged"]
        print(f"dlrm/ragged_exchanged_bytes,{r['exchanged_bytes']},"
              f"cap={cap} x{r['bytes_vs_live']:.2f}_of_live "
              f"drops={r['drops']}")
    return payload


def _exchange_sweep_payload(batch=64, cache_rows=16, reps=5, trials=6):
    """Mono-vs-ring exchange sweep over the fused wire (DESIGN.md §7),
    run INSIDE a forced-multi-device subprocess (see
    ``exchange_pipeline_sweep``): for every codec × exchange mode, time
    the jitted k=0 distributed step under both pipelines (interleaved
    min-of-trials), assert ring output BIT-identical to mono, and record
    the fused buffer's exchanged bytes + GB/s.  P is whatever the forced
    host platform provides."""
    from repro import compat
    from repro.runtime.straggler import CapAutotuner
    from repro.sharding import partition

    p = len(jax.devices())
    cfg = cb.get_arch("dlrm-kaggle").smoke()
    mesh = compat.make_mesh((1, p), ("data", "model"))
    params = D.init_dlrm(jax.random.PRNGKey(0), cfg, n_shards=p)
    t_pad = D.padded_tables(cfg, p)
    b = S.make_batch(cfg, batch, mode="powerlaw_hetero", seed=0,
                     t_pad=t_pad)
    dense, idx, mask = map(jnp.asarray, (b.dense, b.idx, b.mask))
    cache = HC.build_from_batch(params["tables"], b.idx, b.mask,
                                cache_rows)
    bs, t_loc = batch // p, t_pad // p
    out = {"p": p, "batch": batch, "configs": {}}
    with partition.axis_rules(mesh):
        # autotune the ragged cap from this batch's live counts, exactly
        # as the serving engine would
        _, diag = jax.jit(lambda pr, d, i, m: D.forward_distributed(
            pr, cfg, d, i, m, cache=cache, exchange="ragged",
            return_diag=True))(params, dense, idx, mask)
        tuner = CapAutotuner()
        tuner.observe(int(diag.live_max), 0)
        cap = tuner.recommend(dense_rows=bs * t_loc).cap
        for wire in ("float32", "bfloat16", "int8"):
            for ex in ("dense", "ragged"):
                fns = {}
                for pipe in ("mono", "ring"):
                    fns[pipe] = jax.jit(
                        lambda pr, d, i, m, w=wire, ex=ex, pipe=pipe:
                        D.forward_distributed(
                            pr, cfg, d, i, m, cache=cache, wire_dtype=w,
                            exchange=ex, ragged_cap=cap,
                            exchange_pipeline=pipe))
                outs = {k: f(params, dense, idx, mask)
                        for k, f in fns.items()}
                parity = bool(jnp.array_equal(outs["mono"], outs["ring"]))
                times = _best_paired(fns, params, dense, idx, mask,
                                     reps=reps, trials=trials)
                layout = A2A.exchange_wire_layout(
                    ragged=ex == "ragged", n_dest=p, cap=cap, bs=bs,
                    t_loc=t_loc, embed_dim=cfg.embed_dim, wire_dtype=wire,
                    emb_dtype=params["tables"].dtype)
                # the own-destination chunk never crosses the wire (the
                # ring skips it entirely; the all_to_all loops it back)
                cross = layout.wire_bytes * (p - 1) // p
                out["configs"][f"{ex}_{wire}"] = {
                    "cap": cap if ex == "ragged" else 0,
                    "ring_equals_mono": parity,
                    "wire_bytes": layout.wire_bytes,
                    "cross_bytes": cross,
                    "stage_us": {k: v * 1e6 for k, v in times.items()},
                    "exchanged_gb_per_s": {
                        k: cross / v / 1e9 for k, v in times.items()},
                    "ring_vs_mono": times["ring"] / times["mono"],
                }
    return out


def exchange_pipeline_sweep(device_counts=(2, 4, 8)):
    """Run :func:`_exchange_sweep_payload` once per P in a subprocess
    with ``--xla_force_host_platform_device_count=P`` (the parent
    process has already locked its device count).  Returns {P: payload}
    for the BENCH_dlrm.json ``exchange_pipeline`` key."""
    here = os.path.abspath(__file__)
    out = {}
    for p in device_counts:
        env = dict(os.environ)
        # append to (not replace) inherited flags, so the sweep runs
        # under the same XLA configuration as every other bench section
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={p}").strip()
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(here), "..", "src"),
             env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
        r = subprocess.run([sys.executable, here, "--exchange-sweep"],
                           env=env, capture_output=True, text=True,
                           timeout=900)
        if r.returncode != 0:
            raise RuntimeError(
                f"exchange sweep at P={p} failed:\n{r.stdout}\n{r.stderr}")
        out[str(p)] = json.loads(r.stdout.strip().splitlines()[-1])
    return out


def exchange_smoke(p=4, max_ratio=1.2):
    """CI gate (``make bench-smoke``): at smoke scale the ring-pipelined
    exchange must be BIT-identical to the monolithic fused exchange for
    EVERY codec × exchange mode, and its k=0 stage time must stay within
    ``max_ratio`` of mono's across the sweep.  The time clause gates the
    GEOMETRIC MEAN of the per-config ring/mono ratios: single configs run
    ~4 ms on a shared CI host and their individual ratios swing ±50% run
    to run, while the mean over the six configs is stable (interleaved
    min-of-trials inside, like every paired gate here)."""
    sweep = exchange_pipeline_sweep(device_counts=(p,))[str(p)]
    ratios = []
    for name, c in sweep["configs"].items():
        assert c["ring_equals_mono"], \
            f"ring diverged from mono bitwise on {name}"
        ratios.append(c["ring_vs_mono"])
        print(f"bench-smoke OK: {name} ring bit-exact, "
              f"{c['ring_vs_mono']:.2f}x mono "
              f"(wire {c['wire_bytes']}B/member)")
    gmean = float(np.exp(np.mean(np.log(ratios))))
    assert gmean <= max_ratio, (
        f"ring regressed past {max_ratio}x mono at smoke scale: "
        f"geomean {gmean:.2f}x over {len(ratios)} configs {ratios}")
    print(f"bench-smoke OK: ring {gmean:.2f}x mono "
          f"(geomean over {len(ratios)} exchange configs)")


def git_sha() -> str:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            text=True).strip()
    except Exception:
        return "unknown"


def write_bench_json(payload: dict, path: str = "BENCH_dlrm.json") -> str:
    """Append this run's payload to ``path`` keyed by git SHA."""
    data = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except Exception:
            data = {}
    data[git_sha()] = payload
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def run(csv=True):
    st = measure_stages()
    st_thru = st.pop("throughput")
    if csv:
        for k, v in st.items():
            tail = "measured"
            if k in st_thru:
                tail += (f" rows/s={st_thru[k]['rows_per_s']:.0f}"
                         f" gb/s={st_thru[k]['pooled_gb_per_s']:.3f}")
            print(f"dlrm/stage_{k},{v*1e6:.1f},{tail}")
    # drive the paper's experiments with the measured stage times
    rng_wire = st["t_emb"] * 0.5  # exchange ~ half the lookup time
    rows = []
    for setting, kw in [
        ("measured_balanced", {}),
        ("measured_delays", {"delay_max": 2 * st["t_full"]}),
        ("measured_hetero", {"hetero_wire": 2.0}),
    ]:
        from repro.core.schedule_sim import make_workload
        w = make_workload(8, 300, t_emb=st["t_emb"], t_bot=st["t_bot"],
                          t_top=st["t_top"], t_wire=rng_wire, seed=0, **kw)
        for k in (0, 4):
            r = simulate(w, k)
            rows.append((setting, k, r.mean_latency, r.throughput))
            if csv:
                print(f"dlrm/{setting}_k{k},{r.mean_latency*1e6:.1f},"
                      f"thru={r.throughput:.1f}")
    # ring memory overhead at the paper's config (b=512, 26 tables, s=64B)
    from repro.core.bls import memory_overhead_bytes
    ring_payload = jax.ShapeDtypeStruct((512, 26, 16), jnp.float32)
    side = jax.ShapeDtypeStruct((512, 16), jnp.float32)
    per_k = memory_overhead_bytes(ring_payload, side, 1)
    if csv:
        print(f"dlrm/ring_bytes_per_k,{per_k},paper_says_~860KB")
    fused = measure_fused(csv=csv)
    # mono-vs-ring fused-wire sweep (DESIGN.md §7), one subprocess per P
    sweep = exchange_pipeline_sweep()
    if csv:
        for p, pay in sweep.items():
            for name, c in pay["configs"].items():
                print(f"dlrm/exchange_p{p}_{name}_mono,"
                      f"{c['stage_us']['mono']:.1f},"
                      f"gb/s={c['exchanged_gb_per_s']['mono']:.3f}")
                print(f"dlrm/exchange_p{p}_{name}_ring,"
                      f"{c['stage_us']['ring']:.1f},"
                      f"ratio={c['ring_vs_mono']:.2f} "
                      f"parity={c['ring_equals_mono']}")
    return {
        "stages_us": {k: v * 1e6 for k, v in st.items()},
        "stages_throughput": st_thru,
        "sim": [{"setting": s_, "bound": k, "mean_latency_us": lat * 1e6,
                 "throughput": thr} for s_, k, lat, thr in rows],
        "ring_bytes_per_k": per_k,
        "fused": fused,
        "exchange_pipeline": sweep,
    }


def stream_parity_smoke():
    """CI gate (``make bench-smoke``): the DMA-streamed embedding-bag
    kernel must match the VMEM-resident kernel within f32 tolerance —
    including a non-divisible batch and block-boundary row ids — so the
    streamed path can't silently diverge, and both must match the jnp
    reference bit-for-bit in f32 (interpret mode)."""
    from repro.kernels import ops, ref
    t, r, s, b, hot, rb = 2, 1000, 16, 37, 3, 192
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    tbl = jax.random.normal(ks[0], (t, r, s))
    idx = jax.random.randint(ks[1], (b, t, hot), 0, r)
    # hit the block boundaries: first/last row of a block, last table row
    idx = idx.at[0, 0, 0].set(0).at[1, 0, 1].set(rb - 1) \
             .at[2, 1, 0].set(rb).at[3, 1, 2].set(r - 1)
    mask = (jax.random.uniform(ks[2], (b, t, hot)) < 0.7) \
        .astype(jnp.float32)
    want = ref.embedding_bag_stacked_ref(tbl, idx, mask)
    resident = ops.embedding_bag_stacked_op(tbl, idx, mask, row_block=-1,
                                            impl=_kernel_impl())
    streamed = ops.embedding_bag_stacked_op(tbl, idx, mask, row_block=rb,
                                            impl=_kernel_impl())
    d = float(jnp.max(jnp.abs(np.asarray(streamed) - np.asarray(resident))))
    assert d <= 1e-6, f"streamed kernel diverged from resident by {d}"
    assert np.array_equal(np.asarray(streamed), np.asarray(want)), \
        "streamed kernel not bit-identical to the f32 jnp reference"
    print(f"bench-smoke OK: streamed-vs-resident max|d|={d:.1e} "
          f"(rows={r} row_block={rb} batch={b})")


def vector_pool_smoke():
    """CI gate (``make bench-smoke``): the vector pool (DESIGN.md §1) must
    match the scalar pool bit-for-bit in f32 — resident kernel AND the
    streamed DMA pipeline — and must not regress past 1.2x the scalar
    stage time at the smoke size (it should be well under 1x: the scalar
    walk is one row per iteration)."""
    from repro.kernels import ops, ref
    from repro.kernels import embedding_bag as eb
    # large enough that the pooling loop (not fixed call overhead)
    # dominates the stage time, so the ratio gate measures the loops
    t, r, s, b, hot, rb = 2, 1000, 32, 129, 8, 192
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    tbl = jax.random.normal(ks[0], (t, r, s))
    idx = jax.random.randint(ks[1], (b, t, hot), 0, r)
    idx = idx.at[0, 0, 0].set(0).at[1, 0, 1].set(rb - 1) \
             .at[2, 1, 0].set(rb).at[3, 1, 2].set(r - 1)
    mask = (jax.random.uniform(ks[2], (b, t, hot)) < 0.7) \
        .astype(jnp.float32)
    want = ref.embedding_bag_stacked_ref(tbl, idx, mask)
    fns = {
        "resident_scalar": jax.jit(lambda i, m: ops.embedding_bag_stacked_op(
            tbl, i, m, row_block=-1, pool_mode="scalar",
            impl=_kernel_impl())),
        "resident_vector": jax.jit(lambda i, m: ops.embedding_bag_stacked_op(
            tbl, i, m, row_block=-1, pool_mode="vector",
            impl=_kernel_impl())),
        # the real DMA pipeline in both pool modes (interpret machinery
        # executes the async-copy schedule standalone)
        "streamed_scalar": lambda i, m: eb.embedding_bag_stacked(
            tbl, i, m, row_block=rb, pool_mode="scalar", interpret=True,
            dma=True),
        "streamed_vector": lambda i, m: eb.embedding_bag_stacked(
            tbl, i, m, row_block=rb, pool_mode="vector", interpret=True,
            dma=True),
    }
    for name, fn in fns.items():
        got = np.asarray(fn(idx, mask))
        assert np.array_equal(got, np.asarray(want)), \
            f"{name} pool diverged from the f32 jnp reference"
    # the streamed interpret-mode pair runs ~0.6 s/call and its ratio
    # swings past the gate maybe one run in two at 4 trials on a loaded
    # host — 8 interleaved trials give the min filter enough samples
    times = _best_paired(fns, idx, mask, reps=2, trials=8)
    for form in ("resident", "streamed"):
        ratio = times[f"{form}_vector"] / times[f"{form}_scalar"]
        assert ratio <= 1.2, (
            f"vector pool regressed past 1.2x scalar on the {form} "
            f"kernel: {ratio:.2f}x "
            f"({times[f'{form}_vector']*1e6:.0f}us vs "
            f"{times[f'{form}_scalar']*1e6:.0f}us)")
        print(f"bench-smoke OK: {form} vector pool bit-exact, "
              f"{ratio:.2f}x scalar stage time")


def smoke(batch=64, cache_rows=16):
    """CI gate (``make bench-smoke``): at tiny scale the ragged exchange
    must (a) drop nothing at the autotuned cap, (b) physically move fewer
    bytes than the dense butterfly whenever the hot cache absorbs >= 90%
    of lookups, and (c) resolve ``auto`` to dense when the cache is off —
    plus the streamed-vs-resident kernel parity gate
    (:func:`stream_parity_smoke`) and the scalar-vs-vector pool parity +
    regression gate (:func:`vector_pool_smoke`)."""
    p = measure_fused(batch=batch, cache_rows=cache_rows, csv=False)
    r = p["ragged"]
    assert r["drops"] == 0, f"autotuned cap dropped rows: {r}"
    if p["hit_rate"] >= 0.9:
        assert r["exchanged_bytes"] < r["dense_bytes"], (
            f"ragged moved {r['exchanged_bytes']}B >= dense "
            f"{r['dense_bytes']}B at hit rate {p['hit_rate']:.2f}")
    assert p["auto_exchange"]["cache0"] == "dense", p["auto_exchange"]
    print(f"bench-smoke OK: hit_rate={p['hit_rate']:.2f} cap={r['cap']} "
          f"ragged_bytes={r['exchanged_bytes']} "
          f"dense_bytes={r['dense_bytes']} "
          f"(x{r['bytes_vs_live']:.2f} of live)")
    stream_parity_smoke()
    vector_pool_smoke()
    exchange_smoke()


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-scale CI gate instead of the full run")
    ap.add_argument("--exchange-sweep", action="store_true",
                    help="internal: run the mono-vs-ring sweep in THIS "
                         "process (spawned with forced host devices by "
                         "exchange_pipeline_sweep) and print its JSON")
    args = ap.parse_args(argv)
    if args.exchange_sweep:
        print(json.dumps(_exchange_sweep_payload()))
    elif args.smoke:
        smoke()
    else:
        write_bench_json(run())


if __name__ == "__main__":
    main()
