"""Kernel-level benchmarks: the XLA chunked implementations vs their exact
recurrent oracles on this host (wall time), plus the VMEM accounting that
motivates the Pallas versions on TPU."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def _kernel_impl() -> str:
    """Kernels compile natively on TPU; elsewhere the interpreter runs
    them."""
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


def _timeit(fn, *args, reps=5):
    fn(*args)
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps * 1e6


def bench_wkv(csv=True):
    from repro.models.rwkv6 import wkv_chunked, wkv_recurrent
    b, s, h, K = 2, 512, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    r = jax.random.normal(ks[0], (b, s, h, K))
    k = jax.random.normal(ks[1], (b, s, h, K))
    v = jax.random.normal(ks[2], (b, s, h, K))
    lw = -jnp.exp(jax.random.normal(ks[3], (b, s, h, K)))
    u = jax.random.normal(ks[4], (h, K)) * 0.5
    s0 = jnp.zeros((b, h, K, K))
    t_rec = _timeit(jax.jit(lambda *a: wkv_recurrent(*a)[0]),
                    r, k, v, lw, u, s0)
    rows = [("recurrent", t_rec)]
    if csv:
        print(f"kernels/wkv_recurrent_s{s},{t_rec:.0f},exact_scan")
    for chunk in (16, 32, 64):
        t = _timeit(jax.jit(lambda *a, c=chunk: wkv_chunked(*a, chunk=c)[0]),
                    r, k, v, lw, u, s0)
        rows.append((f"chunk{chunk}", t))
        if csv:
            # decay-tensor bytes the Pallas kernel keeps in VMEM instead
            hbm = b * h * (s // chunk) * chunk * chunk * K * 4
            print(f"kernels/wkv_chunk{chunk}_s{s},{t:.0f},"
                  f"xla_decay_tensor_bytes={hbm}")
    return rows


def bench_ssd(csv=True):
    from repro.models.mamba2 import ssd_chunked, ssd_recurrent
    b, s, nh, p, n = 2, 512, 8, 64, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (b, s, nh, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh)))
    B = jax.random.normal(ks[2], (b, s, n))
    C = jax.random.normal(ks[3], (b, s, n))
    A_log = jax.random.normal(ks[4], (nh,)) * 0.5
    D = jnp.ones((nh,))
    st = jnp.zeros((b, nh, p, n))
    t_rec = _timeit(jax.jit(lambda *a: ssd_recurrent(*a)[0]),
                    x, dt, A_log, B, C, D, st)
    if csv:
        print(f"kernels/ssd_recurrent_s{s},{t_rec:.0f},exact_scan")
    for chunk in (32, 64, 128):
        t = _timeit(jax.jit(lambda *a, c=chunk: ssd_chunked(*a, chunk=c)[0]),
                    x, dt, A_log, B, C, D, st)
        if csv:
            print(f"kernels/ssd_chunk{chunk}_s{s},{t:.0f},chunk_parallel")


def bench_dot_interaction(csv=True):
    from repro.kernels.ref import dot_interaction_ref
    z = jax.random.normal(jax.random.PRNGKey(2), (1024, 27, 64))
    t = _timeit(jax.jit(dot_interaction_ref), z)
    if csv:
        print(f"kernels/dot_interaction_b1024,{t:.0f},xla_ref")


def _pool_throughput(batch: int, hot: int, s: int, us: float) -> dict:
    """Scale-independent pooled-lookup throughput: gathered rows/s and the
    GB/s those weighted (row, s) f32 tiles amount to — so cross-SHA entry
    comparisons survive batch/shape changes."""
    gathered = batch * hot
    sec = us / 1e6
    return {"rows_per_s": gathered / sec if sec else 0.0,
            "pooled_gb_per_s": gathered * s * 4 / sec / 1e9 if sec else 0.0}


def bench_embedding_bag(csv=True, batch=128):
    """Embedding-bag sweep (rows × s × hot): jnp reference vs the
    VMEM-resident kernel (scalar AND vector pool, DESIGN.md §1) vs the
    DMA-streamed kernel.

    Off-TPU the kernels run in interpret mode, so the wall times are a
    same-code-path proxy, not TPU numbers — but the sweep pins the perf
    trajectory: the vector pool must stay at or under the scalar walk at
    resident sizes, the streamed kernel must stay near the resident kernel
    at VMEM-resident sizes (no regression where streaming isn't needed)
    and must RUN at R = 256k, where the resident kernel's table block
    exceeds the VMEM budget and fails loudly."""
    from repro.kernels import ops, ref
    from repro.kernels.embedding_bag import (RESIDENT_VMEM_BYTES,
                                             auto_row_block, fits_resident)
    entries = []
    for rows, s, hot in [(1024, 64, 4), (16384, 64, 1), (16384, 16, 4),
                         (16384, 64, 4), (262144, 64, 4)]:
        ks = jax.random.split(jax.random.PRNGKey(rows + hot), 3)
        tbl = jax.random.normal(ks[0], (1, rows, s))
        idx = jax.random.randint(ks[1], (batch, 1, hot), 0, rows)
        mask = (jax.random.uniform(ks[2], (batch, 1, hot)) < 0.8) \
            .astype(jnp.float32)
        resident_ok = fits_resident(rows, s, 4)
        # the streamed kernel at ITS auto block height everywhere: 1-2
        # blocks at VMEM-resident sizes (streaming's fixed cost where
        # streaming isn't needed), a real multi-block stream past them
        rb = auto_row_block(rows)
        fns = {"ref": lambda: ops.embedding_bag_stacked_op(
                   tbl, idx, mask, impl="ref"),
               "streamed": lambda: ops.embedding_bag_stacked_op(
                   tbl, idx, mask, row_block=rb, impl=_kernel_impl())}
        if resident_ok:
            # resident kernel in BOTH pool modes: the scalar-vs-vector
            # A/B the pool_mode knob exists for ('resident' = vector,
            # what 'auto' dispatches)
            fns["resident"] = lambda: ops.embedding_bag_stacked_op(
                tbl, idx, mask, row_block=-1, pool_mode="vector",
                impl=_kernel_impl())
            fns["resident_scalar"] = lambda: ops.embedding_bag_stacked_op(
                tbl, idx, mask, row_block=-1, pool_mode="scalar",
                impl=_kernel_impl())
        for fn in fns.values():
            fn()                                   # compile off the clock
        # interleaved min-of-trials (the bench_dlrm._best_paired idea): a
        # load spike taxes every candidate equally instead of biasing
        # whichever ran under it
        times = {name: float("inf") for name in fns}
        for _ in range(4):
            for name, fn in fns.items():
                times[name] = min(times[name], _timeit(fn, reps=3))
        entry = {"rows": rows, "s": s, "hot": hot, "row_block": rb,
                 "us": dict(times),
                 "throughput": {name: _pool_throughput(batch, hot, s, t)
                                for name, t in times.items()}}
        if resident_ok:
            entry["streamed_vs_resident"] = times["streamed"] / \
                times["resident"]
            entry["vector_vs_scalar"] = times["resident"] / \
                times["resident_scalar"]
        else:
            entry["resident"] = "exceeds_vmem"     # R·s·4 B > budget
            try:
                ops.embedding_bag_stacked_op(tbl, idx, mask, row_block=-1,
                                             impl=_kernel_impl())
                raise AssertionError("resident kernel accepted an "
                                     "oversized table block")
            except ValueError:
                pass
        entries.append(entry)
        if csv:
            tail = (f"streamed/resident={entry['streamed_vs_resident']:.2f}"
                    f" vector/scalar={entry['vector_vs_scalar']:.2f}"
                    if resident_ok else "resident=exceeds_vmem")
            gbs = entry["throughput"]["streamed"]["pooled_gb_per_s"]
            print(f"kernels/embag_r{rows}_s{s}_h{hot},"
                  f"{times['streamed']:.0f},{tail} gb_per_s={gbs:.3f}")
    return {"resident_vmem_bytes": RESIDENT_VMEM_BYTES, "batch": batch,
            "sweep": entries}


def bench_stream_plan(csv=True):
    """Stream-plan construction: the argsort builder vs the counting-sort
    builder (DESIGN.md §1) at L >= 8k indices — the plan sizes where the
    build cost matters.  The counting sort's O(L · nb) histogram +
    hierarchical rank must undercut the O(L log L) comparison sort."""
    from repro.kernels import embedding_bag as eb
    total = 262144
    entries = []
    for L, rb in [(8192, 8192), (8192, 4096), (32768, 8192)]:
        nbmax = min(-(-total // rb), L)
        gid = jax.random.randint(jax.random.PRNGKey(L + rb), (1, L), 0,
                                 total, dtype=jnp.int32)
        fns = {m: jax.jit(lambda g, m=m, rb=rb, nbmax=nbmax:
                          eb._stream_plan(g, rb, total, nbmax, m))
               for m in ("sort", "count")}
        for fn in fns.values():
            fn(gid)                                # compile off the clock
        times = {m: float("inf") for m in fns}
        for _ in range(6):                         # interleaved min-of-trials
            for m, fn in fns.items():
                times[m] = min(times[m], _timeit(fn, gid, reps=3))
        entry = {"L": L, "row_block": rb,
                 "n_buckets": -(-total // rb),
                 "sort_us": times["sort"], "count_us": times["count"],
                 "count_vs_sort": times["count"] / times["sort"],
                 "auto_resolves": eb._resolve_plan_method(
                     "auto", L, -(-total // rb))}
        entries.append(entry)
        if csv:
            print(f"kernels/stream_plan_L{L}_nb{entry['n_buckets']},"
                  f"{times['count']:.0f},"
                  f"count/sort={entry['count_vs_sort']:.2f}")
    return {"total_rows": total, "sweep": entries}


def main():
    bench_wkv()
    bench_ssd()
    bench_dot_interaction()
    return {"embedding_bag": bench_embedding_bag(),
            "stream_plan": bench_stream_plan()}


if __name__ == "__main__":
    main()
