"""Chip smoke run: DLRM serving at the full width of ``dlrm-kaggle`` on TPU.

One process drives the normal serving path — ``ServingFrontend`` in front
of a BLS ``DLRMEngine`` (bound 2, 4 microbatches) over a model mesh —
with random weights from a seed, at the registered widths: 26 tables at the
registered cardinalities, s=64, bottom MLP 512-256-64, top MLP 512-256-1,
up to 100-hot bags (the paper's Setting 1 traffic).  Every served CTR is
checked against ``forward_local`` on the ``ref`` sparse backend (the
kernels/ref.py oracle) over the same batches on the same chip.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # only the four-chip phase: tables
                                      # sharded over model=4, BLS k=2 on
                                      # the ring exchange vs the k=0
                                      # engine vs the oracle

The numbers it prints are from a smoke run, not a benchmark.  It exits
non-zero, without the result line, when JAX finds no TPU or any phase
fails; on success the last line is the JSON result.  JAX's persistent
compilation cache goes to ``JAX_COMPILATION_CACHE_DIR`` when that is set,
else to ``.jax_cache`` inside the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))

from repro.configs import base as cb  # noqa: E402
from repro.data import synthetic as S  # noqa: E402
from repro.kernels import embedding_bag as eb  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import dlrm as D  # noqa: E402
from repro.serving.engine import DLRMEngine  # noqa: E402
from repro.serving.frontend import ServingFrontend  # noqa: E402
from repro.sharding import partition  # noqa: E402

BATCH = 512
N_BATCHES = 4
TOL = 1e-4          # f32 CTR bound, as examples/serve_dlrm_bls.py uses


def log(msg: str):
    print(f"[smoke] {msg}", flush=True)


def serve(frontend, batches):
    """Offer every request of ``batches`` to the frontend, pump after each,
    drain, and return (CTRs in request order, per-flush wall seconds)."""
    served, flush_s = [], []
    for b in batches:
        for i in range(b.dense.shape[0]):
            res = frontend.try_submit(b.dense[i], b.idx[i], b.mask[i])
            if not res.admitted:
                raise RuntimeError(f"request refused: {res.reason}")
            t0 = time.perf_counter()
            done = frontend.pump()
            if done:
                flush_s.append(time.perf_counter() - t0)
            served += done
    served += frontend.drain()
    st = frontend.stats
    n = sum(b.dense.shape[0] for b in batches)
    if not st.accounted or st.completed != n or st.shed or st.rejected:
        raise RuntimeError(f"frontend accounting broken: {st.to_dict()}")
    ctr = np.empty(n, np.float32)
    got = np.zeros(n, bool)
    for r in served:
        ctr[r.request_id], got[r.request_id] = r.ctr, True
    if not got.all():
        raise RuntimeError(f"{(~got).sum()} requests never came back")
    return ctr, flush_s


def engine_ctrs(params, cfg, mesh, batches, *, bound, microbatches, name):
    eng = DLRMEngine(params, cfg, batch_size=BATCH, bound=bound,
                     microbatches=microbatches)
    fe = ServingFrontend(eng, slo_s=3600.0, admission="none", shed=False)
    with partition.axis_rules(mesh):
        ctr, flush_s = serve(fe, batches)
    if not np.isfinite(ctr).all():
        raise RuntimeError(f"{name}: non-finite CTRs")
    steady = float(np.median(flush_s[1:])) if len(flush_s) > 1 else 0.0
    log(f"{name}: served {ctr.size} requests; flush wall s {flush_s} "
        f"(first includes compile); compile ~ {flush_s[0] - steady:.2f} s "
        f"(first minus steady median {steady:.4f} s)")
    return ctr


def oracle_ctrs(params, cfg, batches):
    """CTRs of ``forward_local`` on the ``ref`` backend, on the first chip
    from its own copy of the params (a sharded stack is gathered there for
    the oracle alone, and freed when it returns)."""
    ref_cfg = cfg.replace(sparse_backend="ref")
    local = params
    if len(params["tables"].sharding.device_set) > 1:
        local = jax.device_put(params, jax.devices()[0])
    fwd = jax.jit(lambda p, d, i, m: jax.nn.sigmoid(
        D.forward_local(p, ref_cfg, d, i, m)))
    return np.concatenate([np.asarray(fwd(local, jnp.asarray(b.dense),
                                          jnp.asarray(b.idx),
                                          jnp.asarray(b.mask)))
                           for b in batches])


def check(name, got, want):
    diff = float(np.max(np.abs(got - want)))
    log(f"max |CTR({name}) - CTR(oracle)| = {diff:.3e} (bound {TOL:.0e})")
    if not diff <= TOL:
        raise RuntimeError(f"{name} disagrees with the oracle: {diff:.3e}")
    return diff


def memory_report():
    for d in jax.devices():
        st = d.memory_stats() or {}
        log(f"{d}: peak_bytes_in_use {st.get('peak_bytes_in_use', -1):,} "
            f"bytes_in_use {st.get('bytes_in_use', -1):,}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        log(f"no TPU: JAX found {dev.platform} devices")
        return 1
    if len(devs) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices, "
            f"JAX found {len(devs)}")
        return 1
    log(f"smoke run (not a benchmark) on {len(devs)} x {dev.device_kind}")

    cfg = cb.get_arch("dlrm-kaggle").config
    p = args.chips
    mesh = make_host_mesh(model=p)
    t0 = time.perf_counter()
    params = D.init_dlrm(jax.random.PRNGKey(args.seed), cfg, n_shards=p,
                         mesh=mesh)
    jax.block_until_ready(params)
    tables = params["tables"]
    t_pad, r, s = tables.shape
    log(f"init {time.perf_counter() - t0:.2f} s: tables {tables.shape} "
        f"{tables.dtype}, {tables.nbytes:,} bytes, sharding "
        f"{tables.sharding.spec}")
    if p > 1:
        homes = sorted(sh.device.id for sh in tables.addressable_shards)
        shapes = {sh.data.shape for sh in tables.addressable_shards}
        log(f"table shards on devices {homes}: {shapes}")
        if len(set(homes)) != p or shapes != {(t_pad // p, r, s)}:
            raise RuntimeError("tables are not sharded one shard per chip")
    backend = D.resolve_sparse_backend(cfg.sparse_backend)
    streamed, rb = eb.resolve_row_block(r, s, tables.dtype.itemsize,
                                        cfg.row_block)
    log(f"sparse backend {backend}, regime "
        f"{'streamed' if streamed else 'resident'} row_block {rb}, "
        f"exchange pipeline {D.resolve_pipeline(cfg.exchange_pipeline, p)}")
    if backend != "pallas":
        raise RuntimeError(f"sparse backend resolved to {backend!r}")

    batches = [S.make_batch(cfg, BATCH, mode="hetero", t_pad=t_pad,
                            seed=args.seed + 1, step=i)
               for i in range(N_BATCHES)]
    want = oracle_ctrs(params, cfg, batches)
    bls = engine_ctrs(params, cfg, mesh, batches, bound=2, microbatches=4,
                      name="bls(k=2, mb=4)")
    check("bls", bls, want)
    if p > 1:
        sync = engine_ctrs(params, cfg, mesh, batches, bound=0,
                           microbatches=1, name="sync(k=0)")
        check("sync", sync, want)
        log(f"max |CTR(bls) - CTR(sync)| = "
            f"{float(np.max(np.abs(bls - sync))):.3e}")
    memory_report()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
