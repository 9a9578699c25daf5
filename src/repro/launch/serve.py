"""Production serving driver: DLRM CTR serving with the BLS pipeline (the
paper's deployment) or batched LM decode, on a mesh over every local device.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch dlrm-kaggle --smoke \
      --batches 10 --bound 4 --microbatches 8
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --smoke
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import base as cb
from repro.data import synthetic as S
from repro.launch.mesh import make_host_mesh
from repro.models import api, dlrm as D
from repro.serving.engine import DLRMEngine, LMEngine
from repro.sharding import partition


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batches", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--bound", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()

    spec = cb.get_arch(args.arch)
    cfg = spec.smoke() if args.smoke else spec.config

    if args.arch.startswith("dlrm"):
        # table-parallel over every local device: the engine serves the BLS
        # pipeline through the model-axis exchange, as deployed
        n_model = len(jax.devices())
        while args.batch_size % (args.microbatches * n_model):
            n_model //= 2
        mesh = make_host_mesh(model=n_model)
        params = D.init_dlrm(jax.random.PRNGKey(0), cfg, n_shards=n_model,
                             mesh=mesh)
        t_pad = D.padded_tables(cfg, n_model)
        eng = DLRMEngine(params, cfg, batch_size=args.batch_size,
                         bound=args.bound, microbatches=args.microbatches)
        with partition.axis_rules(mesh):
            for i in range(args.batches):
                b = S.make_batch(cfg, args.batch_size, mode="hetero", seed=3,
                                 step=i, t_pad=t_pad)
                for j in range(args.batch_size):
                    eng.submit(b.dense[j], b.idx[j], b.mask[j])
            eng.flush()
        print(f"served {eng.stats.requests} requests @ "
              f"{eng.stats.throughput_rps:,.0f} req/s "
              f"(bound={args.bound}, mb={args.microbatches})")
        print("monitor:", eng.recommend_bound().reason)
    else:
        params = api.init(jax.random.PRNGKey(0), cfg, 1)
        eng = LMEngine(params, cfg, max_len=64)
        prompts = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (4, 8)).astype(np.int32)
        out = eng.generate(prompts, args.tokens)
        print(f"generated {out.shape}; p50 "
              f"{eng.monitor.percentile(0.5)*1e3:.1f} ms/token")


if __name__ == "__main__":
    main()
