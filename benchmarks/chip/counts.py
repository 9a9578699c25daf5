"""Operations and bytes a DLRM request needs, counted from shapes.

These are the work the model defines, not what an implementation happens
to do: padding rows of a batch, padding tables, DMA'd blocks and
recomputation do not count, so a share computed from them reads the same
whatever implements a layer.

Per request, with F = T + 1 features of width s:
  bottom MLP   2 * sum(d_in * d_out)
  pooling      valid indices * s           one add per element of a row
  interaction  F * (F - 1) / 2 * s * 2     the lower triangle of z z^T
  top MLP      2 * sum(d_in * d_out)
Bias adds, ReLUs and the sigmoid are left out (under 0.1% of the total).

Useful bytes of the embedding-bag pooling, per request: each valid index
reads one row (s * itemsize) plus its index (4 B) and its weight (4 B), and
each real table writes one pooled row (s * itemsize).
"""
from __future__ import annotations

import numpy as np


def _mlp_flops(dims) -> int:
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def dense_flops(cfg: dict) -> int:
    """FLOPs of one request outside the pooling: both MLPs and the dot
    interaction."""
    t, s = len(cfg["table_sizes"]), cfg["embed_dim"]
    f = t + 1
    bot = (cfg["n_dense_features"], *cfg["bottom_mlp"])
    top = (f * (f - 1) // 2 + s, *cfg["top_mlp"])
    return _mlp_flops(bot) + f * (f - 1) * s + _mlp_flops(top)


def request_flops(cfg: dict, valid: np.ndarray) -> np.ndarray:
    """Model FLOPs of requests with ``valid`` indices each."""
    valid = np.asarray(valid, np.int64)
    return dense_flops(cfg) + valid * cfg["embed_dim"]


def pooling_bytes(cfg: dict, valid: np.ndarray) -> np.ndarray:
    """Useful HBM bytes of pooling requests with ``valid`` indices each."""
    s = cfg["embed_dim"]
    item = np.dtype(cfg["dtype"]).itemsize
    valid = np.asarray(valid, np.int64)
    return valid * (s * item + 8) + len(cfg["table_sizes"]) * s * item
